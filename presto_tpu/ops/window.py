"""Window function kernel.

Reference parity: ``WindowOperator`` + window function registry
(row_number, rank, dense_rank, aggregate windows) — SURVEY.md §2.1,
BASELINE.json config "Window functions (rank/row_number OVER PARTITION
BY)".

TPU-first: one stable sort by (partition keys, order keys), then every
window function is a *segmented scan* — partition starts and peer-group
starts fall out of neighbour-compares, ranks are index arithmetic against
segment-start gathers, and running aggregates are cumulative sums with
the partition prefix subtracted (all O(n) vectorized, no per-partition
loops; SURVEY.md §7 step 3 "window (segmented scans)").

Default SQL frame semantics: with ORDER BY, aggregates run over RANGE
UNBOUNDED PRECEDING..CURRENT ROW (peers share the value of their last
peer row); without ORDER BY, over the whole partition. Output rows are
emitted in (partition, order) sorted order — row order between operators
is unspecified in SQL, the final ORDER BY governs.
"""

from __future__ import annotations

import dataclasses
from typing import List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp

from presto_tpu import types as T
from presto_tpu.expr import Expr, ExprLowerer
from presto_tpu.ops.common import boundaries, cumsum, sort_order
from presto_tpu.ops.sort import SortKey
from presto_tpu.page import Block, Page


@dataclasses.dataclass(frozen=True)
class WindowCall:
    """func in {row_number, rank, dense_rank, ntile, lag, lead,
    first_value, last_value, sum, count, avg, min, max}.

    ``offset`` is lag/lead's constant distance (ntile reuses it as the
    bucket count); ``default`` is lag/lead's constant fill for
    out-of-partition positions as a Literal/Cast Expr (None = SQL
    NULL)."""

    func: str
    arg: Optional[Expr]  # None for row_number/rank/dense_rank/count(*)
    out_name: str
    offset: int = 1
    default: Optional[Expr] = None
    #: aggregate frame: "range" = default RANGE UNBOUNDED..CURRENT ROW
    #: (peers share the last peer row's value); "rows" = ROWS
    #: UNBOUNDED..CURRENT ROW (each row sees its own prefix)
    frame: str = "range"

    def result_type(self) -> T.DataType:
        if self.func in ("row_number", "rank", "dense_rank", "count",
                         "ntile"):
            return T.BIGINT
        if self.func in ("percent_rank", "cume_dist"):
            return T.DOUBLE
        t = self.arg.dtype
        if self.func in ("lag", "lead", "first_value", "last_value",
                         "nth_value"):
            return t
        if self.func == "sum":
            if t.is_decimal:
                return T.decimal(18, t.scale)
            if t.is_integer:
                return T.BIGINT
            return T.DOUBLE
        if self.func == "avg":
            return T.DOUBLE
        if self.func in ("min", "max"):
            return t
        raise NotImplementedError(f"window function {self.func}")


def window(
    page: Page,
    partition_by: Sequence[Expr],
    order_by: Sequence[SortKey],
    calls: Sequence[WindowCall],
) -> Page:
    """Append window-function columns to ``page`` (sorted order output)."""
    cap = page.capacity
    live = page.row_mask()
    lowerer = ExprLowerer(page)
    part_eval = [(*lowerer.eval(e), e.dtype) for e in partition_by]
    order_eval = [
        (*lowerer.eval(k.expr), k.expr.dtype) for k in order_by
    ]

    perm = sort_order(
        part_eval + order_eval,
        live,
        descending=[False] * len(part_eval)
        + [k.descending for k in order_by],
        nulls_first=[False] * len(part_eval)
        + [
            k.nulls_first if k.nulls_first is not None else k.descending
            for k in order_by
        ],
    )
    live_s = live[perm]
    part_s = [(d[perm], None if v is None else v[perm]) for d, v, _ in part_eval]
    order_s = [(d[perm], None if v is None else v[perm]) for d, v, _ in order_eval]

    part_bnd = (
        boundaries(part_s, live_s)
        if part_s
        else (jnp.zeros((cap,), jnp.bool_).at[0].set(True) & live_s)
    )
    peer_bnd = boundaries(part_s + order_s, live_s) if order_s else part_bnd

    pos = jnp.arange(cap, dtype=jnp.int64)
    pid = jnp.cumsum(part_bnd.astype(jnp.int32)) - 1
    pid = jnp.where(live_s, pid, cap)  # dead rows -> dropped segment
    peer_gid = jnp.cumsum(peer_bnd.astype(jnp.int32)) - 1
    peer_gid = jnp.where(live_s, peer_gid, cap)

    nseg = cap + 1
    part_start = jax.ops.segment_min(pos, pid, num_segments=nseg)
    peer_start = jax.ops.segment_min(pos, peer_gid, num_segments=nseg)
    # last row position of each peer group (for RANGE frame value sharing)
    peer_end = jax.ops.segment_max(pos, peer_gid, num_segments=nseg)

    safe_pid = jnp.minimum(pid, cap)
    safe_peer = jnp.minimum(peer_gid, cap)

    names = list(page.names)
    for name, blk in zip(names, page.blocks):
        if blk.offsets is not None or blk.children is not None:
            # flat-values gather with stale offsets (arrays/maps) or a
            # permuted placeholder with unpermuted children (rows)
            # would silently corrupt nested columns
            raise NotImplementedError(
                f"nested column {name} ({blk.dtype}) cannot ride "
                "through a window operator; select it separately"
            )
    blocks = [
        dataclasses.replace(
            blk,
            data=blk.data[perm],
            valid=None if blk.valid is None else blk.valid[perm],
        )
        for blk in page.blocks
    ]

    # last live row position of each partition (lead bound, ntile size)
    part_end = jax.ops.segment_max(
        jnp.where(live_s, pos, -1), pid, num_segments=nseg
    )
    part_cnt = jax.ops.segment_sum(
        live_s.astype(jnp.int64), pid, num_segments=nseg
    )

    for call in calls:
        rt = call.result_type()
        if call.func == "row_number":
            # int32 lanes: ranks are bounded by the page capacity, so
            # the BIGINT-typed block carries int32 data — half the HBM
            # and half the result-transfer bytes on rank-heavy outputs
            data = (pos - part_start[safe_pid] + 1).astype(jnp.int32)
            blocks.append(Block(data=data, valid=None, dtype=T.BIGINT))
        elif call.func == "ntile":
            # SQL ntile: sizes differ by at most 1 and the FIRST
            # (m mod n) buckets take the extra row
            n_tiles = jnp.int64(max(int(call.offset), 1))
            rn0 = pos - part_start[safe_pid]
            m = jnp.maximum(part_cnt[safe_pid], 1)
            q = m // n_tiles
            r = m % n_tiles
            big = r * (q + 1)  # rows covered by the (q+1)-sized buckets
            data = jnp.where(
                rn0 < big,
                rn0 // jnp.maximum(q + 1, 1),
                r + (rn0 - big) // jnp.maximum(q, 1),
            ) + 1
            blocks.append(Block(data=data, valid=None, dtype=T.BIGINT))
        elif call.func in ("lag", "lead", "first_value", "last_value",
                           "nth_value"):
            blocks.append(
                _window_nav(
                    call, page, perm, live_s, safe_pid, part_start,
                    part_end, peer_end, safe_peer, pos, lowerer,
                )
            )
        elif call.func == "rank":
            data = (
                peer_start[safe_peer] - part_start[safe_pid] + 1
            ).astype(jnp.int32)
            blocks.append(Block(data=data, valid=None, dtype=T.BIGINT))
        elif call.func == "percent_rank":
            # (rank - 1) / (partition rows - 1); 0 for 1-row partitions
            rank0 = (
                peer_start[safe_peer] - part_start[safe_pid]
            ).astype(jnp.float64)
            denom = (part_cnt[safe_pid] - 1).astype(jnp.float64)
            data = jnp.where(denom > 0, rank0 / jnp.maximum(denom, 1.0), 0.0)
            blocks.append(Block(data=data, valid=None, dtype=T.DOUBLE))
        elif call.func == "cume_dist":
            # rows with position <= last peer row, over partition rows
            thru = (
                peer_end[safe_peer] - part_start[safe_pid] + 1
            ).astype(jnp.float64)
            data = thru / jnp.maximum(
                part_cnt[safe_pid].astype(jnp.float64), 1.0
            )
            blocks.append(Block(data=data, valid=None, dtype=T.DOUBLE))
        elif call.func == "dense_rank":
            first_peer_of_part = jax.ops.segment_min(
                peer_gid, pid, num_segments=nseg
            )
            data = peer_gid - first_peer_of_part[safe_pid] + 1
            blocks.append(
                Block(data=data.astype(jnp.int32), valid=None, dtype=T.BIGINT)
            )
        elif call.func in ("sum", "count", "avg", "min", "max"):
            blocks.append(
                _window_agg(
                    call,
                    page,
                    perm,
                    live_s,
                    pid,
                    safe_pid,
                    peer_gid,
                    safe_peer,
                    part_start,
                    peer_end,
                    pos,
                    running=bool(order_by),
                    nseg=nseg,
                    lowerer=lowerer,
                )
            )
        else:
            raise NotImplementedError(call.func)
        names.append(call.out_name)

    return Page(
        blocks=tuple(blocks), num_valid=page.num_valid, names=tuple(names)
    )


def _window_nav(
    call: WindowCall,
    page: Page,
    perm,
    live_s,
    safe_pid,
    part_start,
    part_end,
    peer_end,
    safe_peer,
    pos,
    lowerer: ExprLowerer,
) -> Block:
    """Navigation functions over the sorted layout: lag/lead by row
    offset within the partition; first_value at the partition start;
    last_value at the current frame end (default RANGE frame: the last
    peer row)."""
    cap = page.capacity
    at = call.arg.dtype
    d, v = lowerer.eval(call.arg)
    d = jnp.broadcast_to(d, (cap,))[perm]
    v_s = None if v is None else jnp.broadcast_to(v, (cap,))[perm]

    if call.func == "lag":
        src = pos - jnp.int64(call.offset)
        in_part = src >= part_start[safe_pid]
    elif call.func == "lead":
        src = pos + jnp.int64(call.offset)
        in_part = src <= part_end[safe_pid]
    elif call.func == "first_value":
        src = part_start[safe_pid].astype(jnp.int64)
        in_part = jnp.ones((cap,), jnp.bool_)
    elif call.func == "nth_value":
        # n-th row of the frame (default RANGE frame ends at the last
        # peer row): NULL until the frame has grown past n rows
        src = part_start[safe_pid].astype(jnp.int64) + jnp.int64(
            call.offset - 1
        )
        in_part = src <= peer_end[safe_peer]
    else:  # last_value: frame ends at the last peer row
        src = peer_end[safe_peer].astype(jnp.int64)
        in_part = jnp.ones((cap,), jnp.bool_)

    src_c = jnp.clip(src, 0, cap - 1).astype(jnp.int32)
    data = d[src_c]
    src_valid = in_part if v_s is None else (in_part & v_s[src_c])
    if call.default is not None and call.func in ("lag", "lead"):
        fd, _ = lowerer.eval(call.default)
        data = jnp.where(in_part, data, jnp.broadcast_to(fd, data.shape))
        src_valid = (
            jnp.ones((cap,), jnp.bool_)
            if v_s is None
            else jnp.where(in_part, src_valid, True)
        )
    valid = live_s & src_valid
    dictionary = None
    if at.is_string:
        dictionary = lowerer.dictionary_of(call.arg)
    return Block(
        data=data.astype(at.jnp_dtype), valid=valid, dtype=at,
        dictionary=dictionary,
    )


def _window_agg(
    call: WindowCall,
    page: Page,
    perm,
    live_s,
    pid,
    safe_pid,
    peer_gid,
    safe_peer,
    part_start,
    peer_end,
    pos,
    running: bool,
    nseg: int,
    lowerer: ExprLowerer = None,
):
    rt = call.result_type()
    if call.arg is not None:
        d, v = lowerer.eval(call.arg)
        d = jnp.broadcast_to(d, (page.capacity,))[perm]
        valid = live_s if v is None else (
            live_s & jnp.broadcast_to(v, (page.capacity,))[perm]
        )
    else:  # count(*)
        d = jnp.ones((page.capacity,), jnp.int64)
        valid = live_s

    at = call.arg.dtype if call.arg is not None else T.BIGINT
    is_float = (
        call.func == "avg" or at.name in ("double", "real")
    ) and call.func not in ("min", "max", "count")

    if is_float:
        x = d.astype(jnp.float64)
        if at.is_decimal:
            x = x / (10 ** at.scale)
        x = jnp.where(valid, x, 0.0)
    elif call.func == "count":
        x = valid.astype(jnp.int64)
    else:
        x = jnp.where(valid, d.astype(jnp.int64), 0)

    run_cnt = None
    if running:
        # running non-null count up to the frame end (shared by every
        # running aggregate's validity and by avg's divisor)
        cnt_cs = jnp.cumsum(valid.astype(jnp.int64))
        cnt_before = jnp.where(
            part_start[safe_pid] > 0,
            cnt_cs[jnp.maximum(part_start[safe_pid] - 1, 0)],
            jnp.zeros((), jnp.int64),
        )
        run_within = cnt_cs - cnt_before
        run_cnt = (
            run_within
            if call.frame == "rows"
            else run_within[peer_end[safe_peer]]
        )

    if call.func in ("min", "max"):
        if at.name in ("double", "real"):
            fill = jnp.inf if call.func == "min" else -jnp.inf
            xv = jnp.where(valid, d.astype(jnp.float64), fill)
        else:
            info = jnp.iinfo(jnp.int64)
            fill = info.max if call.func == "min" else info.min
            xv = jnp.where(valid, d.astype(jnp.int64), fill)
        if running:
            op = jnp.minimum if call.func == "min" else jnp.maximum

            # segmented cumulative min/max in O(log n) parallel depth
            def combine(a, b):
                ap, av = a
                bp, bv = b
                return bp, jnp.where(ap == bp, op(av, bv), bv)

            _, out = jax.lax.associative_scan(combine, (pid, xv))
            # RANGE: peers share the last peer row's value; ROWS: own
            data = (
                out
                if call.frame == "rows"
                else out[peer_end[safe_peer]]
            )
            has = run_cnt > 0
        else:
            seg = (
                jax.ops.segment_min if call.func == "min" else jax.ops.segment_max
            )(xv, pid, num_segments=nseg)
            data = seg[safe_pid]
            cnt_seg = jax.ops.segment_sum(
                valid.astype(jnp.int64), pid, num_segments=nseg
            )
            has = cnt_seg[safe_pid] > 0
        dictionary = None
        if at.is_string:
            dictionary = lowerer.dictionary_of(call.arg)
        return Block(
            data=data.astype(at.jnp_dtype),
            valid=has,
            dtype=at,
            dictionary=dictionary,
        )

    if running:
        cs = cumsum(x)
        before_part = jnp.where(
            part_start[safe_pid] > 0,
            cs[jnp.maximum(part_start[safe_pid] - 1, 0)],
            jnp.zeros((), cs.dtype),
        )
        within = cs - before_part
        # RANGE: peers share the last peer row's value; ROWS: own
        data = (
            within
            if call.frame == "rows"
            else within[peer_end[safe_peer]]
        )
        if call.func == "count":
            return Block(data=data.astype(jnp.int64), valid=None, dtype=T.BIGINT)
        if call.func == "avg":
            return Block(
                data=data / jnp.maximum(run_cnt, 1),
                valid=run_cnt > 0,
                dtype=T.DOUBLE,
            )
        # sum
        if is_float:
            return Block(data=data, valid=run_cnt > 0, dtype=T.DOUBLE)
        return Block(data=data.astype(jnp.int64), valid=run_cnt > 0, dtype=rt)

    # whole-partition aggregate
    seg = jax.ops.segment_sum(x, pid, num_segments=nseg)
    cnt_seg = jax.ops.segment_sum(
        valid.astype(jnp.int64), pid, num_segments=nseg
    )
    if call.func == "count":
        return Block(
            data=seg[safe_pid].astype(jnp.int64), valid=None, dtype=T.BIGINT
        )
    has = cnt_seg[safe_pid] > 0
    if call.func == "avg":
        return Block(
            data=seg[safe_pid] / jnp.maximum(cnt_seg[safe_pid], 1),
            valid=has,
            dtype=T.DOUBLE,
        )
    if is_float:
        return Block(data=seg[safe_pid], valid=has, dtype=T.DOUBLE)
    return Block(data=seg[safe_pid].astype(jnp.int64), valid=has, dtype=rt)
