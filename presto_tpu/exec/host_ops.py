"""Host-side root stage: final Output/Sort/Limit over gathered results.

Reference parity: the single-partition ROOT STAGE — presto executes the
final ordering/limit of a query in one task over the gathered exchange
output (SURVEY.md §2.4 "GATHER", §3.5); it never distributes the root.

TPU-first rationale: a root-stage ORDER BY is tiny work (it runs over
the already-aggregated/filtered result) but XLA sort *lowerings* cost
tens of seconds to minutes of TPU compile time per shape
(multi-operand sorts are worst). Peeling root Output/Sort/Limit out of
the device program and running them in numpy on the gathered rows
removes every per-query root sort from the compile budget while leaving
in-fragment sorts (window functions, TopN inside subqueries, join
internals) on the device. Gated by session property
``host_root_stage`` (default true).

Only ``SortNode``s whose keys are plain column references peel — an
ORDER BY over a computed expression stays in the device program where
the expression engine lives.
"""

from __future__ import annotations

from typing import List, Optional, Tuple

import numpy as np

from presto_tpu import types as T
from presto_tpu.expr import ColumnRef
from presto_tpu.page import Block, Page
from presto_tpu.plan import nodes as N
from presto_tpu.utils import tracing


def orderable_np(data: np.ndarray, dtype: T.DataType) -> np.ndarray:
    """numpy mirror of ops.common.orderable_i64 (order-preserving int64
    image of a column; floats via the IEEE754 sign-magnitude trick)."""
    if dtype.name in ("double", "real"):
        f = np.asarray(data, np.float64).copy()
        f[f == 0] = 0.0  # -0.0 == +0.0 in SQL
        bits = f.view(np.int64)
        neg = bits < 0
        out = bits.copy()
        out[neg] = ~bits[neg] | np.int64(-(2 ** 63))
        return out
    return np.asarray(data).astype(np.int64)


def key_lanes_np(data: np.ndarray, dtype: T.DataType) -> List[np.ndarray]:
    """numpy mirror of ops.common.key_lanes: long decimals expand to
    [hi, lo-as-unsigned] int64 lanes, everything else is one
    orderable_np lane."""
    if dtype.is_long_decimal:
        d = np.asarray(data)
        return [
            d[..., 0].astype(np.int64),
            d[..., 1].astype(np.int64) ^ np.int64(-(2 ** 63)),
        ]
    return [orderable_np(data, dtype)]


def peel_host_ops(
    root: N.PlanNode,
) -> Tuple[N.PlanNode, List[N.PlanNode]]:
    """Split the plan into (device_root, host_ops).

    ``host_ops`` is the chain of peeled root nodes ordered OUTermost
    first; apply_host_ops applies them innermost first.
    """
    peeled: List[N.PlanNode] = []
    node = root
    while True:
        if isinstance(node, (N.OutputNode, N.LimitNode)):
            peeled.append(node)
            node = node.source
            continue
        if isinstance(node, N.SortNode) and all(
            isinstance(k.expr, ColumnRef) for k in node.keys
        ):
            peeled.append(node)
            node = node.source
            continue
        break
    return node, peeled


def apply_host_ops(
    page: Page,
    host_ops: List[N.PlanNode],
    rows_out: Optional[List[int]] = None,
) -> Page:
    """Apply peeled root nodes (innermost first) to a gathered page,
    entirely in numpy; returns a dense result page. ``rows_out``, when
    given, records the row count after each applied op (innermost
    first) for EXPLAIN ANALYZE."""
    import jax

    # Two-phase fetch for a device where each fetch is a sync and D2H
    # bytes cost (neither measured on the chip): 1 scalar fetch for the
    # live count,
    # device-side slices down to n rows, then ONE batched device_get of
    # the small slices (async dispatches pipeline; transfers batch).
    # A page that is ALREADY host-side (the speculative single-round-
    # trip materialization) skips the fetch entirely.
    with tracing.phase("fetch", site="host_ops"):
        n = int(page.num_valid)
        leaves = page.prefix_leaves(n)
        fetched = leaves if page.is_host else jax.device_get(leaves)
    cols = {}  # name -> (np_data, np_valid, dtype, dictionary)
    i = 0
    for name, blk in zip(page.names, page.blocks):
        if blk.dtype.is_map:
            # leaves: offsets[:n+1], then per child full flat data
            # (+valid). Host form = object array of per-row
            # (keys, values, values_valid) slice triples; the child
            # dictionaries ride the dictionary slot as a tuple.
            off = np.asarray(fetched[i])
            i += 1
            chd = []
            for ch in blk.children:
                d = np.asarray(fetched[i])
                i += 1
                if ch.valid is not None:
                    v = np.asarray(fetched[i])
                    i += 1
                else:
                    v = None
                chd.append((d, v))
            (kd, _), (vd, vv) = chd
            rows = np.empty(n, dtype=object)
            for r in range(n):
                lo, hi = off[r], off[r + 1]
                rows[r] = (
                    kd[lo:hi],
                    vd[lo:hi],
                    None if vv is None else vv[lo:hi],
                )
            if blk.valid is not None:
                valid = fetched[i]
                i += 1
            else:
                valid = np.ones(n, dtype=bool)
            cols[name] = (
                rows,
                valid,
                blk.dtype,
                tuple(ch.dictionary for ch in blk.children),
            )
            continue
        if blk.dtype.is_row:
            chd = []
            for ch in blk.children:
                d = np.asarray(fetched[i])
                i += 1
                if ch.valid is not None:
                    v = np.asarray(fetched[i])
                    i += 1
                else:
                    v = None
                chd.append((d, v))
            rows = np.empty(n, dtype=object)
            for r in range(n):
                rows[r] = tuple(
                    (d[r], True if v is None else bool(v[r]))
                    for d, v in chd
                )
            if blk.valid is not None:
                valid = fetched[i]
                i += 1
            else:
                valid = np.ones(n, dtype=bool)
            cols[name] = (
                rows,
                valid,
                blk.dtype,
                tuple(ch.dictionary for ch in blk.children),
            )
            continue
        if blk.offsets is not None:
            # array block leaves: offsets[:n+1] + full flat values.
            # Host form = object array of per-row value slices, so the
            # sort/limit/output permutations below index it natively.
            off = np.asarray(fetched[i])
            i += 1
            vals = np.asarray(fetched[i])
            i += 1
            rows = np.empty(n, dtype=object)
            for r in range(n):
                rows[r] = vals[off[r]: off[r + 1]]
            data = rows
        else:
            data = fetched[i]
            i += 1
        if blk.valid is not None:
            valid = fetched[i]
            i += 1
        else:
            valid = np.ones(n, dtype=bool)
        cols[name] = (data, valid, blk.dtype, blk.dictionary)

    for node in reversed(host_ops):
        if isinstance(node, N.SortNode):
            perm = _host_sort_perm(cols, node.keys, n)
            if node.limit is not None:
                perm = perm[: node.limit]
            cols = {
                name: (d[perm], v[perm], t, dic)
                for name, (d, v, t, dic) in cols.items()
            }
            n = len(perm)
        elif isinstance(node, N.LimitNode):
            n = min(n, node.count)
            cols = {
                name: (d[:n], v[:n], t, dic)
                for name, (d, v, t, dic) in cols.items()
            }
        elif isinstance(node, N.OutputNode):
            cols = {out: cols[src] for out, src in node.columns}
        else:  # pragma: no cover - peel_host_ops only emits the above
            raise AssertionError(f"unexpected host op {type(node).__name__}")
        if rows_out is not None:
            rows_out.append(n)

    import jax.numpy as jnp

    cap = max(n, 1)
    blocks = []
    names = []
    for name, (d, v, t, dic) in cols.items():
        if t.is_map:
            kdic, vdic = dic
            lengths = [len(d[r][0]) for r in range(n)]
            from presto_tpu.exec.staging import bucket_capacity

            offsets = np.zeros(cap + 1, np.int32)
            np.cumsum(lengths, out=offsets[1: n + 1])
            offsets[n + 1:] = offsets[n]
            total = int(offsets[n])
            # value-axis bucketing: exact flat lengths would make every
            # distinct entry total a fresh XLA input shape downstream
            # (same discipline as Block.from_pylist/_pad_flat_child)
            vcap = bucket_capacity(total)
            flat_k = np.zeros((vcap,), t.key.np_dtype)
            flat_v = np.zeros((vcap,), t.value.np_dtype)
            if total:
                flat_k[:total] = np.concatenate(
                    [np.asarray(d[r][0]) for r in range(n)]
                )
                flat_v[:total] = np.concatenate(
                    [np.asarray(d[r][1]) for r in range(n)]
                )
            has_vv = any(d[r][2] is not None for r in range(n))
            flat_vv = None
            if has_vv and total:
                flat_vv = np.zeros((vcap,), bool)
                flat_vv[:total] = np.concatenate(
                    [
                        np.ones(len(d[r][1]), bool)
                        if d[r][2] is None
                        else np.asarray(d[r][2])
                        for r in range(n)
                    ]
                )
            vpad = np.zeros(cap, bool)
            vpad[:n] = v[:n]
            valid = None if bool(np.all(v[:n])) else jnp.asarray(vpad)
            blocks.append(
                Block(
                    data=Block.placeholder_data(cap),
                    valid=valid,
                    dtype=t,
                    offsets=jnp.asarray(offsets),
                    children=(
                        Block(
                            data=jnp.asarray(flat_k),
                            valid=None,
                            dtype=t.key,
                            dictionary=kdic,
                        ),
                        Block(
                            data=jnp.asarray(flat_v),
                            valid=(
                                None
                                if flat_vv is None
                                else jnp.asarray(flat_vv)
                            ),
                            dtype=t.value,
                            dictionary=vdic,
                        ),
                    ),
                )
            )
            names.append(name)
            continue
        if t.is_row:
            children = []
            for fi, ((fname, ftype), fdic) in enumerate(
                zip(t.fields, dic)
            ):
                fd = np.zeros(
                    (cap,), dtype=ftype.np_dtype
                ) if not ftype.is_long_decimal else np.zeros(
                    (cap, 2), np.int64
                )
                fv = np.zeros(cap, bool)
                for r in range(n):
                    fd[r] = d[r][fi][0]
                    fv[r] = d[r][fi][1]
                children.append(
                    Block(
                        data=jnp.asarray(fd),
                        valid=(
                            None
                            if bool(np.all(fv[:n]))
                            else jnp.asarray(fv)
                        ),
                        dtype=ftype,
                        dictionary=fdic,
                    )
                )
            vpad = np.zeros(cap, bool)
            vpad[:n] = v[:n]
            valid = None if bool(np.all(v[:n])) else jnp.asarray(vpad)
            blocks.append(
                Block(
                    data=Block.placeholder_data(cap),
                    valid=valid,
                    dtype=t,
                    children=tuple(children),
                )
            )
            names.append(name)
            continue
        if t.is_array:
            # object array of per-row slices -> offsets + flat values
            lengths = [len(d[r]) for r in range(n)]
            offsets = np.zeros(cap + 1, np.int32)
            np.cumsum(lengths, out=offsets[1: n + 1])
            offsets[n + 1:] = offsets[n]
            flat = (
                np.concatenate([np.asarray(d[r]) for r in range(n)])
                if n and offsets[n]
                else np.zeros(0, t.element.np_dtype)
            )
            vpad = np.zeros(cap, bool)
            vpad[:n] = v[:n]
            valid = None if bool(np.all(v[:n])) else jnp.asarray(vpad)
            blocks.append(
                Block(
                    data=jnp.asarray(flat),
                    valid=valid,
                    dtype=t,
                    dictionary=dic,
                    offsets=jnp.asarray(offsets),
                )
            )
            names.append(name)
            continue
        pad = cap - len(d)
        if pad:
            # long-decimal columns are (n, 2) limb pairs — pad rows only
            d = np.concatenate(
                [d, np.zeros((pad,) + d.shape[1:], dtype=d.dtype)]
            )
            v = np.concatenate([v, np.zeros(pad, dtype=bool)])
        valid = None if bool(np.all(v[:n])) else jnp.asarray(v)
        blocks.append(
            Block(data=jnp.asarray(d), valid=valid, dtype=t, dictionary=dic)
        )
        names.append(name)
    return Page(
        blocks=tuple(blocks),
        num_valid=jnp.asarray(n, jnp.int32),
        names=tuple(names),
    )


def _host_sort_perm(cols, keys, n: int) -> np.ndarray:
    """Stable lexicographic permutation; SQL null placement (nulls last
    in ASC, first in DESC, unless overridden) — numpy mirror of
    ops.common.sort_order."""
    lex = []
    for k in reversed(list(keys)):
        name = k.expr.name
        d, v, t, dic = cols[name]
        lanes = key_lanes_np(d, t)
        if k.descending:
            lanes = [~img for img in lanes]
        nf = k.nulls_first if k.nulls_first is not None else k.descending
        null_rank = np.where(v, 0, -1 if nf else 1).astype(np.int64)
        lex.extend(reversed(lanes))
        lex.append(null_rank)
    if not lex:
        return np.arange(n)
    return np.lexsort(lex)
