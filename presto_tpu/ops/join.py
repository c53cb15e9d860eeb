"""Equi-join kernels: inner / left outer / full outer / semi / anti
(right outer is planned as left outer with the sides swapped).

Reference parity: ``HashBuilderOperator`` -> ``PagesIndex`` ->
``LookupSourceFactory`` bridged to ``LookupJoinOperator`` (+``JoinProbe``)
— the two-pipeline build/probe split of SURVEY.md §3.3.

TPU-first redesign (SURVEY.md §7 step 3): no pointer-chasing hash table.
The build side is *sorted by key* (XLA sort), and every probe row finds
its match range ``[lo, hi)`` in that order by RANK, not by search
(``_match_ranges``): build and probe keys are sorted together, build
rows leading every tie, and a running count of the build rows is the
range — three sort passes, a cumsum and a cummax, no loop and no gather.
The binary search it replaced (two ``jnp.searchsorted`` a join) is a
loop of log2(n) rounds, each a gather of one emulated-int64 key a probe
row, and on the v5e a gather is the dear primitive: ~28 ns an element,
17-20 ms for 2^20 rows, where a sort pass over as many rows with its
payloads takes 1.1-1.7 ms (PERF.md §6, PRs 35 and 36). Q3 at SF1 made
516 M such gathers a statement — its four ``while`` loops were 14.7 s of
the 18.5 s the device worked (ledger, PR 35, ``sf1_join``). For the
same reason a unique build's output passes the probe's columns through
instead of gathering them by an iota (XLA keeps that gather).
Duplicate build keys become [lo, hi) ranges; the output expansion is the
classic prefix-sum + inverse-rank trick (the same ``_match_ranges``),
entirely static-shape: the planner supplies ``out_capacity`` and the
kernel reports overflow (host re-runs at a bigger bucket), mirroring the
engine-wide capacity-bucket protocol (SURVEY.md §7 "Hard parts").

Keys are single int64 columns; the planner packs two int32-representable
key columns bijectively via ``pack_keys`` (wider composites: future
round). NULL keys never match (SQL equi-join); anti join keeps unmatched
probe rows (NOT EXISTS semantics — NOT IN null handling is a planner
rewrite). Join keys of exactly int64-max are unsupported (sentinel);
unreachable for real workloads.
"""

from __future__ import annotations

import dataclasses
from typing import List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp

from presto_tpu import types as T
from presto_tpu.ops.common import (
    _u32_lanes,
    argsort_i64,
    orderable_i64,
    sort_u32_lanes,
)
from presto_tpu.page import Block, Page

_I64_MAX = jnp.iinfo(jnp.int64).max


def pack_keys(a: jnp.ndarray, b: jnp.ndarray) -> jnp.ndarray:
    """Bijectively pack two int32-representable key columns into int64."""
    return (a.astype(jnp.int64) << 32) | (b.astype(jnp.int64) & 0xFFFFFFFF)


def _key_of(page: Page, key_cols: Sequence[str]) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """(int64 key, ok-mask) for live rows with non-null key columns."""
    ok = page.row_mask()
    datas = []
    widths = []
    for name in key_cols:
        blk = page.block(name)
        if blk.dtype.is_long_decimal:
            # int128 limb pair -> one int64 via a splitmix64 fold. NOT
            # injective: the planner only emits a long-decimal kernel
            # key on INNER joins with a residual limb-equality filter
            # attached (JoinNode.residual), which removes any
            # mix-collision false match — collisions cost out_capacity,
            # never correctness (plan/planner.py long-decimal join path)
            d = jnp.asarray(blk.data)
            hi = d[..., 0].astype(jnp.uint64)
            z = hi + jnp.uint64(0x9E3779B97F4A7C15)
            z = (z ^ (z >> jnp.uint64(30))) * jnp.uint64(0xBF58476D1CE4E5B9)
            z = (z ^ (z >> jnp.uint64(27))) * jnp.uint64(0x94D049BB133111EB)
            z = z ^ (z >> jnp.uint64(31))
            mixed = (z ^ d[..., 1].astype(jnp.uint64)).astype(jnp.int64)
            datas.append(mixed)
        else:
            datas.append(orderable_i64(blk.data, blk.dtype))
        widths.append(blk.dtype.np_dtype.itemsize)
        if blk.valid is not None:
            ok = ok & blk.valid
    if len(datas) == 1:
        key = datas[0]
    elif len(datas) == 2:
        # pack is bijective only for 32-bit key columns; wider values
        # would wrap modulo 2^64 and silently collide. The planner must
        # cast bigint keys down (stats-bounded) before using a pair key.
        if any(w > 4 for w in widths):
            raise NotImplementedError(
                "two-column join keys must be 32-bit columns "
                f"(got widths {widths}); planner must narrow first"
            )
        key = pack_keys(datas[0], datas[1])
    else:
        raise NotImplementedError(
            ">2 join key columns (pack wider composites in the planner)"
        )
    return key, ok


def _mask_out(page: Page, keep: jnp.ndarray) -> Page:
    """Select rows of ``page`` lazily: keep them in place under a live
    mask (Page masked form) instead of nonzero+gather compaction — the
    downstream kernels all consume row_mask() (see ops.filter_project)."""
    return dataclasses.replace(
        page, live=keep, num_valid=jnp.sum(keep).astype(jnp.int32)
    )


def _match_ranges(
    build_keys: jnp.ndarray, probe_keys: jnp.ndarray
) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """For every probe key its range ``[lo, hi)`` among the build keys
    in sorted order: exactly ``searchsorted(sorted(build_keys),
    probe_keys, "left")`` and ``(..., "right")``, int32 — found by
    ranking, not by searching (the module docstring says why). No loop,
    no gather; the build keys need not arrive sorted.

    Both sides are concatenated, build first, and sorted stably by key
    with their origin index riding along, so the build rows lead every
    run of equal keys. The running count of build rows is then ``hi``
    at every probe row, and the count before the run of its key — the
    running maximum of the counts at the runs' first rows, which never
    decrease — is ``lo``. A sort on the origin brings both back to
    probe order (a sort, not a scatter: a scatter costs what a gather
    costs)."""
    nb = build_keys.shape[0]
    keys = jnp.concatenate([build_keys, probe_keys]).astype(jnp.int64)
    origin = jnp.arange(keys.shape[0], dtype=jnp.int32)
    (low, high), (origin,) = sort_u32_lanes(
        _u32_lanes(keys, False), (origin,)
    )
    is_build = (origin < nb).astype(jnp.int32)
    hi = jnp.cumsum(is_build)
    change = (low[1:] != low[:-1]) | (high[1:] != high[:-1])
    first = jnp.concatenate([jnp.ones((1,), jnp.bool_), change])
    lo = jax.lax.cummax(jnp.where(first, hi - is_build, 0))
    _, lo, hi = jax.lax.sort((origin, lo, hi), num_keys=1)
    return lo[nb:], hi[nb:]


def hash_join(
    probe: Page,
    build: Page,
    probe_keys: Sequence[str],
    build_keys: Sequence[str],
    join_type: str = "inner",
    build_payload: Optional[Sequence[str]] = None,
    build_unique: bool = False,
    out_capacity: Optional[int] = None,
    payload_rename: Optional[dict] = None,
) -> Tuple[Page, jnp.ndarray]:
    """Join ``probe`` with ``build`` on equality of packed keys.

    Returns (result, overflow). Result columns = all probe columns plus
    ``build_payload`` columns (optionally renamed via ``payload_rename``).
    join_type: inner | left | full | semi | anti.

    FULL OUTER executes as left outer plus an appended section of
    unmatched build rows (probe columns NULL) — the appended section
    rides the Page live-mask (masked form), so no compaction gather is
    paid for it.
    """
    build_payload = list(build_payload or [])
    payload_rename = payload_rename or {}

    for pc, bc in zip(probe_keys, build_keys):
        pb, bb = probe.block(pc), build.block(bc)
        if pb.dtype.is_string or bb.dtype.is_string:
            # ids are only comparable within ONE dictionary; the planner
            # re-encodes one side before a string-keyed join
            if pb.dictionary != bb.dictionary:
                raise NotImplementedError(
                    f"string join key {pc}={bc} across different "
                    "dictionaries: planner must re-encode first"
                )

    pk, p_ok = _key_of(probe, probe_keys)
    bk, b_ok = _key_of(build, build_keys)

    with jax.named_scope("join_build"):
        # sort build by key; unmatchable rows carry the sentinel and
        # sort last
        b_sort_key = jnp.where(b_ok, bk, _I64_MAX)
        b_order = argsort_i64(b_sort_key)
        nb = jnp.sum(b_ok).astype(jnp.int32)

    with jax.named_scope("join_probe"):
        pk_eff = jnp.where(p_ok, pk, _I64_MAX)
        lo, hi = _match_ranges(b_sort_key, pk_eff)
        lo = jnp.minimum(lo, nb)
        hi = jnp.minimum(hi, nb)
        m = jnp.where(p_ok, hi - lo, 0)  # matches per probe row

    if join_type == "semi":
        return _mask_out(probe, m > 0), jnp.asarray(False)
    if join_type == "anti":
        keep = (m == 0) & probe.row_mask()
        return _mask_out(probe, keep), jnp.asarray(False)

    with jax.named_scope("join_output"):
        if build_unique:
            # PK side: m in {0,1}; output row i IS probe row i (static!), so
            # the probe's columns pass through and only the build's payload
            # is gathered
            matched = m > 0
            b_idx = b_order[jnp.clip(lo, 0, build.capacity - 1)]
            out = _join_output(
                probe,
                build,
                None,
                b_idx,
                matched,
                build_payload,
                payload_rename,
                left_outer=(join_type in ("left", "full")),
            )
            if join_type == "inner":
                keep = matched & probe.row_mask()
                return _mask_out(out, keep), jnp.asarray(False)
            # left/full outer keep every probe row: positional layout, so
            # the probe's own liveness (mask or prefix) carries over
            out = dataclasses.replace(out, live=probe.live)
            if join_type == "full":
                out = _append_unmatched_build(
                    out, probe, build, pk_eff, p_ok, bk, b_ok,
                    build_payload, payload_rename,
                )
            return out, jnp.asarray(False)

        # general duplicate-capable expansion
        if out_capacity is None:
            raise ValueError(
                "non-unique inner/left join requires out_capacity"
            )
        m_eff = jnp.maximum(m, 1) if join_type in ("left", "full") else m
        m_eff = jnp.where(probe.row_mask(), m_eff, 0)
        total = jnp.cumsum(m_eff)
        out_count = (
            total[-1] if probe.capacity else jnp.asarray(0, jnp.int64)
        )
        overflow = out_count > out_capacity

        j = jnp.arange(out_capacity, dtype=jnp.int64)
        _, p_idx = _match_ranges(total, j)  # searchsorted(..., "right")
        p_idx = jnp.minimum(p_idx, probe.capacity - 1)
        prev = jnp.where(p_idx > 0, total[jnp.maximum(p_idx - 1, 0)], 0)
        offset = j - prev
        row_m = m[p_idx]
        matched = row_m > 0
        b_pos = lo[p_idx] + jnp.minimum(offset, jnp.maximum(row_m - 1, 0))
        b_idx = b_order[jnp.clip(b_pos, 0, build.capacity - 1)]
        out = _join_output(
            probe,
            build,
            p_idx,
            b_idx,
            matched,
            build_payload,
            payload_rename,
            left_outer=(join_type in ("left", "full")),
        )
        out = dataclasses.replace(
            out,
            num_valid=jnp.minimum(out_count, out_capacity).astype(jnp.int32),
        )
        if join_type == "full":
            out = _append_unmatched_build(
                out, probe, build, pk_eff, p_ok, bk, b_ok,
                build_payload, payload_rename,
            )
        return out, overflow


def cross_join(
    left: Page, right: Page, out_capacity: int
) -> Tuple[Page, jnp.ndarray]:
    """General nested-loop cross product (reference:
    NestedLoopJoinOperator — SURVEY.md §2.1 "Operators"). Static-shape:
    the same prefix-sum + inverse-searchsorted expansion the
    duplicate-key equi-join uses, with every live left row matching
    every live right row. Returns (result, overflow) under the engine's
    capacity-bucket protocol."""
    from presto_tpu.page import compact_page

    right_c = compact_page(right)  # offsets index the live prefix
    nr = right_c.num_valid.astype(jnp.int64)
    m_eff = jnp.where(left.row_mask(), nr, 0)
    total = jnp.cumsum(m_eff)
    out_count = total[-1] if left.capacity else jnp.asarray(0, jnp.int64)
    overflow = out_count > out_capacity

    j = jnp.arange(out_capacity, dtype=jnp.int64)
    _, p_idx = _match_ranges(total, j)  # searchsorted(..., "right")
    p_idx = jnp.minimum(p_idx, left.capacity - 1)
    prev = jnp.where(p_idx > 0, total[jnp.maximum(p_idx - 1, 0)], 0)
    b_idx = jnp.clip(j - prev, 0, right_c.capacity - 1)

    names: List[str] = []
    blocks: List[Block] = []
    for name, blk in zip(left.names, left.blocks):
        blocks.append(
            dataclasses.replace(
                blk,
                data=blk.data[p_idx],
                valid=None if blk.valid is None else blk.valid[p_idx],
            )
        )
        names.append(name)
    for name, blk in zip(right_c.names, right_c.blocks):
        blocks.append(
            dataclasses.replace(
                blk,
                data=blk.data[b_idx],
                valid=None if blk.valid is None else blk.valid[b_idx],
            )
        )
        names.append(name)
    return (
        Page(
            blocks=tuple(blocks),
            num_valid=jnp.minimum(out_count, out_capacity).astype(
                jnp.int32
            ),
            names=tuple(names),
        ),
        overflow,
    )


def _append_unmatched_build(
    out: Page,
    probe: Page,
    build: Page,
    pk_eff: jnp.ndarray,
    p_ok: jnp.ndarray,
    bk: jnp.ndarray,
    b_ok: jnp.ndarray,
    build_payload: Sequence[str],
    payload_rename: dict,
) -> Page:
    """FULL OUTER's second section: build rows no probe key matched,
    appended after the left-outer section with NULL probe columns. The
    result is a masked-form Page (section 1's liveness concatenated
    with the unmatched-build mask) — zero gathers."""
    # membership of each build key among the live probe keys: its range
    # in their sorted order; matches beyond the live count are sentinel
    # slots, not real keys — clip like the main probe path does
    n_live = jnp.sum(p_ok).astype(jnp.int32)
    lo, hi = _match_ranges(pk_eff, bk)
    matched_b = b_ok & (jnp.minimum(hi, n_live) > jnp.minimum(lo, n_live))
    keep_b = build.row_mask() & ~matched_b

    rename = payload_rename or {}
    payload_names = {rename.get(c, c) for c in build_payload}
    cap_b = build.capacity
    blocks = []
    for name, blk in zip(out.names, out.blocks):
        if name in payload_names:
            src_name = next(
                c for c in build_payload if rename.get(c, c) == name
            )
            b_blk = build.block(src_name)
            tail_data = b_blk.data
            tail_valid = (
                jnp.ones((cap_b,), jnp.bool_)
                if b_blk.valid is None
                else b_blk.valid
            )
        else:
            # probe column: NULL in the appended section
            tail_data = jnp.zeros((cap_b,), blk.data.dtype)
            tail_valid = jnp.zeros((cap_b,), jnp.bool_)
        head_valid = (
            jnp.ones((out.capacity,), jnp.bool_)
            if blk.valid is None
            else blk.valid
        )
        blocks.append(
            dataclasses.replace(
                blk,
                data=jnp.concatenate([blk.data, tail_data]),
                valid=jnp.concatenate([head_valid, tail_valid]),
            )
        )
    live = jnp.concatenate([out.row_mask(), keep_b])
    return Page(
        blocks=tuple(blocks),
        num_valid=(
            out.num_valid + jnp.sum(keep_b).astype(jnp.int32)
        ),
        names=out.names,
        live=live,
    )


def _join_output(
    probe: Page,
    build: Page,
    p_idx: Optional[jnp.ndarray],
    b_idx: jnp.ndarray,
    matched: jnp.ndarray,
    build_payload: Sequence[str],
    payload_rename: dict,
    left_outer: bool,
) -> Page:
    """Output row j = probe row ``p_idx[j]`` (row j itself where
    ``p_idx`` is None: the blocks pass through, nothing is gathered — XLA
    does not fold a gather by an iota into a copy) beside the payload of
    build row ``b_idx[j]``."""
    for name in list(probe.names) + list(build_payload):
        src = probe if name in probe.names else build
        blk = src.block(name)
        if blk.offsets is not None or blk.children is not None:
            # a row-index gather of the FLAT values array with stale
            # offsets (arrays/maps) or of the placeholder without the
            # children (rows) would silently corrupt nested columns
            raise NotImplementedError(
                f"nested column {name} ({blk.dtype}) cannot ride "
                "through a join output; select it before the join or "
                "join on its parent rows and access fields/unnest after"
            )
    names: List[str] = []
    blocks: List[Block] = []
    for name in probe.names:
        blk = probe.block(name)
        if p_idx is not None:
            blk = dataclasses.replace(
                blk,
                data=blk.data[p_idx],
                valid=None if blk.valid is None else blk.valid[p_idx],
            )
        blocks.append(blk)
        names.append(name)
    for name in build_payload:
        blk = build.block(name)
        data = blk.data[b_idx]
        valid = None if blk.valid is None else blk.valid[b_idx]
        if left_outer:
            valid = matched if valid is None else (valid & matched)
        blocks.append(dataclasses.replace(blk, data=data, valid=valid))
        names.append(payload_rename.get(name, name))
    return Page(
        blocks=tuple(blocks), num_valid=probe.num_valid, names=tuple(names)
    )
