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
range — three sort passes (two on one lane, below), a cumsum and a
cummax, no loop and no gather.
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

The build step (``join_build``) returns the build side in key order:
its keys sorted with the row mask and the payload riding the sort, dead
rows last, so the sort is its compaction too. The probe (``join_probe``)
and the output (``join_output``) read only that, so a build side that is
the same for every split batch of a task is built once, in a program of
its own, cut to its first rows (``cut_build``) and handed to each
batch's program as an argument (``exec/local_runner.py:
prepare_builds``); a build that depends on the batch runs the same
function inside the batch's program.

Keys ride ONE uint32 lane wherever their values are proved to fit it
(``_lane_ranges``): each key column a dictionary's ids, a boolean, or an
integer or date column with the inclusive range the connector's
statistics state for it (``JoinNode.key_ranges``, the build side's), two
columns as the mixed-radix digits ``(a - lo_a) * n_b + (b - lo_b)`` while
the product of their sizes stays under ``_NONE`` (0xFFFFFFFF). A dead
row, a NULL key and a probe key outside the ranges carry ``_NONE`` and
rank among the build's dead rows, past ``nb``: they match nothing, in
every join type. The key is then sorted in one pass, not two: the build's
sort is one pass, the probe's two (``_match_ranges``). A stated range is
checked against every build: a live build key outside it sets the
build's ``stale``, which ``hash_join`` reports as ``overflow``, and the
executor runs the batch again with nothing stated
(``local_runner._scale_capacities``; a prepared build's own program
reports it, so it is built again on two lanes): statistics gone stale
cost a re-run, never an answer. Any other key is an int64 — one column,
or two int32-representable columns packed bijectively by ``pack_keys``
(wider composites are packed by the planner) — sorted as two uint32
lanes, the program a join with no proof has always compiled. NULL keys
never match (SQL equi-join); anti join keeps unmatched probe rows (NOT
EXISTS semantics — NOT IN null handling is a planner rewrite). Join keys
of exactly int64-max are unsupported on two lanes (sentinel);
unreachable for real workloads.
"""

from __future__ import annotations

import contextlib
import dataclasses
import math
import threading
from functools import partial
from typing import List, Optional, Sequence, Tuple, Union

import jax
import jax.numpy as jnp

from presto_tpu import types as T
from presto_tpu.ops.common import (
    _SIGN32,
    _u32_lanes,
    orderable_i64,
    sort_u32_lanes,
)
from presto_tpu.page import Block, Page

_I64_MAX = jnp.iinfo(jnp.int64).max

#: the one lane's value for a row no probe key can match (dead, a NULL
#: key, a key outside the range): past every packed key
_NONE = 0xFFFFFFFF

#: trace-time tally of the key lanes join probes sort
#: (``record_probe_lanes``)
_LANES = threading.local()


@contextlib.contextmanager
def record_probe_lanes():
    """Count the key lanes that the join probes traced inside the block
    sort: one where the keys ride one uint32 lane, two where they do not.
    Yields a one-item list that holds the count
    (``device.join_probe_lanes``)."""
    cell = [0]
    prev = getattr(_LANES, "cell", None)
    _LANES.cell = cell
    try:
        yield cell
    finally:
        _LANES.cell = prev


def pack_keys(a: jnp.ndarray, b: jnp.ndarray) -> jnp.ndarray:
    """Bijectively pack two int32-representable key columns into int64."""
    return (a.astype(jnp.int64) << 32) | (b.astype(jnp.int64) & 0xFFFFFFFF)


def _lane_ranges(
    page: Page, key_cols: Sequence[str], key_ranges: Sequence = ()
) -> tuple:
    """Per key column ``(lo, n, stated)``: the ``n`` values from ``lo``
    that it is proved to take (a dictionary's ids, a boolean's two) or
    that the connector's statistics state for it (``key_ranges``, per
    column an inclusive ``(lo, hi)`` or None: ``JoinNode.key_ranges``).
    ``()`` where a column has neither, or where the product of the
    sizes leaves no room below ``_NONE``: the key then takes two
    lanes."""
    stated = list(key_ranges) + [None] * (len(key_cols) - len(key_ranges))
    out = []
    for name, rng in zip(key_cols, stated):
        blk = page.block(name)
        if blk.dtype.is_string and blk.dictionary is not None:
            out.append((0, len(blk.dictionary.values), False))
        elif blk.dtype.name == "boolean":
            out.append((0, 2, False))
        elif rng is not None:
            out.append((int(rng[0]), int(rng[1]) - int(rng[0]) + 1, True))
        else:
            return ()
    if math.prod(n for _, n, _ in out) > _NONE:
        return ()
    return tuple(out)


def _key_of(
    page: Page, key_cols: Sequence[str], ranges: tuple = ()
) -> Tuple[jnp.ndarray, jnp.ndarray, Optional[jnp.ndarray]]:
    """The key of every row, whether the row can match, and whether a
    stated range left out a live row with no NULL key column (None
    without ``ranges``). Without ``ranges`` (``_lane_ranges``) the key is
    int64 and a row can match where it is live with no NULL key column;
    with them it is one uint32 lane, each column a mixed-radix digit
    ``value - lo``, and a row can match only inside every range."""
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
    if ranges:
        lane, inside, outside = None, ok, jnp.asarray(False)
        for d, (lo, n, stated) in zip(datas, ranges):
            fits = (d >= lo) & (d < lo + n)
            if stated:
                outside = outside | jnp.any(ok & ~fits)
            inside = inside & fits
            digit = (d - lo).astype(jnp.uint32)
            lane = digit if lane is None else lane * jnp.uint32(n) + digit
        return lane, inside, outside
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
    return key, ok, None


def _unmatchable(key: jnp.ndarray):
    """The key of a row nothing can match: it sorts after every other."""
    return jnp.uint32(_NONE) if key.dtype == jnp.uint32 else _I64_MAX


def _lanes(key: jnp.ndarray) -> List[jnp.ndarray]:
    """A join key as the uint32 lanes its sort compares, least
    significant first: itself where it is one lane already, else an
    int64's two."""
    if key.dtype == jnp.uint32:
        return [key]
    return _u32_lanes(key.astype(jnp.int64), False)


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
    costs).

    The keys are int64, sorted as two uint32 lanes (three sort passes in
    all), or both sides one uint32 lane already (``_key_of`` with
    ranges: two passes, the key sort's operands two, not three)."""
    nb = build_keys.shape[0]
    keys = jnp.concatenate([build_keys, probe_keys])
    origin = jnp.arange(keys.shape[0], dtype=jnp.int32)
    lanes, (origin,) = sort_u32_lanes(_lanes(keys), (origin,))
    is_build = (origin < nb).astype(jnp.int32)
    hi = jnp.cumsum(is_build)
    change = lanes[0][1:] != lanes[0][:-1]
    for lane in lanes[1:]:
        change = change | (lane[1:] != lane[:-1])
    first = jnp.concatenate([jnp.ones((1,), jnp.bool_), change])
    lo = jax.lax.cummax(jnp.where(first, hi - is_build, 0))
    _, lo, hi = jax.lax.sort((origin, lo, hi), num_keys=1)
    return lo[nb:], hi[nb:]


@partial(
    jax.tree_util.register_dataclass,
    data_fields=["keys", "nb", "live", "page", "stale"],
    meta_fields=["key_dicts", "ranges"],
)
@dataclasses.dataclass
class JoinBuild:
    """A join's build side in key order (:func:`join_build`): all that
    the probe and the output steps read. ``keys`` are ascending: int64,
    or one uint32 lane where ``ranges`` (``_lane_ranges``) prove the
    key columns fit it; a row whose key is null, or that is not live,
    carries the sentinel and sorts last, so the first ``nb`` rows are
    those a probe key can match. ``live`` is the build's row mask and
    ``page`` its payload columns, both in the keys' order. ``stale``
    (one lane only, else None): a live row's key left a stated range, so
    the join is reported as overflowed. ``key_dicts``: (is string,
    dictionary) of each key column, for the probe side's dictionary
    check."""

    keys: jnp.ndarray
    nb: jnp.ndarray
    live: jnp.ndarray
    page: Page
    stale: Optional[jnp.ndarray] = None
    key_dicts: tuple = ()
    ranges: tuple = ()

    @property
    def capacity(self) -> int:
        return self.keys.shape[0]

    @property
    def num_valid(self) -> jnp.ndarray:
        """The rows a probe key can match."""
        return self.nb


def join_build(
    build: Page,
    build_keys: Sequence[str],
    build_payload: Sequence[str] = (),
    key_ranges: Sequence = (),
) -> JoinBuild:
    """The build side sorted by key: the key's lanes (one where
    ``_lane_ranges`` proves it fits, from ``key_ranges`` and the key
    columns, two otherwise) sorted with the row mask and the payload's
    values and validity riding along (no gather by the sort's
    permutation). Dead rows sort last like null keys, so the sort is the
    build's compaction too."""
    for name in build_payload:
        _check_flat(build.block(name), name)
    ranges = _lane_ranges(build, build_keys, key_ranges)
    with jax.named_scope("join_build"):
        bk, b_ok, stale = _key_of(build, build_keys, ranges)
        blocks = [build.block(c) for c in build_payload]
        arrays = [build.row_mask()]
        for b in blocks:
            arrays += [b.data] + ([] if b.valid is None else [b.valid])
        # a sort's operands are vectors: a (rows, limbs) array rides as
        # its limbs
        rides = [
            col for a in arrays
            for col in (a.reshape(a.shape[0], -1).T if a.ndim > 1 else [a])
        ]
        # unmatchable rows carry the sentinel and sort last
        lanes, moved = sort_u32_lanes(
            _lanes(jnp.where(b_ok, bk, _unmatchable(bk))), rides
        )
        if ranges:
            (keys,) = lanes
        else:
            low, high = lanes
            keys = (
                jax.lax.bitcast_convert_type(high ^ _SIGN32, jnp.int32)
                .astype(jnp.int64)
                << 32
            ) | low.astype(jnp.int64)
        moved = iter(moved)

        def back(a):
            if a.ndim == 1:
                return next(moved)
            limbs = [next(moved) for _ in range(a[0].size)]
            return jnp.stack(limbs, axis=1).reshape(a.shape)

        live = back(arrays[0])
        sorted_blocks = []
        for b in blocks:
            data = back(b.data)
            valid = None if b.valid is None else back(b.valid)
            sorted_blocks.append(dataclasses.replace(b, data=data, valid=valid))
        sorted_blocks = tuple(sorted_blocks)
        nb = jnp.sum(b_ok).astype(jnp.int32)
        return JoinBuild(
            keys=keys,
            nb=nb,
            live=live,
            page=Page(
                blocks=sorted_blocks, num_valid=nb, names=tuple(build_payload)
            ),
            stale=stale,
            key_dicts=tuple(
                (build.block(c).dtype.is_string, build.block(c).dictionary)
                for c in build_keys
            ),
            ranges=ranges,
        )


def cut_build(build: JoinBuild, k: int) -> JoinBuild:
    """The first ``k`` rows of a build in key order: all a probe can
    match where ``k`` >= ``nb``."""
    return dataclasses.replace(
        build,
        keys=build.keys[:k],
        live=build.live[:k],
        page=dataclasses.replace(
            build.page,
            blocks=tuple(
                dataclasses.replace(
                    b,
                    data=b.data[:k],
                    valid=None if b.valid is None else b.valid[:k],
                )
                for b in build.page.blocks
            ),
        ),
    )


def hash_join(
    probe: Page,
    build: Union[Page, JoinBuild],
    probe_keys: Sequence[str],
    build_keys: Sequence[str],
    join_type: str = "inner",
    build_payload: Optional[Sequence[str]] = None,
    build_unique: bool = False,
    out_capacity: Optional[int] = None,
    payload_rename: Optional[dict] = None,
    key_ranges: Sequence = (),
) -> Tuple[Page, jnp.ndarray]:
    """Join ``probe`` with ``build`` on equality of packed keys.

    Returns (result, overflow). Result columns = all probe columns plus
    ``build_payload`` columns (optionally renamed via ``payload_rename``).
    join_type: inner | left | full | semi | anti. ``build`` is a page, or
    that page already put in key order with the same keys, payload and
    ``key_ranges`` (:func:`join_build`, then :func:`cut_build` at no
    fewer than its ``nb`` rows — all of them for FULL); the result is
    the same. The probe keys take the build's lanes: on one lane a probe
    key outside the build's ranges matches nothing. ``overflow`` is also
    set where the build's ``stale`` is: the caller runs again with
    nothing stated.

    FULL OUTER executes as left outer plus an appended section of
    unmatched build rows (probe columns NULL) — the appended section
    rides the Page live-mask (masked form), so no compaction gather is
    paid for it.
    """
    build_payload = list(build_payload or [])
    payload_rename = payload_rename or {}
    if isinstance(build, Page):
        build = join_build(build, build_keys, build_payload, key_ranges)

    for pc, (b_string, b_dict) in zip(probe_keys, build.key_dicts):
        pb = probe.block(pc)
        if (pb.dtype.is_string or b_string) and pb.dictionary != b_dict:
            # ids are only comparable within ONE dictionary; the planner
            # re-encodes one side before a string-keyed join
            raise NotImplementedError(
                f"string join key {pc} across different "
                "dictionaries: planner must re-encode first"
            )

    pk, p_ok, _ = _key_of(probe, probe_keys, build.ranges)
    nb = build.nb
    last = build.capacity - 1
    stale = jnp.asarray(False) if build.stale is None else build.stale

    with jax.named_scope("join_probe"):
        pk_eff = jnp.where(p_ok, pk, _unmatchable(pk))
        cell = getattr(_LANES, "cell", None)
        if cell is not None:
            cell[0] += 1 if build.ranges else 2
        lo, hi = _match_ranges(build.keys, pk_eff)
        lo = jnp.minimum(lo, nb)
        hi = jnp.minimum(hi, nb)
        m = jnp.where(p_ok, hi - lo, 0)  # matches per probe row

    if join_type == "semi":
        return _mask_out(probe, m > 0), stale
    if join_type == "anti":
        keep = (m == 0) & probe.row_mask()
        return _mask_out(probe, keep), stale

    with jax.named_scope("join_output"):
        if build_unique:
            # PK side: m in {0,1}; output row i IS probe row i (static!), so
            # the probe's columns pass through and only the build's payload
            # is gathered
            matched = m > 0
            out = _join_output(
                probe,
                build.page,
                None,
                jnp.clip(lo, 0, last),
                matched,
                build_payload,
                payload_rename,
                left_outer=(join_type in ("left", "full")),
            )
            if join_type == "inner":
                keep = matched & probe.row_mask()
                return _mask_out(out, keep), stale
            # left/full outer keep every probe row: positional layout, so
            # the probe's own liveness (mask or prefix) carries over
            out = dataclasses.replace(out, live=probe.live)
            if join_type == "full":
                out = _append_unmatched_build(
                    out, pk_eff, p_ok, build, build_payload, payload_rename,
                )
            return out, stale

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
        if build.stale is not None:
            overflow = overflow | stale

        j = jnp.arange(out_capacity, dtype=jnp.int64)
        _, p_idx = _match_ranges(total, j)  # searchsorted(..., "right")
        p_idx = jnp.minimum(p_idx, probe.capacity - 1)
        prev = jnp.where(p_idx > 0, total[jnp.maximum(p_idx - 1, 0)], 0)
        offset = j - prev
        row_m = m[p_idx]
        matched = row_m > 0
        b_pos = lo[p_idx] + jnp.minimum(offset, jnp.maximum(row_m - 1, 0))
        out = _join_output(
            probe,
            build.page,
            p_idx,
            jnp.clip(b_pos, 0, last),
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
                out, pk_eff, p_ok, build, build_payload, payload_rename,
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
    pk_eff: jnp.ndarray,
    p_ok: jnp.ndarray,
    build: JoinBuild,
    build_payload: Sequence[str],
    payload_rename: dict,
) -> Page:
    """FULL OUTER's second section: build rows no probe key matched,
    appended after the left-outer section with NULL probe columns, in
    the build's key order. The result is a masked-form Page (section 1's
    liveness concatenated with the unmatched-build mask) — zero
    gathers."""
    # membership of each build key among the live probe keys: its range
    # in their sorted order; matches beyond the live count are sentinel
    # slots, not real keys — clip like the main probe path does
    n_live = jnp.sum(p_ok).astype(jnp.int32)
    lo, hi = _match_ranges(pk_eff, build.keys)
    b_ok = jnp.arange(build.capacity, dtype=jnp.int32) < build.nb
    matched_b = b_ok & (jnp.minimum(hi, n_live) > jnp.minimum(lo, n_live))
    keep_b = build.live & ~matched_b

    rename = payload_rename or {}
    payload_names = {rename.get(c, c) for c in build_payload}
    cap_b = build.capacity
    blocks = []
    for name, blk in zip(out.names, out.blocks):
        if name in payload_names:
            src_name = next(
                c for c in build_payload if rename.get(c, c) == name
            )
            b_blk = build.page.block(src_name)
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


def _check_flat(blk: Block, name: str) -> None:
    """A row-index gather of the FLAT values array with stale offsets
    (arrays/maps) or of the placeholder without the children (rows)
    would silently corrupt nested columns."""
    if blk.offsets is not None or blk.children is not None:
        raise NotImplementedError(
            f"nested column {name} ({blk.dtype}) cannot ride "
            "through a join output; select it before the join or "
            "join on its parent rows and access fields/unnest after"
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
    build row ``b_idx[j]`` (``build``: the payload in key order)."""
    for name in probe.names:
        _check_flat(probe.block(name), name)
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
