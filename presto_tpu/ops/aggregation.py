"""Grouped aggregation kernel.

Reference parity: ``HashAggregationOperator`` + ``GroupByHash`` +
``InMemoryHashAggregationBuilder`` and the annotation-generated
accumulators (SURVEY.md §2.1 "Operators", "Function registry").

TPU-first redesign (SURVEY.md §7 step 3), informed by v5e microbenchmarks
(scatter-adds — XLA's lowering of ``jax.ops.segment_*`` — run ~0.6s per
call over 8M rows regardless of segment count; sorts are fast at runtime
but cost minutes of compile; one-hot reduction and cumsum are ~10ms):

- **one-hot path**: when every group key has a statically *provable*
  small domain (dict-encoded strings, booleans) and the composite domain
  is tiny, each accumulator is a masked broadcast-reduce against the
  one-hot key matrix — XLA fuses it into a single pass, no sort, no
  scatter. TPU analogue of the reference's array-based
  ``BigintGroupByHash`` fast path.
- **sorted path**: every other grouping, and no row moves by a
  permutation. The keys become uint32 lanes packed as tightly as their
  proofs allow (``_key_lanes``): a key whose values are proved to lie in
  a range — a dictionary's length, a boolean, or the inclusive ``(lo,
  hi)`` the connector's column statistics state
  (``AggregationNode.key_ranges``, checked every batch) — is a digit of
  a mixed-radix lane shared with its neighbours while their product
  fits; any other key takes the one or two lanes of its stored width.
  One stable single-key sort a lane (``common.sort_u32_lanes``) carries
  the other lanes and every aggregate's argument along; a group starts
  and ends where any lane differs from its neighbour; every accumulator
  is a *scan* over the sorted rows (running totals for counts and
  integer sums, segmented scans for min/max and float sums); and ONE
  more sort, of the flag of the rows that end a group, brings them to
  the front in group order with the lanes and the scans riding — the
  groups' keys are unpacked from their lanes, their totals are the
  differences of consecutive running totals. On the v5e a gather of
  2^20 int64 by a permutation costs 17.4 ms and ``nonzero``'s scatter
  47.6 ms, a sort pass with its payloads 1.1-1.7 ms (PERF.md §6); Q3's
  lane-by-lane aggregation spent 1.8 s of its 2.5 s of device time a
  statement at SF1 in those gathers and that scatter (PERF.md §5).
- **lane-by-lane branch** (``_ordered_aggregate``): ``array_agg`` and
  the order statistics (``_ORDER_FUNCS``) alone, which need the sort's
  permutation and the groups' spans themselves: ``common.sort_order``,
  keys and arguments gathered by the permutation, the group starts by
  ``nonzero_1d``. Every such move is counted while a program traces
  (``record_row_moves``; ``device.agg_row_moves`` at each dispatch).
- Shapes stay static, and ONE rule sizes a grouped aggregation's page
  (``_out_capacity``), applied here, where the aggregation meets the
  page it is bound to, for every caller alike: no more slots than the
  planner's ``max_groups`` (a bucket of its ROW estimate: half the
  source's rows, 2^24 for a scan of ``lineitem`` at SF10), no more than
  the input page has row slots (a 2^20-row split batch cannot hold more
  groups than rows), and no more than the keys' proved domain holds
  (Q1: 1,024 for four groups; Q15 at SF10: 2^17 for 100,000
  suppliers). Kernels report overflow instead of reallocating, and the
  host re-runs at a bigger ``max_groups`` on overflow (SURVEY.md §7
  "Hard parts: dynamic shapes"): only the planner's bucket can
  overflow, the other two bounds are proofs. The global path emits one
  row.

Aggregate functions: count(*), count(x), sum, min, max, avg. Null
semantics match SQL: aggregates skip nulls; count(*) counts rows;
min/max on dictionary ids are valid because dictionaries are
order-preserving. ``count(DISTINCT x)`` is a planner rewrite into a
two-level aggregation, not a kernel (see presto_tpu.plan).

Result types: sum(int)->bigint, sum(decimal(p,s))->decimal(18,s) exact on
int64, sum(double)->double, count->bigint, avg->double (deviation: the
reference returns decimal for decimal inputs; exact decimal avg lands
with int128), min/max preserve the input type.

Exactness note: decimal/bigint sums on the sorted path are differences
of page-wide int64 running totals read at the groups' last rows — exact
in modular arithmetic whenever a group's true sum fits int64, even where
the page's running total wraps. Where a group's sum does not fit, a
traced per-group float64 shadow (``_sum_wraps``: a wrap displaces the
value by ~2^64, far beyond float accumulation error) raises through the
error-flag channel instead of returning a silently wrong sum. Float
sums use per-segment scans (not the running-total trick) so no
cross-group cancellation is introduced.
"""

from __future__ import annotations

import contextlib
import dataclasses
import functools
import math
import operator
import threading
from typing import List, Optional, Sequence, Tuple

import jax.numpy as jnp
from jax import lax

from presto_tpu import types as T
from presto_tpu.expr import Expr, ExprLowerer
from presto_tpu.ops.common import (
    _SIGN32,
    _is_narrow,
    _u32_lanes,
    boundaries,
    cumsum,
    key_lanes,
    lexsort_u32,
    sort_order,
    sort_u32_lanes,
)
from presto_tpu.page import Block, Page, nonzero_1d

@dataclasses.dataclass(frozen=True)
class AggCall:
    """One KERNEL aggregate: func in {count, count_star, sum, min, max,
    avg, stddev_samp, stddev_pop, var_samp, var_pop, array_agg,
    approx_percentile, min_by, max_by}.

    Composed aggregates (corr, covar, skewness, checksum, ... —
    presto_tpu.functions.ComposedAgg) never reach the kernel: the
    planner lowers them to primitive AggCalls plus a finisher
    projection, so the kernel surface stays the primitive set.

    ``arg2`` is min_by/max_by's ordering argument; ``param`` is
    approx_percentile's quantile in [0, 1]."""

    func: str
    arg: Optional[Expr]  # None only for count_star
    out_name: str
    arg2: Optional[Expr] = None
    param: Optional[float] = None

    def result_type(self) -> T.DataType:
        if self.func in ("count", "count_star"):
            return T.BIGINT
        if self.func in _VARIANCE_FUNCS:
            return T.DOUBLE
        if self.func == "array_agg":
            return T.array(self.arg.dtype)
        t = self.arg.dtype
        if self.func == "sum":
            if t.is_decimal:
                return T.decimal(18, t.scale)
            if t.is_integer:
                return T.BIGINT
            return T.DOUBLE
        if self.func == "avg":
            return T.DOUBLE
        if self.func in ("min", "max", "approx_percentile",
                         "min_by", "max_by"):
            return t
        raise NotImplementedError(f"aggregate {self.func}")


_VARIANCE_FUNCS = ("stddev_samp", "stddev_pop", "var_samp", "var_pop")

#: aggregates that require the sorted layout (a per-group value order)
_ORDER_FUNCS = ("array_agg", "approx_percentile", "min_by", "max_by")


def _variance_block(
    s1: jnp.ndarray, s2: jnp.ndarray, cnt: jnp.ndarray, func: str
) -> Block:
    """Variance family from (Σx, Σx², n) in float64.

    var_pop = Σx²/n − (Σx/n)²; var_samp scales by n/(n−1). NULL when
    n == 0 (pop) or n < 2 (samp), like the reference."""
    n = jnp.maximum(cnt, 1).astype(jnp.float64)
    mean = s1 / n
    var_pop = jnp.maximum(s2 / n - mean * mean, 0.0)
    if func.endswith("_samp"):
        var = var_pop * (n / jnp.maximum(n - 1.0, 1.0))
        has = cnt > 1
    else:
        var = var_pop
        has = cnt > 0
    data = jnp.sqrt(var) if func.startswith("stddev") else var
    return Block(data=data, valid=has, dtype=T.DOUBLE)


#: one-hot path ceiling: cost is O(rows * domain) fused on the VPU;
#: 256 keeps that under ~2G lane-ops for 8M-row pages
_ONEHOT_MAX_SEGMENTS = 256


def _static_domain(e: Expr, lowerer: ExprLowerer) -> Optional[int]:
    """Provable key-domain size, or None when unbounded.

    Only *proofs* qualify (collisions would be wrong answers): dictionary
    ids are bounded by the static dictionary length; booleans by 2.
    Range-bounded ints via connector stats are estimates, not proofs, so
    they do NOT qualify.
    """
    if e.dtype.is_string:
        try:
            dic = lowerer.dictionary_of(e)
        except NotImplementedError:
            return None
        if dic is None:
            return None
        return len(dic.values)
    if e.dtype.name == "boolean":
        return 2
    return None


def _out_capacity(max_groups: int, rows: int, proved: Optional[int]) -> int:
    """Slots of a grouped aggregation's output page — the one rule
    (module docstring): the planner's bucket, capped by the bucket of
    the input page's row slots and by the bucket of the key domain
    where one is ``proved``. A ``bucket_capacity`` bucket throughout,
    the capacity ``materialize_page`` re-pads a fetched prefix to."""
    from presto_tpu.exec.staging import bucket_capacity

    cap = min(max_groups, bucket_capacity(rows))
    if proved is not None:
        cap = min(cap, bucket_capacity(proved))
    return cap


def hash_aggregate(
    page: Page,
    group_keys: Sequence[Tuple[str, Expr]],
    aggs: Sequence[AggCall],
    max_groups: int,
    errors_out: Optional[List] = None,
    key_ranges: Sequence[Optional[Tuple[int, int]]] = (),
) -> Tuple[Page, jnp.ndarray]:
    """Group ``page`` by key expressions, compute aggregates.

    Returns (result_page, overflow) where overflow is a traced bool: True
    when the data had more groups than the planner's ``max_groups``
    allows (surplus groups were dropped), or a key left the range
    ``key_ranges`` states for it (its row went to another group's
    slot). Either way the host runs the batch again, at a bigger bucket
    and with nothing stated (``local_runner._scale_capacities``). The
    page has ``_out_capacity`` slots.

    ``errors_out``, when given, collects ``(message, traced_bool)`` hard
    errors — currently the bigint-sum overflow trap of the sorted path
    (the reference raises on per-group bigint overflow; the sorted path's
    page-wide running total would otherwise wrap *silently* even when
    individual group sums are in range — see _sum_wraps).

    ``key_ranges`` (``AggregationNode.key_ranges``): per key the
    inclusive ``(lo, hi)`` the connector's statistics state for its
    values, or None. A statement, checked against every batch: a
    connector whose statistics went stale costs a second run, never an
    answer. A caller that passes ranges re-runs without them on
    overflow.

    Global aggregation (no keys) is the plain-reduction degenerate case.
    """
    live = page.row_mask()
    lowerer = ExprLowerer(page)

    if not group_keys:
        return _global_aggregate(page, aggs, live, lowerer)

    keys = [(name, *lowerer.eval(e), e) for name, e in group_keys]
    rows = page.capacity

    if any(a.func in _ORDER_FUNCS for a in aggs):
        # these need the sort's permutation and the groups' spans
        # (array_agg: the spans ARE the output arrays; percentile /
        # min_by / max_by: a per-group value ordering, by a second sort
        # over the keys as they are)
        return _ordered_aggregate(
            page, keys, aggs, max_groups, live, lowerer, errors_out
        )

    domains = [_static_domain(e, lowerer) for _, _, _, e in keys]
    ranges = list(key_ranges) + [None] * (len(keys) - len(key_ranges))
    # per key: (smallest value, how many values, whether only stated) it
    # is proved to take
    proofs = [
        (0, dom, False) if dom is not None
        else (rng[0], rng[1] - rng[0] + 1, True) if rng is not None
        else None
        for dom, rng in zip(domains, ranges)
    ]
    nseg = None
    if all(p is not None for p in proofs):
        slots = [
            max(n + (1 if v is not None else 0), 1)
            for (_, n, _), (_, _, v, _) in zip(proofs, keys)
        ]
        nseg = math.prod(slots)
    out_cap = _out_capacity(max_groups, rows, nseg)

    if (
        nseg is not None and nseg <= _ONEHOT_MAX_SEGMENTS
        and all(d is not None for d in domains)
    ):
        return _onehot_aggregate(
            page, keys, domains, slots, nseg, aggs, max_groups, out_cap,
            live, lowerer,
        )
    return _sorted_aggregate(
        page, keys, aggs, max_groups, live, lowerer, errors_out,
        out_cap=out_cap, proofs=proofs,
    )


# --------------------------------------------------------- one-hot path


def _onehot_aggregate(
    page: Page,
    keys,
    domains: List[int],
    slots: List[int],
    nseg: int,
    aggs: Sequence[AggCall],
    max_groups: int,
    out_cap: int,
    live: jnp.ndarray,
    lowerer: ExprLowerer,
) -> Tuple[Page, jnp.ndarray]:
    """Sort-free, scatter-free aggregation over a tiny provable domain.

    Strides assign the first key the most significant position, so
    ascending segment order is lexicographic in the keys (dict ids are
    order-preserving); a key's NULL slot is its largest id (nulls group
    last, matching the sorted path's NULLS LAST grouping order).

    The output page is ``out_cap`` long, which ``_out_capacity`` holds
    to the bucket of ``nseg``: there are at most ``nseg`` groups by
    construction (dead rows match no column), so nothing here is
    allocated, scanned, scattered or gathered at ``max_groups``. Sized
    by the planner's bucket instead, Q1's partial stage at SF10 built
    2^24-slot pages for four groups: 17.6 ms of ``reduce-window`` (the
    compaction's cumsum over the slots) and 13 ms of copies a 2^20-row
    batch, 1.73 GB of output of which 105 KB was fetched, 2.1 s of
    device time a statement where 41 ms do (PERF.md §6, PR 30).
    ``overflow`` can only be true where a caller passes ``max_groups``
    under ``nseg``; the first ``max_groups`` groups are kept then.
    """
    cap = page.capacity

    strides = []
    s = 1
    for sl in reversed(slots):
        strides.append(s)
        s *= sl
    strides = list(reversed(strides))

    gid = jnp.zeros((cap,), jnp.int32)
    for (name, d, v, e), dom, stride in zip(keys, domains, strides):
        comp = d.astype(jnp.int32)
        if v is not None:
            comp = jnp.where(v, comp, dom)  # null slot = largest id
        gid = gid + comp * jnp.int32(stride)
    gid = jnp.where(live, gid, nseg)  # dead rows match no one-hot column

    oh = gid[:, None] == jnp.arange(nseg, dtype=jnp.int32)[None, :]

    counts = jnp.sum(oh, axis=0)  # (nseg,) live rows per group
    occupied = counts > 0
    num_groups = jnp.sum(occupied).astype(jnp.int32)
    overflow = num_groups > max_groups

    # occupied segments compacted to the front, ascending (lexicographic)
    sel = nonzero_1d(occupied, out_cap, nseg)
    safe_sel = jnp.minimum(sel, nseg - 1).astype(jnp.int32)

    names: List[str] = []
    blocks: List[Block] = []
    for (name, d, v, e), dom, stride, sl in zip(
        keys, domains, strides, slots
    ):
        comp = (safe_sel // jnp.int32(stride)) % jnp.int32(sl)
        valid = None if v is None else (comp != dom)
        data = comp.astype(d.dtype)
        dictionary = None
        if e.dtype.is_string:
            dictionary = lowerer.dictionary_of(e)
        names.append(name)
        blocks.append(
            Block(data=data, valid=valid, dtype=e.dtype, dictionary=dictionary)
        )

    for agg in aggs:
        full = _onehot_one_agg(agg, page, oh, live, counts, lowerer)
        blocks.append(
            dataclasses.replace(
                full,
                data=full.data[safe_sel],
                valid=None if full.valid is None else full.valid[safe_sel],
            )
        )
        names.append(agg.out_name)

    out = Page(
        blocks=tuple(blocks),
        num_valid=jnp.minimum(num_groups, max_groups).astype(jnp.int32),
        names=tuple(names),
    )
    return out, overflow


def _onehot_one_agg(
    agg: AggCall,
    page: Page,
    oh: jnp.ndarray,  # (cap, nseg) bool; dead rows all-False
    live: jnp.ndarray,
    counts: jnp.ndarray,  # (nseg,) live rows per group
    lowerer: ExprLowerer,
) -> Block:
    """One aggregate as full (nseg,) arrays via masked broadcast-reduce
    (fuses into one pass; no scatter)."""
    if agg.func == "count_star":
        return Block(
            data=counts.astype(jnp.int64), valid=None, dtype=T.BIGINT
        )

    d, v = lowerer.eval(agg.arg)
    d = jnp.broadcast_to(d, (page.capacity,))
    valid = live if v is None else (live & jnp.broadcast_to(v, live.shape))

    ohv = oh & valid[:, None]
    cnt = jnp.sum(ohv, axis=0)

    if agg.func == "count":
        return Block(data=cnt.astype(jnp.int64), valid=None, dtype=T.BIGINT)

    group_has_value = cnt > 0
    at = agg.arg.dtype

    if agg.func in _VARIANCE_FUNCS:
        x = d.astype(jnp.float64)
        if at.is_decimal:
            x = x / (10 ** at.scale)
        xm = jnp.where(ohv, x[:, None], 0.0)
        s1 = jnp.sum(xm, axis=0)
        s2 = jnp.sum(jnp.where(ohv, (x * x)[:, None], 0.0), axis=0)
        return _variance_block(s1, s2, cnt, agg.func)

    if agg.func in ("sum", "avg"):
        if at.name in ("double", "real") or agg.func == "avg":
            x = d.astype(jnp.float64)
            if at.is_decimal:
                x = x / (10 ** at.scale)
            s = jnp.sum(jnp.where(ohv, x[:, None], 0.0), axis=0)
            if agg.func == "avg":
                return Block(
                    data=s / jnp.maximum(cnt, 1),
                    valid=group_has_value,
                    dtype=T.DOUBLE,
                )
            return Block(data=s, valid=group_has_value, dtype=T.DOUBLE)
        x = d.astype(jnp.int64)
        s = jnp.sum(jnp.where(ohv, x[:, None], 0), axis=0)
        return Block(data=s, valid=group_has_value, dtype=agg.result_type())

    if agg.func in ("min", "max"):
        reduce = jnp.min if agg.func == "min" else jnp.max
        if at.name in ("double", "real"):
            fill = jnp.inf if agg.func == "min" else -jnp.inf
            x = d.astype(jnp.float64)
            data = reduce(jnp.where(ohv, x[:, None], fill), axis=0)
            data = data.astype(at.jnp_dtype)
        else:
            info = jnp.iinfo(jnp.int64)
            fill = info.max if agg.func == "min" else info.min
            x = d.astype(jnp.int64)
            data = reduce(jnp.where(ohv, x[:, None], fill), axis=0)
            data = data.astype(at.jnp_dtype)
        dictionary = None
        if at.is_string:
            dictionary = lowerer.dictionary_of(agg.arg)
        return Block(
            data=data, valid=group_has_value, dtype=at, dictionary=dictionary
        )

    raise NotImplementedError(f"aggregate {agg.func}")


# ---------------------------------------------------------- sorted path

#: the first key lane of a dead row: past every packed composite
_DEAD = 0xFFFFFFFF

#: trace-time tally of the row moves grouped aggregations issue
#: (``record_row_moves``)
_MOVES = threading.local()


@contextlib.contextmanager
def record_row_moves():
    """Count the page-sized row moves that grouped aggregations issue
    while a program traces inside the block: each gather by a sort's
    permutation or at the groups' spans, each ``nonzero_1d``, each
    scatter of values to their ranks. Yields a one-item list that holds
    the count. Only the lane-by-lane branch, which ``array_agg`` and the
    order statistics take, issues any (``device.agg_row_moves``)."""
    cell = [0]
    prev = getattr(_MOVES, "cell", None)
    _MOVES.cell = cell
    try:
        yield cell
    finally:
        _MOVES.cell = prev


def _moved() -> None:
    cell = getattr(_MOVES, "cell", None)
    if cell is not None:
        cell[0] += 1


def _take(a: jnp.ndarray, idx: jnp.ndarray) -> jnp.ndarray:
    """``a[idx]``, counted as a row move (``record_row_moves``)."""
    _moved()
    return a[idx]


def _segmented_scan_reduce(
    x: jnp.ndarray, bnd: jnp.ndarray, op
) -> jnp.ndarray:
    """Inclusive segmented reduction scan: position p holds op-reduction
    of its segment's values up to p; segments restart where ``bnd``.
    Read at segment END positions for per-segment totals."""

    def combine(a, b):
        av, af = a
        bv, bf = b
        return jnp.where(bf, bv, op(av, bv)), af | bf

    vals, _ = lax.associative_scan(combine, (x, bnd))
    return vals


def _diff(c: jnp.ndarray) -> jnp.ndarray:
    """Running totals read at the groups' last rows, in group order, as
    each group's own total: the difference of consecutive reads, which
    is ``c[end] - (c - w)[start]`` (groups are contiguous, the first
    starts at row 0) and exact in modular int64 arithmetic."""
    return c - jnp.concatenate([jnp.zeros((1,), c.dtype), c[:-1]])


def _slots(a: jnp.ndarray, out_cap: int) -> jnp.ndarray:
    """The first ``out_cap`` rows of ``a``, zero-padded where it has
    fewer."""
    if out_cap <= a.shape[0]:
        return a[:out_cap]
    pad = jnp.zeros((out_cap - a.shape[0],) + a.shape[1:], a.dtype)
    return jnp.concatenate([a, pad])


@dataclasses.dataclass(frozen=True)
class _Place:
    """Where ``_key_lanes`` put one key: a key with a proof ``(lo, n)``
    is the digit ``factor`` — ``(lane, position)`` — of a packed lane,
    ``n`` its NULL; any other key has lanes of its own, ``raw``, and
    where it is nullable the digit ``null_at`` (1 for NULL)."""

    factor: Optional[Tuple[int, int]] = None
    lo: int = 0
    n: int = 0
    raw: Tuple[int, ...] = ()
    null_at: Optional[Tuple[int, int]] = None


def _key_lanes(keys, proofs, live):
    """The group keys of every row as uint32 lanes, most significant
    first, in the order the page lists groups: the first key most
    significant, a key's NULL after its values, dead rows after
    everything.

    A key with a proof (``proofs``: per key ``(lo, n, stated)`` or
    None) — a dictionary's or a type's domain, or the inclusive range
    the connector's statistics state — is a digit of ``n`` values, ``n +
    1`` where it is nullable (NULL the largest), of a lane packed in
    mixed radix; neighbouring keys share a lane while the product of
    their digits stays under ``_DEAD``. Any other key takes its
    ``_u32_lanes`` (one lane where it is stored in 32 bits or fewer, two
    otherwise; the value 0 under a NULL) and, where it is nullable, a
    digit of two in the lane before them. The first lane is always a
    packed one, of no digit where the first key has lanes of its own,
    and holds ``_DEAD`` for a dead row.

    Returns ``(lanes, radix, places, stale)``: ``radix`` gives each lane
    its digits' sizes (None for a key's own lane), ``places`` where each
    key is (``_Place``), and ``stale`` is true where a live, non-null
    value of a stated key lies outside its range — it would take
    another group's slot, so the caller reports the batch as overflowed
    and it runs again with nothing stated."""
    shape = live.shape
    lanes, radix, places = [], [], []
    stale = jnp.asarray(False)
    acc, sizes = None, []

    def close():
        nonlocal acc, sizes
        lanes.append(jnp.zeros(shape, jnp.uint32) if acc is None else acc)
        radix.append(tuple(sizes))
        acc, sizes = None, []

    def digit(comp, size):
        nonlocal acc
        if sizes and math.prod(sizes) * size > _DEAD:
            close()
        acc = comp if acc is None else acc * jnp.uint32(size) + comp
        sizes.append(size)
        return len(lanes), len(sizes) - 1

    for (name, d, v, e), proof in zip(keys, proofs):
        d = jnp.broadcast_to(d, shape) if jnp.ndim(d) == 0 else d
        v = None if v is None else jnp.broadcast_to(v, shape)
        if proof is not None and proof[2]:
            lo, n, _ = proof
            wide = d.astype(jnp.int64)
            outside = live & ((wide < lo) | (wide >= lo + n))
            if v is not None:
                outside = outside & v
            stale = stale | jnp.any(outside)
        size = None if proof is None else max(proof[1] + (v is not None), 1)
        if size is not None and size <= _DEAD:
            lo, n, stated = proof
            if stated:
                comp = (d.astype(jnp.int64) - lo).astype(jnp.uint32)
            else:
                comp = d.astype(jnp.uint32)
            if v is not None:
                comp = jnp.where(v, comp, jnp.uint32(n))
            places.append(_Place(factor=digit(comp, size), lo=lo, n=n))
            continue
        null_at = None
        if v is not None:
            null_at = digit(jnp.where(v, 0, 1).astype(jnp.uint32), 2)
        if sizes or not lanes:
            close()
        raw = []
        narrow = _is_narrow(d, e.dtype)
        for lane in key_lanes(d, e.dtype):
            if v is not None:
                lane = jnp.where(v, lane, 0)
            for u in reversed(_u32_lanes(lane, narrow)):
                raw.append(len(lanes))
                lanes.append(u)
                radix.append(None)
        places.append(_Place(raw=tuple(raw), null_at=null_at))
    if sizes or not lanes:
        close()
    lanes[0] = jnp.where(live, lanes[0], jnp.uint32(_DEAD))
    return lanes, radix, places, stale


def _digit(lanes, radix, at) -> jnp.ndarray:
    """The digit ``at`` (``(lane, position)``) of packed lanes."""
    lane, pos = at
    comp = lanes[lane]
    stride = math.prod(radix[lane][pos + 1:])
    if stride > 1:
        comp = comp // jnp.uint32(stride)
    if pos > 0:
        comp = comp % jnp.uint32(radix[lane][pos])
    return comp


def _from_lanes(us, d: jnp.ndarray, dtype: T.DataType) -> jnp.ndarray:
    """A key's values from its own uint32 lanes (most significant
    first): ``_u32_lanes`` and ``key_lanes`` read backwards — an int64
    bit pattern goes back to float64 by bitcast, the direction the v5e
    compiles (``common.float_bits_i64``)."""
    narrow = _is_narrow(d, dtype)
    wide = []
    for i in range(0, len(us), 1 if narrow else 2):
        hi = lax.bitcast_convert_type(
            us[i] ^ jnp.uint32(_SIGN32), jnp.int32
        ).astype(jnp.int64)
        wide.append(hi if narrow else (hi << 32) | us[i + 1].astype(jnp.int64))
    if dtype.is_long_decimal:
        hi, lo = wide
        return jnp.stack([hi, lo ^ jnp.int64(-(2 ** 63))], axis=-1)
    if dtype.name in ("double", "real"):
        (o,) = wide  # orderable_i64's map is its own inverse
        bits = jnp.where(o >= 0, o, (~o) | jnp.int64(-(2 ** 63)))
        return lax.bitcast_convert_type(bits, jnp.float64)
    return wide[0]


def _unpack_keys(lanes, radix, places, keys, lowerer) -> List[Block]:
    """The key blocks of the groups whose key lanes are ``lanes``:
    ``_key_lanes`` read backwards, so no key column is gathered."""
    blocks = []
    for (name, d, v, e), place in zip(keys, places):
        if place.factor is not None:
            comp = _digit(lanes, radix, place.factor)
            data = comp if place.lo == 0 else comp.astype(jnp.int64) + place.lo
            valid = None if v is None else comp != jnp.uint32(place.n)
        else:
            data = _from_lanes([lanes[i] for i in place.raw], d, e.dtype)
            valid = None if v is None else (
                _digit(lanes, radix, place.null_at) == 0
            )
        blocks.append(
            Block(
                data=data.astype(jnp.asarray(d).dtype),
                valid=valid,
                dtype=e.dtype,
                dictionary=(
                    lowerer.dictionary_of(e) if e.dtype.is_string else None
                ),
            )
        )
    return blocks


def _carried_args(aggs, cap: int, lowerer: ExprLowerer):
    """The aggregates' arguments as operands to ride the key sort — a
    payload costs a sort pass little, a gather of 2^20 int64 by its
    permutation cost 17.4 ms on the v5e (PERF.md §6): per
    distinct argument its data (a ``(rows, limbs)`` array as its limbs)
    and its validity. Returns ``(rides, args)``: ``args(rode)`` maps
    each argument to its ``(data, valid)`` among the rides as the sort
    returns them."""
    rides, at = [], {}
    for agg in aggs:
        if agg.arg is None or agg.arg in at:  # sum(x) and count(x) share x
            continue
        d, v = lowerer.eval(agg.arg)
        d = jnp.broadcast_to(d, (cap,) + jnp.shape(d)[1:])
        limbs = [d] if d.ndim == 1 else list(d.reshape(cap, -1).T)
        at[agg.arg] = (len(rides), d.shape, v is not None)
        rides += limbs
        if v is not None:
            rides.append(jnp.broadcast_to(v, (cap,)))

    def args(rode):
        out = {}
        for arg, (i, shape, nullable) in at.items():
            k = math.prod(shape[1:])
            d = rode[i] if len(shape) == 1 else (
                jnp.stack(rode[i:i + k], axis=1).reshape(shape)
            )
            out[arg] = (d, rode[i + k] if nullable else None)
        return out

    return rides, args


def _sum_wraps(x: jnp.ndarray, bnd: jnp.ndarray, end: jnp.ndarray):
    """The bigint-sum overflow trap: true where a group's true sum of
    ``x`` (int64, in key order) leaves int64. A group's running total
    read at its last row is exact under modular arithmetic whenever the
    TRUE group sum fits int64, even where the page-wide total wraps, so
    the check is per group: a float64 shadow of the same segmented sum.
    A real overflow displaces the int result ~2^64 from the shadow; the
    float error stays many orders below the 2^62 threshold. The shadow
    runs only for a page whose magnitudes add up to 2^62 or more: under
    that no group of it can overflow, and a sum of magnitudes is one
    reduction. Read at the groups' last rows (``end``) as a mask, so
    nothing is gathered."""
    xf = x.astype(jnp.float64)

    def shadow():
        si = _segmented_scan_reduce(x, bnd, jnp.add)
        sf = _segmented_scan_reduce(xf, bnd, jnp.add)
        return jnp.any(end & (jnp.abs(si.astype(jnp.float64) - sf) > 2.0**62))

    return lax.cond(
        jnp.sum(jnp.abs(xf)) < 2.0**62, lambda: jnp.asarray(False), shadow
    )


def _accumulators(aggs, args, live_s, bnd, end, lowerer, errors_out):
    """Every aggregate as inclusive scans over the rows in key order,
    each to be read at the rows that end a group: running totals
    (``cumsum``) for counts and integer sums — a group's total is the
    difference of consecutive reads (``_diff``) — and segmented scans
    for min/max and float sums, whose value at a group's last row is
    the group's. A scan two aggregates need is made once. Returns
    ``(scans, finish)``: ``finish(reads, has_rows)`` builds the
    aggregates' blocks from the scans read at the groups' last rows in
    group order (``has_rows``: the slots that hold a group)."""
    scans, index = [], {}

    def scan(key, make):
        if key not in index:
            index[key] = len(scans)
            scans.append(make())
        return index[key]

    plans = [
        _accumulate(agg, args.get(agg.arg), live_s, bnd, end, scan,
                    lowerer, errors_out)
        for agg in aggs
    ]

    def finish(reads, has_rows):
        return [plan(reads, has_rows) for plan in plans]

    return scans, finish


def _accumulate(agg, arg, live_s, bnd, end, scan, lowerer, errors_out):
    """One aggregate for ``_accumulators``: registers the scans it
    reads with ``scan(key, make)`` and returns how to build its block
    from them, ``(reads, has_rows) -> Block``."""
    if agg.func == "count_star":
        c = scan(("count", None), lambda: cumsum(live_s.astype(jnp.int32)))
        return lambda r, has: Block(
            data=_diff(r[c]).astype(jnp.int64), valid=None, dtype=T.BIGINT
        )

    d, v = arg
    valid_s = live_s if v is None else (live_s & v)

    def count():
        # an argument that is never NULL counts the group's rows
        return scan(
            ("count", None if v is None else agg.arg),
            lambda: cumsum(valid_s.astype(jnp.int32)),
        )

    if agg.func == "count":
        c = count()
        return lambda r, has: Block(
            data=_diff(r[c]).astype(jnp.int64), valid=None, dtype=T.BIGINT
        )

    # an argument that is never NULL: a group has a value because it has
    # a row, so no count is scanned to say so
    c = None if v is None and agg.func in ("sum", "min", "max") else count()

    def has_value(r, has):
        return has if c is None else _diff(r[c]) > 0

    at = agg.arg.dtype

    def as_float():
        x = d.astype(jnp.float64)
        if at.is_decimal:
            x = x / (10 ** at.scale)
        return jnp.where(valid_s, x, 0.0)

    if agg.func in _VARIANCE_FUNCS:
        s1 = scan(
            ("fsum", agg.arg),
            lambda: _segmented_scan_reduce(as_float(), bnd, jnp.add),
        )
        s2 = scan(
            ("fsq", agg.arg),
            lambda: _segmented_scan_reduce(as_float() ** 2, bnd, jnp.add),
        )
        return lambda r, has: _variance_block(
            r[s1], r[s2], _diff(r[c]), agg.func
        )

    if agg.func in ("sum", "avg"):
        if at.name in ("double", "real") or agg.func == "avg":
            # decimal avg and double sums: a SEGMENTED scan, not a global
            # cumsum — differencing a whole-page running float total
            # would cancel catastrophically for small late groups
            s = scan(
                ("fsum", agg.arg),
                lambda: _segmented_scan_reduce(as_float(), bnd, jnp.add),
            )
            if agg.func == "avg":
                return lambda r, has: Block(
                    data=r[s] / jnp.maximum(_diff(r[c]), 1),
                    valid=_diff(r[c]) > 0,
                    dtype=T.DOUBLE,
                )
            return lambda r, has: Block(
                data=r[s], valid=has_value(r, has), dtype=T.DOUBLE
            )
        x = jnp.where(valid_s, d.astype(jnp.int64), 0)
        s = scan(("sum", agg.arg), lambda: cumsum(x))
        if errors_out is not None:
            errors_out.append(
                (f"bigint sum overflow in {agg.out_name}",
                 _sum_wraps(x, bnd, end))
            )
        return lambda r, has: Block(
            data=_diff(r[s]), valid=has_value(r, has), dtype=agg.result_type()
        )

    if agg.func in ("min", "max"):
        op = jnp.minimum if agg.func == "min" else jnp.maximum
        if at.name in ("double", "real"):
            fill = jnp.inf if agg.func == "min" else -jnp.inf
            x = d.astype(jnp.float64)
        else:
            info = jnp.iinfo(jnp.int64)
            fill = info.max if agg.func == "min" else info.min
            x = d.astype(jnp.int64)
        m = scan(
            (agg.func, agg.arg),
            lambda: _segmented_scan_reduce(jnp.where(valid_s, x, fill), bnd, op),
        )
        dictionary = lowerer.dictionary_of(agg.arg) if at.is_string else None
        return lambda r, has: Block(
            data=r[m].astype(at.jnp_dtype), valid=has_value(r, has),
            dtype=at, dictionary=dictionary,
        )

    raise NotImplementedError(f"aggregate {agg.func}")


def _sorted_aggregate(
    page: Page,
    keys,
    aggs: Sequence[AggCall],
    max_groups: int,
    live: jnp.ndarray,
    lowerer: ExprLowerer,
    errors_out: Optional[List] = None,
    out_cap: Optional[int] = None,
    proofs: Sequence = (),
) -> Tuple[Page, jnp.ndarray]:
    """Group by sorting, and move no row by the sort's permutation.

    The keys become uint32 lanes (``_key_lanes``: ``proofs`` per key
    ``(lo, n, stated)`` or None, as ``hash_aggregate`` finds them) and
    are sorted one lane a pass with the other lanes and the aggregates'
    arguments riding (``common.sort_u32_lanes``); a group starts or ends
    where any lane differs from the row before or after. Every
    aggregate is a scan over the sorted rows (``_accumulators``), and
    ONE more sort — stable, of the flag of the rows that end a group —
    brings those rows to the front in group order with the lanes and the
    scans riding: the groups' keys are unpacked from their lanes, their
    aggregates read from their scans. ``out_cap``: the page's slots where
    the caller knows a proved domain; ``_out_capacity`` without one."""
    cap = page.capacity
    if out_cap is None:
        out_cap = _out_capacity(max_groups, cap, None)
    proofs = list(proofs) + [None] * (len(keys) - len(proofs))
    lanes, radix, places, stale = _key_lanes(keys, proofs, live)
    rides, args = _carried_args(aggs, cap, lowerer)
    lanes, rode = sort_u32_lanes(lanes[::-1], rides)  # last lane primary
    lanes = lanes[::-1]
    live_s = lanes[0] != jnp.uint32(_DEAD)
    differs = functools.reduce(
        operator.or_, [lane[1:] != lane[:-1] for lane in lanes]
    )
    one = jnp.ones((1,), jnp.bool_)
    bnd = live_s & jnp.concatenate([one, differs])
    end = live_s & jnp.concatenate([differs, one])
    num_groups = jnp.sum(bnd).astype(jnp.int32)
    scans, finish = _accumulators(
        aggs, args(rode), live_s, bnd, end, lowerer, errors_out
    )
    _, *ended = lax.sort(
        (jnp.where(end, 0, 1).astype(jnp.uint32), *lanes, *scans),
        num_keys=1, is_stable=True,
    )
    ended = [_slots(a, out_cap) for a in ended]
    has_rows = jnp.arange(out_cap, dtype=jnp.int32) < num_groups
    blocks = _unpack_keys(ended[:len(lanes)], radix, places, keys, lowerer)
    blocks += finish(ended[len(lanes):], has_rows)
    out = Page(
        blocks=tuple(blocks),
        num_valid=jnp.minimum(num_groups, max_groups).astype(jnp.int32),
        names=tuple([name for name, _, _, _ in keys]
                    + [agg.out_name for agg in aggs]),
    )
    return out, (num_groups > max_groups) | stale


# ------------------------------------------- lane-by-lane (order funcs)


def _group_spans(
    starts: jnp.ndarray, cap: int
) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """(starts, ends) sorted-space positions per group (gather-safe),
    from the groups' first positions with ``cap`` in the slots past the
    last group.

    ``ends[i] = starts[i+1] - 1`` with cap-1 for the final/fill groups —
    safe because rows past the live prefix carry neutral values for every
    accumulator (0 for running totals, +-inf fills for min/max scans).
    """
    nxt = jnp.concatenate(
        [starts[1:], jnp.full((1,), cap, starts.dtype)]
    )
    ends = jnp.clip(nxt - 1, 0, cap - 1)
    safe_starts = jnp.minimum(starts, cap - 1).astype(jnp.int32)
    return safe_starts, ends.astype(jnp.int32)


def _ordered_aggregate(
    page: Page,
    keys,
    aggs: Sequence[AggCall],
    max_groups: int,
    live: jnp.ndarray,
    lowerer: ExprLowerer,
    errors_out: Optional[List] = None,
) -> Tuple[Page, jnp.ndarray]:
    """The lane-by-lane branch, for ``array_agg`` and the order
    statistics alone (``_ORDER_FUNCS``): they need the sort's
    permutation and the groups' spans themselves. ``common.sort_order``
    (a ``lexsort_u32`` pass a lane), keys and arguments gathered by the
    permutation, the groups' first rows by ``nonzero_1d``; the other
    aggregates are ``_accumulators``' scans read at the spans' ends.
    Every such move is counted (``record_row_moves``)."""
    cap = page.capacity
    out_cap = _out_capacity(max_groups, cap, None)
    order = sort_order([(d, v, e.dtype) for _, d, v, e in keys], live)
    live_s = _take(live, order)
    keys_s = [
        (name, _take(d, order), None if v is None else _take(v, order), e)
        for name, d, v, e in keys
    ]
    bnd = boundaries([(d, v) for _, d, v, _ in keys_s], live_s)
    end = live_s & jnp.concatenate(
        [bnd[1:] | ~live_s[1:], jnp.ones((1,), jnp.bool_)]
    )
    num_groups = jnp.sum(bnd).astype(jnp.int32)
    first = nonzero_1d(bnd, out_cap, cap)
    _moved()
    starts, ends = _group_spans(first, cap)

    names: List[str] = []
    blocks: List[Block] = []
    for name, d, v, e in keys_s:
        names.append(name)
        blocks.append(
            Block(
                data=_take(d, starts),
                valid=None if v is None else _take(v, starts),
                dtype=e.dtype,
                dictionary=(
                    lowerer.dictionary_of(e) if e.dtype.is_string else None
                ),
            )
        )

    plain = [a for a in aggs if a.func not in _ORDER_FUNCS]
    args = {}
    for agg in plain:
        if agg.arg is not None and agg.arg not in args:
            d, v = lowerer.eval(agg.arg)
            args[agg.arg] = (
                _take(jnp.broadcast_to(d, (cap,)), order),
                None if v is None else _take(jnp.broadcast_to(v, (cap,)), order),
            )
    scans, finish = _accumulators(
        plain, args, live_s, bnd, end, lowerer, errors_out
    )
    has_rows = jnp.arange(out_cap, dtype=jnp.int32) < num_groups
    done = iter(finish([_take(s, ends) for s in scans], has_rows))
    for agg in aggs:
        if agg.func == "array_agg":
            blk = _array_agg(agg, page, order, live_s, first, starts, lowerer)
        elif agg.func in _ORDER_FUNCS:
            blk = _order_stat_agg(agg, page, keys, live, starts, ends, lowerer)
        else:
            blk = next(done)
        names.append(agg.out_name)
        blocks.append(blk)

    out = Page(
        blocks=tuple(blocks),
        num_valid=jnp.minimum(num_groups, max_groups).astype(jnp.int32),
        names=tuple(names),
    )
    return out, num_groups > max_groups


def _order_stat_agg(
    agg: AggCall,
    page: Page,
    keys,  # ORIGINAL (unsorted) key evals: [(name, d, v, e), ...]
    live: jnp.ndarray,
    starts: jnp.ndarray,
    ends: jnp.ndarray,
    lowerer: ExprLowerer,
) -> Block:
    """approx_percentile / min_by / max_by on the lane-by-lane branch.

    Each takes its own secondary sort: (group keys, ordering value) —
    within every group the ordering value's non-null rows form an
    ascending prefix (sort_order puts value-NULLs after valid values,
    dead rows after everything). Because the secondary sort is the same
    stable lexicographic key order, every group occupies the SAME
    [start, end] span positions as in the primary order, so the caller's
    spans are reused; only the within-group permutation differs.

    - approx_percentile(x, p): element at nearest rank ceil(p*n) among
      the group's n valid values (exact — error 0 is within any qdigest
      bound the reference guarantees; SURVEY.md §2.1 approx family).
    - min_by(x, y)/max_by(x, y): x gathered at the group's first/last
      y-valid position (any tie representative, like the reference).
    """
    cap = page.capacity
    is_by = agg.func in ("min_by", "max_by")
    val = agg.arg2 if is_by else agg.arg
    vd, vv = lowerer.eval(val)
    vd = jnp.broadcast_to(vd, (cap,))
    vvb = None if vv is None else jnp.broadcast_to(vv, (cap,))
    order2 = sort_order(
        [(d, v, e.dtype) for _, d, v, e in keys]
        + [(vd, vvb, val.dtype)],
        live,
    )
    live2 = _take(live, order2)
    valid2 = live2 if vvb is None else (live2 & _take(vvb, order2))
    cntv = _diff(_take(cumsum(valid2.astype(jnp.int32)), ends))
    cntv = cntv.astype(jnp.int64)
    group_has = cntv > 0

    if agg.func == "approx_percentile":
        p = float(agg.param if agg.param is not None else 0.5)
        k = jnp.clip(
            jnp.ceil(p * cntv.astype(jnp.float64)).astype(jnp.int64) - 1,
            0,
            jnp.maximum(cntv - 1, 0),
        )
        idx = jnp.minimum(
            starts.astype(jnp.int64) + k, cap - 1
        ).astype(jnp.int32)
        return Block(
            data=_take(_take(vd, order2), idx), valid=group_has,
            dtype=agg.arg.dtype,
        )

    xd, xv = lowerer.eval(agg.arg)
    xd2 = _take(jnp.broadcast_to(xd, (cap,)), order2)
    if agg.func == "min_by":
        idx = starts
    else:
        idx = jnp.minimum(
            starts.astype(jnp.int64) + jnp.maximum(cntv - 1, 0),
            cap - 1,
        ).astype(jnp.int32)
    valid = group_has
    if xv is not None:
        valid = valid & _take(
            _take(jnp.broadcast_to(xv, (cap,)), order2), idx
        )
    dictionary = None
    if agg.arg.dtype.is_string:
        dictionary = lowerer.dictionary_of(agg.arg)
    return Block(
        data=_take(xd2, idx), valid=valid, dtype=agg.arg.dtype,
        dictionary=dictionary,
    )


def _array_agg(
    agg: AggCall,
    page: Page,
    order: jnp.ndarray,
    live_s: jnp.ndarray,
    first: jnp.ndarray,
    starts: jnp.ndarray,
    lowerer: ExprLowerer,
) -> Block:
    """array_agg on the lane-by-lane branch: the sorted layout IS the
    concatenated per-group arrays (groups are contiguous spans). NULL
    inputs are SKIPPED, so valid values scatter to their rank among
    valid rows — stable, so groups stay contiguous — and group offsets
    are the valid counts at group starts. (Deviation: the reference's
    array_agg default INCLUDES nulls; arrays here carry no element
    validity.)"""
    cap = page.capacity
    d, v = lowerer.eval(agg.arg)
    d_s = _take(jnp.broadcast_to(d, (cap,)), order)
    valid_s = live_s if v is None else (
        live_s & _take(jnp.broadcast_to(v, (cap,)), order)
    )
    cum = jnp.cumsum(valid_s.astype(jnp.int32))
    total = cum[-1] if cap else jnp.int32(0)
    pos = jnp.where(valid_s, cum - 1, cap)  # cap = dump slot
    out_vals = jnp.zeros((cap + 1,), d_s.dtype).at[pos].set(d_s)
    _moved()
    # padding group slots must read offset == total; the CLAMPED starts
    # (cap-1) would read total-1 on a completely full page and silently
    # drop the last group's last element, so detect padding from the
    # UNCLAMPED first rows
    start_off = jnp.where(
        first >= cap,
        total,
        _take(cum, starts) - _take(valid_s, starts).astype(jnp.int32),
    )
    offsets = jnp.concatenate(
        [
            jnp.minimum(start_off, total).astype(jnp.int32),
            total.reshape(1),
        ]
    )
    dictionary = None
    if agg.arg.dtype.is_string:
        dictionary = lowerer.dictionary_of(agg.arg)
    return Block(
        data=out_vals[:cap],
        valid=None,
        dtype=agg.result_type(),
        dictionary=dictionary,
        offsets=offsets,
    )


# ---------------------------------------------------------- global path


def _global_aggregate(
    page: Page,
    aggs: Sequence[AggCall],
    live: jnp.ndarray,
    lowerer: ExprLowerer,
) -> Tuple[Page, jnp.ndarray]:
    """No GROUP BY: plain masked whole-array reductions (no segments, no
    sort, no scatter). One output row always (SQL: global aggregates over
    zero rows emit one row; sum -> NULL via the empty-group validity
    rule, count -> 0)."""
    names, blocks = [], []
    for agg in aggs:
        blocks.append(_global_one_agg(agg, page, live, lowerer))
        names.append(agg.out_name)
    out = Page(
        blocks=tuple(blocks),
        num_valid=jnp.asarray(1, jnp.int32),
        names=tuple(names),
    )
    return out, jnp.asarray(False)


def _global_one_agg(
    agg: AggCall, page: Page, live: jnp.ndarray, lowerer: ExprLowerer
) -> Block:
    def one(x):
        return x.reshape(1)

    if agg.func == "count_star":
        return Block(
            data=one(jnp.sum(live).astype(jnp.int64)),
            valid=None,
            dtype=T.BIGINT,
        )

    if agg.func == "array_agg":
        d, v = lowerer.eval(agg.arg)
        d = jnp.broadcast_to(d, (page.capacity,))
        keep = live if v is None else (
            live & jnp.broadcast_to(v, live.shape)
        )
        # stable-compact kept values to the front (single global array;
        # NULL inputs skipped — documented deviation from include-nulls)
        order = lexsort_u32([(~keep).astype(jnp.uint32)])
        n = jnp.sum(keep).astype(jnp.int32)
        dictionary = None
        if agg.arg.dtype.is_string:
            dictionary = lowerer.dictionary_of(agg.arg)
        return Block(
            data=d[order],
            valid=None,
            dtype=agg.result_type(),
            dictionary=dictionary,
            offsets=jnp.stack([jnp.int32(0), n]),
        )

    if agg.func in ("approx_percentile", "min_by", "max_by"):
        cap = page.capacity
        is_by = agg.func in ("min_by", "max_by")
        val = agg.arg2 if is_by else agg.arg
        vd, vv = lowerer.eval(val)
        vd = jnp.broadcast_to(vd, (cap,))
        vvb = None if vv is None else jnp.broadcast_to(vv, (cap,))
        order = sort_order([(vd, vvb, val.dtype)], live)
        live_s = live[order]
        valid_s = live_s if vvb is None else (live_s & vvb[order])
        cntv = jnp.sum(valid_s).astype(jnp.int64)
        has = one(cntv > 0)
        if agg.func == "approx_percentile":
            p = float(agg.param if agg.param is not None else 0.5)
            k = jnp.clip(
                jnp.ceil(p * cntv.astype(jnp.float64)).astype(jnp.int64)
                - 1,
                0,
                jnp.maximum(cntv - 1, 0),
            )
            data = one(vd[order][jnp.minimum(k, cap - 1)])
            return Block(data=data, valid=has, dtype=agg.arg.dtype)
        xd, xv = lowerer.eval(agg.arg)
        xd_s = jnp.broadcast_to(xd, (cap,))[order]
        idx = (
            jnp.int64(0)
            if agg.func == "min_by"
            else jnp.minimum(jnp.maximum(cntv - 1, 0), cap - 1)
        )
        valid = cntv > 0
        if xv is not None:
            valid = valid & jnp.broadcast_to(xv, (cap,))[order][idx]
        dictionary = None
        if agg.arg.dtype.is_string:
            dictionary = lowerer.dictionary_of(agg.arg)
        return Block(
            data=one(xd_s[idx]), valid=one(valid),
            dtype=agg.arg.dtype, dictionary=dictionary,
        )

    d, v = lowerer.eval(agg.arg)
    d = jnp.broadcast_to(d, (page.capacity,))
    valid = live if v is None else (live & jnp.broadcast_to(v, live.shape))
    cnt = jnp.sum(valid).astype(jnp.int64)

    if agg.func == "count":
        return Block(data=one(cnt), valid=None, dtype=T.BIGINT)

    has = one(cnt > 0)
    at = agg.arg.dtype

    if agg.func in _VARIANCE_FUNCS:
        x = d.astype(jnp.float64)
        if at.is_decimal:
            x = x / (10 ** at.scale)
        x = jnp.where(valid, x, 0.0)
        blk = _variance_block(
            one(jnp.sum(x)), one(jnp.sum(x * x)), one(cnt), agg.func
        )
        return blk

    if agg.func in ("sum", "avg"):
        if at.name in ("double", "real") or agg.func == "avg":
            x = d.astype(jnp.float64)
            if at.is_decimal:
                x = x / (10 ** at.scale)
            s = jnp.sum(jnp.where(valid, x, 0.0))
            if agg.func == "avg":
                return Block(
                    data=one(s / jnp.maximum(cnt, 1)),
                    valid=has,
                    dtype=T.DOUBLE,
                )
            return Block(data=one(s), valid=has, dtype=T.DOUBLE)
        s = jnp.sum(jnp.where(valid, d.astype(jnp.int64), 0))
        return Block(data=one(s), valid=has, dtype=agg.result_type())

    if agg.func in ("min", "max"):
        reduce = jnp.min if agg.func == "min" else jnp.max
        if at.name in ("double", "real"):
            fill = jnp.inf if agg.func == "min" else -jnp.inf
            data = one(
                reduce(jnp.where(valid, d.astype(jnp.float64), fill))
            ).astype(at.jnp_dtype)
        else:
            info = jnp.iinfo(jnp.int64)
            fill = info.max if agg.func == "min" else info.min
            data = one(
                reduce(jnp.where(valid, d.astype(jnp.int64), fill))
            ).astype(at.jnp_dtype)
        dictionary = None
        if at.is_string:
            dictionary = lowerer.dictionary_of(agg.arg)
        return Block(
            data=data, valid=has, dtype=at, dictionary=dictionary
        )

    raise NotImplementedError(f"aggregate {agg.func}")
