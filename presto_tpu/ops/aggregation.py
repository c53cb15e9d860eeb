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
- **sorted path**: general keys — one stable multi-key sort brings equal
  keys together; every accumulator is then a *scan*, not a scatter:
  sums/counts are inclusive-cumsum differences at group boundaries,
  min/max are segmented associative scans read at group ends.
- **packed sort** (PR 35): where every key's values are proved to lie
  in a range — a dictionary's length, a boolean, or the inclusive
  ``(lo, hi)`` the connector's column statistics state
  (``AggregationNode.key_ranges``) — and the composite fits 32 bits,
  the keys pack into ONE uint32 with dead rows as its largest value.
  One two-operand sort then does what a lane-by-lane ``lexsort`` over
  emulated int64 did (2.0 against 20.3 ms for 2^20 rows on the v5e,
  ``chiprun_out/pr33/micro_q15.json``), the sorted key itself says
  which rows live and where groups start (no gather of the key and
  mask columns, 17.4 and 10.9 ms), and a second such sort compacts the
  group starts (against 73.5 ms for ``nonzero``'s scatter). The
  accumulators are the sorted path's own, so both give the same page.
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

Exactness note: decimal/bigint sums on the sorted path are inclusive
int64 cumsums differenced at boundaries — exact unless the *running
total over the whole page* exceeds int64, a stricter-than-SQL bound
(the reference overflows per-group). A traced overflow trap (float64
shadow cumsum compared against the int64 cumsum; a wrap displaces the
value by ~2^64, far beyond float accumulation error) raises through the
error-flag channel instead of returning silently wrong sums. Float sums
use per-segment scans (not the cumsum trick) so no cross-group
cancellation is introduced.
"""

from __future__ import annotations

import dataclasses
from typing import List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
from jax import lax

from presto_tpu import types as T
from presto_tpu.expr import Expr, ExprLowerer
from presto_tpu.ops.common import (
    boundaries,
    cumsum,
    lexsort_u32,
    sort_order,
)
from presto_tpu.page import Block, Page, nonzero_1d

#: the packed sort key of a dead row: past every composite
_DEAD = 0xFFFFFFFF


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
    individual group sums are in range — see _sorted_one_agg).

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
        # these need the sorted layout (array_agg: group spans ARE the
        # output arrays; percentile/min_by/max_by: a per-group value
        # ordering, by a second sort over the keys as they are); skip
        # the one-hot and packed paths
        return _sorted_aggregate(
            page, keys, aggs, max_groups, live, lowerer, errors_out
        )

    domains = [_static_domain(e, lowerer) for _, _, _, e in keys]
    ranges = list(key_ranges) + [None] * (len(keys) - len(key_ranges))
    # per key: (smallest value, how many values) it is proved to take
    proofs = [
        (0, dom) if dom is not None
        else (rng[0], rng[1] - rng[0] + 1) if rng is not None
        else None
        for dom, rng in zip(domains, ranges)
    ]
    nseg = None
    if all(p is not None for p in proofs):
        slots = [
            max(n + (1 if v is not None else 0), 1)
            for (_, n), (_, _, v, _) in zip(proofs, keys)
        ]
        nseg = 1
        for sl in slots:
            nseg *= sl
    out_cap = _out_capacity(max_groups, rows, nseg)

    if (
        nseg is not None and nseg <= _ONEHOT_MAX_SEGMENTS
        and all(d is not None for d in domains)
    ):
        return _onehot_aggregate(
            page, keys, domains, slots, nseg, aggs, max_groups, out_cap,
            live, lowerer,
        )
    packed = None
    if nseg is not None and nseg <= _DEAD:
        packed = _packed_key(keys, proofs, slots, domains, live)
    return _sorted_aggregate(
        page, keys, aggs, max_groups, live, lowerer, errors_out,
        out_cap=out_cap, packed=packed,
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


def _group_spans(
    starts: jnp.ndarray, cap: int
) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """(starts, ends) sorted-space positions per group (gather-safe),
    from the groups' first positions with ``cap`` in the slots past the
    last group.

    ``ends[i] = starts[i+1] - 1`` with cap-1 for the final/fill groups —
    safe because rows past the live prefix carry neutral values for every
    accumulator (0 for cumsum deltas, +-inf fills for min/max scans).
    """
    nxt = jnp.concatenate(
        [starts[1:], jnp.full((1,), cap, starts.dtype)]
    )
    ends = jnp.clip(nxt - 1, 0, cap - 1)
    safe_starts = jnp.minimum(starts, cap - 1).astype(jnp.int32)
    return safe_starts, ends.astype(jnp.int32)


def _packed_key(keys, proofs, slots, domains, live):
    """The group keys of every row as ONE uint32, ordered as the sorted
    path orders groups: the first key most significant, a key's NULL
    slot its largest value (nulls group last), dead rows ``_DEAD``,
    past every composite. ``proofs``: per key ``(lo, n)``, the smallest
    value and the number of values it may take; ``slots``: ``n`` plus
    the NULL slot where the key is nullable. Returns ``(key, proofs,
    slots, stale)``.

    A key proved by a dictionary or a type cannot leave its domain. One
    whose range the connector's statistics state (``domains`` has None
    for it) is held to them: ``stale`` is true where a live, non-null
    value lies outside ``[lo, lo + n)`` — it would land in another
    group's slot, so the caller reports the batch as overflowed and it
    runs again with nothing stated."""
    gid = jnp.zeros(live.shape, jnp.uint32)
    stale = jnp.asarray(False)
    for (name, d, v, e), (lo, n), sl, dom in zip(
        keys, proofs, slots, domains
    ):
        d = jnp.broadcast_to(d, live.shape)
        if dom is None:
            wide = d.astype(jnp.int64)
            outside = live & ((wide < lo) | (wide >= lo + n))
            if v is not None:
                outside = outside & v
            stale = stale | jnp.any(outside)
            comp = (wide - lo).astype(jnp.uint32)
        else:
            comp = d.astype(jnp.uint32)
        if v is not None:
            comp = jnp.where(v, comp, jnp.uint32(n))
        gid = gid * jnp.uint32(sl) + comp
    gid = jnp.where(live, gid, jnp.uint32(_DEAD))
    return gid, proofs, slots, stale


def _unpack_keys(key_g: jnp.ndarray, keys, proofs, slots, lowerer):
    """The key blocks of the groups whose packed keys are ``key_g``:
    ``_packed_key`` read backwards, so no key column is gathered."""
    strides, stride = [], 1
    for sl in reversed(slots):
        strides.append(stride)
        stride *= sl
    blocks = []
    for (name, d, v, e), (lo, n), sl, stride in zip(
        keys, proofs, slots, reversed(strides)
    ):
        comp = key_g
        if stride > 1:
            comp = comp // jnp.uint32(stride)
        if len(slots) > 1:
            comp = comp % jnp.uint32(sl)
        data = comp if lo == 0 else comp.astype(jnp.int64) + lo
        blocks.append(
            Block(
                data=data.astype(jnp.asarray(d).dtype),
                valid=None if v is None else comp != jnp.uint32(n),
                dtype=e.dtype,
                dictionary=(
                    lowerer.dictionary_of(e) if e.dtype.is_string else None
                ),
            )
        )
    return blocks


def _packed_groups(gid: jnp.ndarray, out_cap: int, carried=()):
    """Sort rows by their packed key: ``(order, key_s, live_s, bnd,
    num_groups, starts, carried_s)`` as the sorted path has them — the
    permutation (stable, so a group's rows keep their order), the
    sorted key, which sorted rows live, where a group starts, the
    groups' first positions with the capacity in the slots past the
    last group, and the ``carried`` columns in sorted order. Two sorts
    of one uint32 key: the first takes the columns along as payloads —
    a payload costs the sort little, a gather of 2^20 int64 by the
    permutation cost 17.4 ms on the v5e
    (``chiprun_out/pr33/micro_q15.json``; 20.5 ms a batch in Q15's
    trace, PERF.md §6, PR 35) —, the second brings the rows that start
    a group to the front in order, which is what ``nonzero`` computes
    with a scatter."""
    cap = gid.shape[0]
    iota = jnp.arange(cap, dtype=jnp.int32)
    key_s, order, *carried_s = lax.sort(
        (gid, iota, *carried), num_keys=1, is_stable=True
    )
    live_s = key_s != jnp.uint32(_DEAD)
    prev = jnp.concatenate([key_s[:1], key_s[:-1]])
    bnd = live_s & ((iota == 0) | (key_s != prev))
    num_groups = jnp.sum(bnd).astype(jnp.int32)
    _, first = lax.sort(
        (jnp.where(bnd, 0, 1).astype(jnp.uint32), iota),
        num_keys=1, is_stable=True,
    )
    if out_cap > cap:
        first = jnp.concatenate(
            [first, jnp.full((out_cap - cap,), cap, jnp.int32)]
        )
    starts = jnp.where(
        jnp.arange(out_cap, dtype=jnp.int32) < num_groups,
        first[:out_cap], cap,
    )
    return order, key_s, live_s, bnd, num_groups, starts, carried_s


def _carried_args(aggs, cap: int, lowerer: ExprLowerer):
    """The aggregates' arguments that ride the packed sort: per
    aggregate with an integer-typed argument (ints, dates, scaled
    decimals, dictionary ids, booleans) the positions of its data and
    its validity among the ``columns`` to carry. Floats and long
    decimals are gathered by the permutation as before."""
    columns, where, seen = [], {}, {}
    for i, agg in enumerate(aggs):
        if agg.arg is None:
            continue
        if agg.arg in seen:  # sum(x) and count(x) share x
            where[i] = seen[agg.arg]
            continue
        d, v = lowerer.eval(agg.arg)
        d = jnp.broadcast_to(d, (cap,) + jnp.shape(d)[1:])
        if d.ndim != 1 or jnp.issubdtype(d.dtype, jnp.floating):
            continue
        at = [len(columns), None]
        columns.append(d.astype(jnp.uint8) if d.dtype == jnp.bool_ else d)
        if v is not None:
            at[1] = len(columns)
            columns.append(jnp.broadcast_to(v, (cap,)).astype(jnp.uint8))
        where[i] = seen[agg.arg] = (at[0], at[1], d.dtype)
    return columns, where


def _sorted_aggregate(
    page: Page,
    keys,
    aggs: Sequence[AggCall],
    max_groups: int,
    live: jnp.ndarray,
    lowerer: ExprLowerer,
    errors_out: Optional[List] = None,
    out_cap: Optional[int] = None,
    packed: Optional[tuple] = None,
) -> Tuple[Page, jnp.ndarray]:
    """Sort rows by key, then every accumulator is a scan read at the
    groups' spans. ``packed`` (``_packed_key``) takes the packed sort;
    without it the keys sort lane by lane as they are. Both bring the
    same rows together in the same order, so the page is the same.
    ``out_cap``: the page's slots where the caller knows a proved
    domain; ``_out_capacity`` without one."""
    cap = page.capacity
    if out_cap is None:
        out_cap = _out_capacity(max_groups, cap, None)
    sorted_args = {}  # aggregate -> its argument, already in sorted order
    if packed is not None:
        gid, proofs, slots, stale = packed
        columns, where = _carried_args(aggs, cap, lowerer)
        order, key_s, live_s, bnd, num_groups, first, columns = (
            _packed_groups(gid, out_cap, columns)
        )
        for i, (at_d, at_v, dtype) in where.items():
            d = columns[at_d]
            sorted_args[i] = (
                d != 0 if dtype == jnp.bool_ else d,
                None if at_v is None else columns[at_v] != 0,
            )
    else:
        order = sort_order(
            [(d, v, e.dtype) for _, d, v, e in keys], live
        )
        live_s = live[order]
        keys_s = [
            (name, d[order], None if v is None else v[order], e)
            for name, d, v, e in keys
        ]
        bnd = boundaries([(d, v) for _, d, v, _ in keys_s], live_s)
        num_groups = jnp.sum(bnd).astype(jnp.int32)
        first = nonzero_1d(bnd, out_cap, cap)
        stale = False
    overflow = (num_groups > max_groups) | stale

    starts, ends = _group_spans(first, cap)

    names: List[str] = []
    blocks: List[Block] = []
    if packed is not None:
        blocks.extend(
            _unpack_keys(key_s[starts], keys, proofs, slots, lowerer)
        )
        names.extend(name for name, _, _, _ in keys)
    else:
        for name, d, v, e in keys_s:
            names.append(name)
            dictionary = None
            if e.dtype.is_string:
                dictionary = lowerer.dictionary_of(e)
            blocks.append(
                Block(
                    data=d[starts],
                    valid=None if v is None else v[starts],
                    dtype=e.dtype,
                    dictionary=dictionary,
                )
            )

    for i, agg in enumerate(aggs):
        if agg.func in ("approx_percentile", "min_by", "max_by"):
            blk = _order_stat_agg(
                agg, page, keys, live, starts, ends, lowerer
            )
        else:
            blk = _sorted_one_agg(
                agg, page, order, live_s, bnd, starts, ends, lowerer,
                errors_out, arg_s=sorted_args.get(i),
            )
        names.append(agg.out_name)
        blocks.append(blk)

    out = Page(
        blocks=tuple(blocks),
        num_valid=jnp.minimum(num_groups, max_groups).astype(jnp.int32),
        names=tuple(names),
    )
    return out, overflow


def _order_stat_agg(
    agg: AggCall,
    page: Page,
    keys,  # ORIGINAL (unsorted) key evals: [(name, d, v, e), ...]
    live: jnp.ndarray,
    starts: jnp.ndarray,
    ends: jnp.ndarray,
    lowerer: ExprLowerer,
) -> Block:
    """approx_percentile / min_by / max_by on the sorted path.

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
    live2 = live[order2]
    valid2 = live2 if vvb is None else (live2 & vvb[order2])
    cntv = _cumsum_span(valid2.astype(jnp.int64), starts, ends)
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
            data=vd[order2][idx], valid=group_has, dtype=agg.arg.dtype
        )

    xd, xv = lowerer.eval(agg.arg)
    xd2 = jnp.broadcast_to(xd, (cap,))[order2]
    if agg.func == "min_by":
        idx = starts
    else:
        idx = jnp.minimum(
            starts.astype(jnp.int64) + jnp.maximum(cntv - 1, 0),
            cap - 1,
        ).astype(jnp.int32)
    valid = group_has
    if xv is not None:
        valid = valid & jnp.broadcast_to(xv, (cap,))[order2][idx]
    dictionary = None
    if agg.arg.dtype.is_string:
        dictionary = lowerer.dictionary_of(agg.arg)
    return Block(
        data=xd2[idx], valid=valid, dtype=agg.arg.dtype,
        dictionary=dictionary,
    )


def _cumsum_span(
    w: jnp.ndarray, starts: jnp.ndarray, ends: jnp.ndarray
) -> jnp.ndarray:
    """Per-group totals of ``w`` via inclusive cumsum differenced over
    [start, end] spans (no scatter): the running total at the span's
    end less the one before its start, two gathers a span (each 2.7 ms
    at 2^17 slots on the v5e, ``chiprun_out/pr33/micro_q15.json``)."""
    c = cumsum(w)
    return c[ends] - (c - w)[starts]


def _sorted_one_agg(
    agg: AggCall,
    page: Page,
    order: jnp.ndarray,
    live_s: jnp.ndarray,
    bnd: jnp.ndarray,
    starts: jnp.ndarray,
    ends: jnp.ndarray,
    lowerer: ExprLowerer,
    errors_out: Optional[List] = None,
    arg_s: Optional[tuple] = None,
) -> Block:
    """One aggregate over rows in sorted order. ``arg_s``: its
    argument's ``(data, valid)`` where they are in sorted order already
    (they rode the packed sort); evaluated and gathered by ``order``
    otherwise."""
    rt = agg.result_type()

    if agg.func == "count_star":
        data = _cumsum_span(live_s.astype(jnp.int64), starts, ends)
        return Block(data=data, valid=None, dtype=T.BIGINT)

    if agg.func == "array_agg":
        # the sorted layout IS the concatenated per-group arrays
        # (groups are contiguous spans); NULL inputs are SKIPPED, so
        # valid values scatter to their rank among valid rows — stable,
        # so groups stay contiguous — and group offsets are the valid
        # counts at group starts. (Deviation: the reference's
        # array_agg default INCLUDES nulls; arrays here carry no
        # element validity.)
        cap = page.capacity
        d, v = lowerer.eval(agg.arg)
        d_s = jnp.broadcast_to(d, (cap,))[order]
        valid_s = live_s if v is None else (
            live_s & jnp.broadcast_to(v, (cap,))[order]
        )
        cum = jnp.cumsum(valid_s.astype(jnp.int32))
        total = cum[-1] if cap else jnp.int32(0)
        pos = jnp.where(valid_s, cum - 1, cap)  # cap = dump slot
        out_vals = jnp.zeros((cap + 1,), d_s.dtype).at[pos].set(d_s)
        # padding group slots must read offset == total; the CLAMPED
        # starts (cap-1) would read total-1 on a completely full page
        # and silently drop the last group's last element, so detect
        # padding from the UNCLAMPED boundary positions
        raw_starts = nonzero_1d(bnd, starts.shape[0], cap)
        start_off = jnp.where(
            raw_starts >= cap,
            total,
            cum[starts] - valid_s[starts].astype(jnp.int32),
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
            dtype=rt,
            dictionary=dictionary,
            offsets=offsets,
        )

    if arg_s is None:
        d, v = lowerer.eval(agg.arg)
        d = jnp.broadcast_to(d, (page.capacity,))[order]
        if v is not None:
            v = jnp.broadcast_to(v, (page.capacity,))[order]
    else:
        d, v = arg_s
    valid_s = live_s if v is None else (live_s & v)

    if agg.func == "count":
        data = _cumsum_span(valid_s.astype(jnp.int64), starts, ends)
        return Block(data=data, valid=None, dtype=T.BIGINT)

    if v is None and agg.func in ("sum", "min", "max"):
        # an argument that is never NULL: a group has a value because it
        # has a row, so no count is scanned and gathered to say so (a
        # cumsum and two gathers at the page's slots: some 7 ms of a
        # 2^20-row batch on the v5e)
        cnt = None
        group_has_value = (
            jnp.arange(starts.shape[0], dtype=jnp.int32) < jnp.sum(bnd)
        )
    else:
        cnt = _cumsum_span(valid_s.astype(jnp.int64), starts, ends)
        group_has_value = cnt > 0

    if agg.func in _VARIANCE_FUNCS:
        at = agg.arg.dtype
        x = d.astype(jnp.float64)
        if at.is_decimal:
            x = x / (10 ** at.scale)
        x = jnp.where(valid_s, x, 0.0)
        s1 = _segmented_scan_reduce(x, bnd, jnp.add)[ends]
        s2 = _segmented_scan_reduce(x * x, bnd, jnp.add)[ends]
        return _variance_block(s1, s2, cnt, agg.func)

    if agg.func in ("sum", "avg"):
        at = agg.arg.dtype
        if at.name in ("double", "real") or agg.func == "avg":
            # decimal avg and double sums: SEGMENTED scan, not a global
            # cumsum — differencing a whole-page running float total
            # would cancel catastrophically for small late groups
            x = d.astype(jnp.float64)
            if at.is_decimal:
                x = x / (10 ** at.scale)
            x = jnp.where(valid_s, x, 0.0)
            s = _segmented_scan_reduce(x, bnd, jnp.add)[ends]
            if agg.func == "avg":
                data = s / jnp.maximum(cnt, 1)
                return Block(
                    data=data, valid=group_has_value, dtype=T.DOUBLE
                )
            return Block(data=s, valid=group_has_value, dtype=T.DOUBLE)
        x = jnp.where(valid_s, d.astype(jnp.int64), 0)
        s = _cumsum_span(x, starts, ends)
        if errors_out is not None:
            # per-group overflow trap: the differenced int64 sums are
            # exact under modular arithmetic whenever the TRUE group sum
            # fits int64 (even if the page-wide running total wraps), so
            # the check must be per group — a float64 shadow of the same
            # span difference. A real per-group overflow displaces the
            # int result ~2^64 from the shadow; float cancellation error
            # stays many orders below the 2^62 threshold. The shadow (a
            # float64 scan and two gathers) runs only for a page whose
            # magnitudes add up to 2^62 or more: under that no group of
            # it can overflow, and a sum of magnitudes is one reduction.
            xf = x.astype(jnp.float64)

            def shadow():
                sf = _cumsum_span(xf, starts, ends)
                return jnp.any(
                    jnp.abs(s.astype(jnp.float64) - sf) > 2.0**62
                )

            wrapped = lax.cond(
                jnp.sum(jnp.abs(xf)) < 2.0**62,
                lambda: jnp.asarray(False), shadow,
            )
            errors_out.append(
                (f"bigint sum overflow in {agg.out_name}", wrapped)
            )
        return Block(data=s, valid=group_has_value, dtype=rt)

    if agg.func in ("min", "max"):
        at = agg.arg.dtype
        op = jnp.minimum if agg.func == "min" else jnp.maximum
        if at.name in ("double", "real"):
            fill = jnp.inf if agg.func == "min" else -jnp.inf
            x = jnp.where(valid_s, d.astype(jnp.float64), fill)
            scan = _segmented_scan_reduce(x, bnd, op)
            data = scan[ends].astype(at.jnp_dtype)
        else:
            info = jnp.iinfo(jnp.int64)
            fill = info.max if agg.func == "min" else info.min
            x = jnp.where(valid_s, d.astype(jnp.int64), fill)
            scan = _segmented_scan_reduce(x, bnd, op)
            data = scan[ends].astype(at.jnp_dtype)
        dictionary = None
        if at.is_string:
            dictionary = lowerer.dictionary_of(agg.arg)
        return Block(
            data=data, valid=group_has_value, dtype=at, dictionary=dictionary
        )

    raise NotImplementedError(f"aggregate {agg.func}")


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
