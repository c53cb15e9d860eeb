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
- Shapes stay static, and each path sizes its output from what it
  knows. The planner supplies ``max_groups``, a bucket of its ROW
  estimate (half the source's rows: 2^24 for Q1 at SF10, which has four
  groups). The sorted path has nothing better — unbounded keys carry no
  proof — so its output is ``max_groups`` long; kernels report overflow
  instead of reallocating, and the host re-runs at a bigger bucket on
  overflow (SURVEY.md §7 "Hard parts: dynamic shapes"). The one-hot
  path holds a proof of its key domain (``nseg`` <= 256 segments), so it
  builds its output at that domain's bucket,
  ``min(max_groups, bucket_capacity(nseg))``, whatever the planner
  estimated. The global path emits one row.

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


def hash_aggregate(
    page: Page,
    group_keys: Sequence[Tuple[str, Expr]],
    aggs: Sequence[AggCall],
    max_groups: int,
    errors_out: Optional[List] = None,
) -> Tuple[Page, jnp.ndarray]:
    """Group ``page`` by key expressions, compute aggregates.

    Returns (result_page, overflow) where overflow is a traced bool: True
    when the data had more than ``max_groups`` groups (host must re-run
    with a larger bucket; surplus groups were dropped).

    ``errors_out``, when given, collects ``(message, traced_bool)`` hard
    errors — currently the bigint-sum overflow trap of the sorted path
    (the reference raises on per-group bigint overflow; the sorted path's
    page-wide running total would otherwise wrap *silently* even when
    individual group sums are in range — see _sorted_one_agg).

    Global aggregation (no keys) is the plain-reduction degenerate case.
    """
    live = page.row_mask()
    lowerer = ExprLowerer(page)

    if not group_keys:
        return _global_aggregate(page, aggs, live, lowerer)

    keys = [(name, *lowerer.eval(e), e) for name, e in group_keys]

    domains = [_static_domain(e, lowerer) for _, _, _, e in keys]
    if any(a.func in _ORDER_FUNCS for a in aggs):
        # these need the sorted layout (array_agg: group spans ARE the
        # output arrays; percentile/min_by/max_by: a per-group value
        # ordering); skip the one-hot fast path
        return _sorted_aggregate(
            page, keys, aggs, max_groups, live, lowerer, errors_out
        )
    if all(d is not None for d in domains):
        slots = [
            d + (1 if v is not None else 0)
            for d, (_, _, v, _) in zip(domains, keys)
        ]
        nseg = 1
        for s in slots:
            nseg *= max(s, 1)
        if 0 < nseg <= _ONEHOT_MAX_SEGMENTS:
            return _onehot_aggregate(
                page, keys, domains, slots, nseg, aggs, max_groups,
                live, lowerer,
            )

    return _sorted_aggregate(
        page, keys, aggs, max_groups, live, lowerer, errors_out
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
    live: jnp.ndarray,
    lowerer: ExprLowerer,
) -> Tuple[Page, jnp.ndarray]:
    """Sort-free, scatter-free aggregation over a tiny provable domain.

    Strides assign the first key the most significant position, so
    ascending segment order is lexicographic in the keys (dict ids are
    order-preserving); a key's NULL slot is its largest id (nulls group
    last, matching the sorted path's NULLS LAST grouping order).

    The output page is ``min(max_groups, bucket_capacity(nseg))`` long:
    there are at most ``nseg`` groups by construction (dead rows match no
    column), so nothing here is allocated, scanned, scattered or gathered
    at ``max_groups``. Sized by the planner's bucket instead, Q1's
    partial stage at SF10 built 2^24-slot pages for four groups: 17.6 ms
    of ``reduce-window`` (the compaction's cumsum over the slots) and
    13 ms of copies a 2^20-row batch, 1.73 GB of output of which 105 KB
    was fetched, 2.1 s of device time a statement where 41 ms do
    (PERF.md §6, PR 30). The capacity stays a ``bucket_capacity``
    bucket, the one ``materialize_page`` re-pads a fetched prefix to.
    ``overflow`` can only be true where a caller passes ``max_groups``
    under ``nseg``; the first ``max_groups`` groups are kept then.
    """
    from presto_tpu.exec.staging import bucket_capacity

    cap = page.capacity
    out_cap = min(max_groups, bucket_capacity(nseg))

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
    bnd: jnp.ndarray, max_groups: int, cap: int
) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """(starts, ends) sorted-space positions per group (gather-safe).

    ``ends[i] = starts[i+1] - 1`` with cap-1 for the final/fill groups —
    safe because rows past the live prefix carry neutral values for every
    accumulator (0 for cumsum deltas, +-inf fills for min/max scans).
    """
    starts = nonzero_1d(bnd, max_groups, cap)
    nxt = jnp.concatenate(
        [starts[1:], jnp.full((1,), cap, starts.dtype)]
    )
    ends = jnp.clip(nxt - 1, 0, cap - 1)
    safe_starts = jnp.minimum(starts, cap - 1).astype(jnp.int32)
    return safe_starts, ends.astype(jnp.int32)


def _sorted_aggregate(
    page: Page,
    keys,
    aggs: Sequence[AggCall],
    max_groups: int,
    live: jnp.ndarray,
    lowerer: ExprLowerer,
    errors_out: Optional[List] = None,
) -> Tuple[Page, jnp.ndarray]:
    cap = page.capacity
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
    overflow = num_groups > max_groups

    starts, ends = _group_spans(bnd, max_groups, cap)

    names: List[str] = []
    blocks: List[Block] = []
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

    for agg in aggs:
        if agg.func in ("approx_percentile", "min_by", "max_by"):
            blk = _order_stat_agg(
                agg, page, keys, live, starts, ends, lowerer
            )
        else:
            blk = _sorted_one_agg(
                agg, page, order, live_s, bnd, starts, ends, lowerer,
                errors_out,
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
    [start, end] spans (no scatter)."""
    c = cumsum(w)
    return c[ends] - c[starts] + w[starts]


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
) -> Block:
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

    d, v = lowerer.eval(agg.arg)
    d = jnp.broadcast_to(d, (page.capacity,))[order]
    valid_s = live_s if v is None else (
        live_s & jnp.broadcast_to(v, (page.capacity,))[order]
    )

    if agg.func == "count":
        data = _cumsum_span(valid_s.astype(jnp.int64), starts, ends)
        return Block(data=data, valid=None, dtype=T.BIGINT)

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
            # stays many orders below the 2^62 threshold.
            sf = _cumsum_span(x.astype(jnp.float64), starts, ends)
            wrapped = jnp.any(
                jnp.abs(s.astype(jnp.float64) - sf) > 2.0**62
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
