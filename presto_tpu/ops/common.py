"""Shared kernel utilities: orderable keys, lexicographic sort orders.

The TPU has no comparator trees for structs — multi-column orderings are
expressed as a sequence of stable int64 sorts (XLA sorts are fast,
vectorized, and fuse with the surrounding gather). Every SQL type maps to
an *order-preserving* int64 image (``orderable_i64``), so one code path
serves sort, group-by boundary detection, merge and join kernels.
"""

from __future__ import annotations

from typing import List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp

from presto_tpu import types as T


def float_bits_i64(data: jnp.ndarray) -> jnp.ndarray:
    """The IEEE754 binary64 bit pattern of a float column as int64,
    with -0.0 read as +0.0 and every NaN as the canonical quiet NaN.

    Built by arithmetic, not by ``f.view(int64)``: the v5e compiler
    refuses a bitcast *from* float64 (``UNIMPLEMENTED ... X64 element
    types ... bitcast-convert``; the int64 -> float64 direction, used
    below for the exact power of two, compiles). ``log2`` only guesses
    the exponent; the two corrections make it exact, so on IEEE
    hardware the result equals the bitcast bit for bit (the host twin
    ``exec.host_ops.orderable_np`` relies on that). Subnormals read as
    zero, as they always have on the device: XLA flushes them."""
    f = jnp.asarray(data, jnp.float64)
    a = jnp.abs(f)
    nan = f != f
    inf = a == jnp.inf
    normal = ~nan & ~inf & (a >= 2.0 ** -1022)
    s = jnp.where(normal, a, 1.0)
    e = jnp.clip(jnp.floor(jnp.log2(s)).astype(jnp.int64), -1022, 1023)
    q = s / ((e + 1023) << 52).view(jnp.float64)  # s / 2**e, exact
    low = q < 1.0
    e, q = jnp.where(low, e - 1, e), jnp.where(low, q * 2.0, q)
    high = q >= 2.0
    e, q = jnp.where(high, e + 1, e), jnp.where(high, q * 0.5, q)
    mag = ((e + 1023) << 52) + ((q - 1.0) * 2.0 ** 52).astype(jnp.int64)
    mag = jnp.where(normal, mag, 0)
    mag = jnp.where(inf, jnp.int64(0x7FF0000000000000), mag)
    bits = jnp.where(f < 0, mag | jnp.int64(-(2 ** 63)), mag)
    return jnp.where(nan, jnp.int64(0x7FF8000000000000), bits)


def orderable_i64(data: jnp.ndarray, dtype: T.DataType) -> jnp.ndarray:
    """Map a column to int64 such that int comparison == SQL comparison.

    - ints/dates/decimals/dict-ids: widen to int64 (dict ids are
      order-preserving by construction, presto_tpu.page.Dictionary)
    - floats: sign-magnitude bit trick (IEEE754 totally ordered for
      non-NaN; -0.0 = +0.0; NaN sorts last as in the reference's
      ORDER BY)
    """
    if dtype.is_long_decimal:
        # a (cap, 2) limb pair does not fit ONE orderable int64 — the
        # multi-lane callers (sort_order/boundaries via key_lanes)
        # handle long decimals; anything still calling the scalar form
        # (single-int64 join packing) gets the documented deviation
        raise NotImplementedError(
            "long decimals (p>18) do not reduce to a single orderable "
            "int64 lane — use key_lanes()"
        )
    if dtype.name in ("double", "real"):
        bits = float_bits_i64(data)
        # IEEE754 total order as signed int64: positives keep their bit
        # pattern in [0, 2^63); negatives map to ~bits with the sign bit
        # set, landing in [-2^63, 0) in reversed-magnitude order.
        return jnp.where(bits >= 0, bits, (~bits) | jnp.int64(-(2 ** 63)))
    if dtype.name == "boolean":
        return data.astype(jnp.int64)
    return jnp.asarray(data).astype(jnp.int64)


def key_lanes(data: jnp.ndarray, dtype: T.DataType) -> List[jnp.ndarray]:
    """A key column as 1..2 order-preserving int64 lanes, most
    significant first. Long decimals ((cap, 2) int64 limb pairs —
    types.LongDecimalType layout) expand to [hi, lo-as-unsigned]:
    lexicographic comparison of the lane pair equals int128 comparison
    (lo's int64 bit pattern gets the sign bit flipped so signed lane
    order matches its unsigned-limb order). Every other type is the
    single ``orderable_i64`` lane."""
    if dtype.is_long_decimal:
        d = jnp.asarray(data)
        hi = d[..., 0].astype(jnp.int64)
        lo = d[..., 1].astype(jnp.int64) ^ jnp.int64(-(2 ** 63))
        return [hi, lo]
    return [orderable_i64(data, dtype)]


def cumsum(x: jnp.ndarray) -> jnp.ndarray:
    """Inclusive running sum along axis 0. Floats go through
    ``lax.associative_scan``: ``jnp.cumsum`` lowers to a
    ``reduce_window``, and over float64 — emulated on the TPU — the v5e
    compiler spends minutes of code generation on it (65536 rows: more
    than 150 s against 3.4 s for the scan's log-depth adds; PR 22,
    compiled for the described chip). Integer cumsums compile in
    seconds as they are."""
    if jnp.issubdtype(x.dtype, jnp.floating):
        return jax.lax.associative_scan(jnp.add, x)
    return jnp.cumsum(x)


_SIGN32 = 0x80000000


def _u32_lanes(lane: jnp.ndarray, narrow: bool) -> List[jnp.ndarray]:
    """An int64 sort lane as uint32 lanes, LEAST significant first,
    whose unsigned lexicographic order is the lane's signed order.
    ``narrow``: the values are known to fit int32 (the column is
    stored in 32 bits or fewer), so one lane carries them."""
    if narrow:
        return [lane.astype(jnp.int32).astype(jnp.uint32) ^ _SIGN32]
    lo = (lane & 0xFFFFFFFF).astype(jnp.uint32)
    hi = (lane >> 32).astype(jnp.int32).astype(jnp.uint32) ^ _SIGN32
    return [lo, hi]


def lexsort_u32(lanes: Sequence[jnp.ndarray]) -> jnp.ndarray:
    """``jnp.lexsort`` over uint32 lanes (last lane primary) as one
    stable single-key sort per lane, least significant first, with the
    int32 permutation riding as the only payload.

    Why not ONE variadic sort: the v5e compiler's time for a sort grows
    steeply with the comparator's operand count and hardly with the
    row count — a three-key int64 ``lexsort`` (what a one-column ORDER
    BY used to lower to) compiled for 374 s at 1 M rows and 125 s at
    16,384, against 49 s and 7 s for the passes here, each the same
    two-operand program (PR 22; compile times from compiling for the
    described chip on the sandbox's CPU, run time not measured)."""
    perm = jnp.arange(lanes[0].shape[0], dtype=jnp.int32)
    for i, lane in enumerate(lanes):
        key = lane if i == 0 else lane[perm]
        _, perm = jax.lax.sort((key, perm), num_keys=1, is_stable=True)
    return perm


def sort_u32_lanes(
    lanes: Sequence[jnp.ndarray], payloads: Sequence[jnp.ndarray] = ()
) -> Tuple[List[jnp.ndarray], List[jnp.ndarray]]:
    """Rows sorted stably by uint32 lanes (last lane primary, as
    :func:`lexsort_u32`), returned as the sorted lanes and payloads
    themselves. Each pass still compares ONE key; the other lanes and
    the payloads ride it as operands, so no ``lane[perm]`` gather runs
    between the passes and none after them (on the v5e a gather of 2^20
    rows costs ten such passes: PERF.md §6, PRs 35 and 36)."""
    ops = list(lanes) + list(payloads)
    for i in range(len(lanes)):
        out = jax.lax.sort(
            (ops[i], *ops[:i], *ops[i + 1:]), num_keys=1, is_stable=True
        )
        ops = [*out[1:i + 1], out[0], *out[i + 1:]]
    return ops[:len(lanes)], ops[len(lanes):]


def argsort_i64(x: jnp.ndarray) -> jnp.ndarray:
    """Stable argsort of one int64 key (int32 permutation), as two
    uint32 passes — see :func:`lexsort_u32`."""
    return lexsort_u32(_u32_lanes(jnp.asarray(x, jnp.int64), False))


def _is_narrow(data: jnp.ndarray, dtype: T.DataType) -> bool:
    """True when ``orderable_i64`` of this column fits int32."""
    if dtype.is_long_decimal or dtype.name in ("double", "real"):
        return False
    dt = jnp.asarray(data).dtype
    return dt == jnp.bool_ or (
        jnp.issubdtype(dt, jnp.integer) and dt.itemsize <= 4
        and dt != jnp.uint32
    )


def sort_order(
    keys: Sequence[Tuple[jnp.ndarray, Optional[jnp.ndarray], T.DataType]],
    live: jnp.ndarray,
    descending: Optional[Sequence[bool]] = None,
    nulls_first: Optional[Sequence[bool]] = None,
) -> jnp.ndarray:
    """Permutation sorting rows by keys (list of (data, valid, dtype)),
    live rows first. SQL default: nulls last in ASC, first in DESC
    (reference: NULLS LAST semantics for ASC ordering).

    Multi-lane keys (long decimals) contribute all their lanes at one
    significance position: DESC flips every lane (lexicographic reverse
    of (hi, lo) is (~hi, ~lo)), and the null rank stays per-KEY.
    """
    n = len(keys)
    descending = descending or [False] * n
    nulls_first = nulls_first or [d for d in descending]
    lex: List[jnp.ndarray] = []
    # lexsort order: LAST lane is primary -> emit least-significant first
    for (data, valid, dtype), desc, nf in zip(
        reversed(list(keys)), reversed(list(descending)), reversed(list(nulls_first))
    ):
        narrow = _is_narrow(data, dtype)
        for lane in reversed(key_lanes(data, dtype)):
            # bitwise-not reverses order without INT64_MIN overflow
            # (and keeps a narrow lane narrow)
            lex.extend(_u32_lanes(~lane if desc else lane, narrow))
        if valid is not None:  # more significant than the value
            lex.append(
                jnp.where(valid, 1, 0 if nf else 2).astype(jnp.uint32)
            )
    lex.append(jnp.where(live, 0, 1).astype(jnp.uint32))  # live first
    return lexsort_u32(lex)


def boundaries(
    sorted_keys: Sequence[Tuple[jnp.ndarray, Optional[jnp.ndarray]]],
    live_sorted: jnp.ndarray,
) -> jnp.ndarray:
    """True where a new group starts (first live row or any key change).
    Inputs already sorted; nulls group together (SQL GROUP BY)."""
    first = jnp.zeros(live_sorted.shape, jnp.bool_).at[0].set(True)
    change = first
    for data, valid in sorted_keys:
        d = jnp.asarray(data)
        neq = d[1:] != d[:-1]
        if d.ndim == 2:  # long-decimal limb pairs: any limb differs
            neq = jnp.any(neq, axis=-1)
        if jnp.issubdtype(d.dtype, jnp.floating):
            # NaN != NaN, but SQL grouping puts all NaNs in one group
            neq = neq & ~(jnp.isnan(d[1:]) & jnp.isnan(d[:-1]))
        diff = jnp.concatenate([jnp.ones((1,), jnp.bool_), neq])
        if valid is not None:
            v = jnp.asarray(valid)
            vdiff = jnp.concatenate(
                [jnp.ones((1,), jnp.bool_), v[1:] != v[:-1]]
            )
            diff = diff | vdiff
            # two nulls are the same group regardless of payload data
            both_null = jnp.concatenate(
                [jnp.zeros((1,), jnp.bool_), (~v[1:]) & (~v[:-1])]
            )
            diff = diff & ~both_null
        change = change | diff
    return change & live_sorted
