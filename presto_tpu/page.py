"""Columnar Block/Page data model — device-resident, static-shape.

Reference parity: ``presto-common`` ``Block`` hierarchy (LongArrayBlock,
IntArrayBlock, VariableWidthBlock, DictionaryBlock, RunLengthEncodedBlock)
and ``Page`` — SURVEY.md §2.1 "Block/Page data model".

TPU-first redesign (SURVEY.md §7 "Design stance"):

- A ``Block`` is a pytree of fixed-shape JAX arrays: ``data`` plus an
  optional ``valid`` null-mask. There is no VariableWidthBlock — strings are
  dictionary ids (int32) with the dictionary held host-side (strings never
  touch the device; the VPU only ever sees fixed-width lanes).
- A ``Page`` carries a traced scalar ``num_valid``: the first ``num_valid``
  rows are live, the rest is padding. Filters *compact* survivors to the
  front (static-shape ``jnp.nonzero(size=...)``) instead of shrinking the
  array, so every downstream kernel sees the same shapes and XLA compiles
  each fragment exactly once per capacity bucket.
- Capacity (array length) is static metadata; the planner picks capacity
  buckets so selective filters can step pages down to smaller compiled
  shapes between fragments (host-side re-bucketing).

Blocks/Pages are registered as pytree dataclasses: ``data``/``valid``/
``num_valid`` are leaves (traced), everything else is static aux data that
participates in the jit cache key.
"""

from __future__ import annotations

import dataclasses
import hashlib
from functools import partial
from typing import Dict, List, Optional, Sequence

import jax
import jax.numpy as jnp
import numpy as np

from presto_tpu import types as T


class Dictionary:
    """Host-side, order-preserving string dictionary.

    Ids are assigned in sorted order of the distinct values, so integer
    comparison of ids agrees with lexicographic comparison of the strings
    they encode (within a single dictionary). This is what lets <, =,
    BETWEEN, ORDER BY, and min/max on varchar run entirely on-device over
    int32 lanes; LIKE and other string functions evaluate host-side over
    the (small) dictionary into a boolean lookup table that is then
    gathered on-device (SURVEY.md §7 "Strings on TPU").

    Immutable and hashable (content digest) — safe as static jit metadata.
    """

    __slots__ = ("values", "_str_values", "_index", "_digest")

    def __init__(self, sorted_values: np.ndarray):
        self.values = np.asarray(sorted_values)
        self._str_values = self.values.astype(str)
        self._index: Optional[dict] = None
        h = hashlib.blake2b(digest_size=16)
        h.update(str(len(self.values)).encode())
        for v in self._str_values:
            h.update(v.encode())
            h.update(b"\x00")
        self._digest = h.digest()

    @classmethod
    def build(cls, values: Sequence[str]) -> "Dictionary":
        return cls(np.unique(np.asarray(values, dtype=object)))

    def __len__(self) -> int:
        return len(self.values)

    def __hash__(self):
        return hash(self._digest)

    def __eq__(self, other):
        return isinstance(other, Dictionary) and self._digest == other._digest

    def id_of(self, value: str) -> int:
        """Exact id of value, or -1 if absent."""
        if self._index is None:
            self._index = {v: i for i, v in enumerate(self.values)}
        return self._index.get(value, -1)

    def searchsorted(self, value: str, side: str = "left") -> int:
        """Insertion point of value — supports range predicates on absent
        literals (e.g. ``c < 'm'`` where 'm' is not in the dictionary)."""
        return int(np.searchsorted(self._str_values, value, side=side))

    def decode(self, ids: np.ndarray) -> np.ndarray:
        ids = np.asarray(ids)
        if len(self.values) == 0:  # all-NULL column
            return np.full(ids.shape, None, dtype=object)
        out = self.values[np.clip(ids, 0, len(self.values) - 1)]
        return np.where(ids < 0, None, out)

    def predicate_lut(self, fn) -> np.ndarray:
        """Evaluate a host predicate over every dictionary entry -> bool LUT
        (device gathers LUT[id] to evaluate e.g. LIKE)."""
        return np.asarray([bool(fn(v)) for v in self.values], dtype=bool)


_NATIVE_ENCODE_MIN_ROWS = 4096


def _pad_flat_child(child: "Block", vcap: int) -> "Block":
    """Pad a flat child block (map keys/values) to the bucketed value
    capacity — same value-axis discipline as array blocks."""
    n = child.data.shape[0]
    if n >= vcap:
        return child
    pad = [(0, vcap - n)] + [(0, 0)] * (child.data.ndim - 1)
    return dataclasses.replace(
        child,
        data=jnp.pad(child.data, pad),
        valid=(
            None
            if child.valid is None
            else jnp.pad(child.valid, [(0, vcap - n)])
        ),
    )


def encode_strings(
    values: Sequence, force_numpy: bool = False
) -> tuple[np.ndarray, np.ndarray, Dictionary]:
    """Encode strings -> (int32 ids, valid mask, order-preserving dict).

    None values get id -1 and valid=False. Large columns route through
    the C++ host-agent codec when it is available (native/dict_codec.cpp
    — ~2x over the np.unique path, measured table in BASELINE.md);
    identical semantics either way."""
    arr = np.asarray(values, dtype=object)
    if len(arr) >= _NATIVE_ENCODE_MIN_ROWS and not force_numpy:
        from presto_tpu import native

        out = native.encode_strings_native(arr)
        if out is not None:
            ids, valid, uniq = out  # codec writes -1 for NULL rows
            return ids, valid, Dictionary(uniq)
    isnull = np.array([v is None for v in arr], dtype=bool)
    present = arr[~isnull].astype(str) if (~isnull).any() else np.array([], str)
    dictionary = Dictionary(np.unique(present))
    ids = np.full(len(arr), -1, dtype=np.int32)
    if len(present):
        ids[~isnull] = np.searchsorted(
            dictionary._str_values, present
        ).astype(np.int32)
    return ids, ~isnull, dictionary


@partial(
    jax.tree_util.register_dataclass,
    data_fields=["data", "valid", "offsets", "children"],
    meta_fields=["dtype", "dictionary"],
)
@dataclasses.dataclass
class Block:
    """One column: fixed-width device array + optional null mask.

    ``valid`` is None when the column is known null-free (the common case
    for TPC-H) — that knowledge is static, so XLA never materialises or
    computes masks for non-null columns.

    Array columns (``dtype.is_array``, reference: ArrayBlock): ``data``
    is the flat VALUES array (its own padded capacity) and ``offsets``
    is an int32 (row_capacity + 1,) array — row i's elements are
    ``data[offsets[i]:offsets[i+1]]``; ``valid`` stays per-ROW. Scalar
    columns carry offsets=None.

    Map columns (``dtype.is_map``, reference: MapBlock): ``offsets`` as
    for arrays, ``children`` = (keys Block, values Block) — two flat
    blocks sharing the offsets; ``data`` is a zero-width placeholder.
    Row columns (``dtype.is_row``, reference: RowBlock): ``children`` =
    one Block per field at ROW capacity, no offsets, placeholder data.
    ``children`` is a pytree data field (None for scalar/array blocks —
    an empty pytree, so existing block traversals see no new leaves).
    """

    data: jnp.ndarray
    valid: Optional[jnp.ndarray]  # bool, True = non-null; None = all valid
    dtype: T.DataType
    dictionary: Optional[Dictionary] = None
    offsets: Optional[jnp.ndarray] = None  # int32 (capacity+1,) arrays only
    children: Optional[tuple] = None  # map: (keys, values); row: fields

    @property
    def capacity(self) -> int:
        if self.offsets is not None:
            return self.offsets.shape[0] - 1
        return self.data.shape[0]

    @staticmethod
    def placeholder_data(cap: int) -> jnp.ndarray:
        """Zero-byte per-row stand-in for blocks whose payload lives in
        ``children`` (map/row): keeps ``data.shape[0] == capacity`` with
        no device memory."""
        return jnp.zeros((cap, 0), jnp.int8)

    @classmethod
    def from_numpy(
        cls,
        values: np.ndarray,
        dtype: T.DataType,
        valid: Optional[np.ndarray] = None,
        dictionary: Optional[Dictionary] = None,
    ) -> "Block":
        data = jnp.asarray(np.asarray(values), dtype=dtype.jnp_dtype)
        v = None if valid is None else jnp.asarray(valid, dtype=jnp.bool_)
        return cls(data=data, valid=v, dtype=dtype, dictionary=dictionary)

    @classmethod
    def from_pylist(cls, values: Sequence, dtype: T.DataType) -> "Block":
        """Build from Python values (None = NULL). Handles dictionary
        encoding for varchar, scaling for decimals, and offsets+flat
        values for arrays (elements recurse through this builder)."""
        if dtype.is_array:
            lengths = [0 if v is None else len(v) for v in values]
            offsets = np.zeros(len(values) + 1, np.int32)
            np.cumsum(lengths, out=offsets[1:])
            flat: list = []
            for v in values:
                if v is not None:
                    flat.extend(v)
            if any(x is None for x in flat):
                raise NotImplementedError(
                    "NULL array elements are not supported (documented "
                    "deviation; NULL rows are)"
                )
            child = cls.from_pylist(flat, dtype.element)
            from presto_tpu.exec.staging import bucket_capacity

            vcap = bucket_capacity(len(flat))
            if child.data.shape[0] < vcap:
                # bucket the VALUE axis (same discipline as rows):
                # exact element counts would churn XLA input shapes
                child = dataclasses.replace(
                    child,
                    data=jnp.pad(
                        child.data, [(0, vcap - child.data.shape[0])]
                    ),
                )
            isnull = np.array([v is None for v in values], bool)
            return cls(
                data=child.data,
                valid=(
                    None
                    if not isnull.any()
                    else jnp.asarray(~isnull)
                ),
                dtype=dtype,
                dictionary=child.dictionary,
                offsets=jnp.asarray(offsets),
            )
        if dtype.is_map:
            # python dicts -> offsets + flat keys/values child blocks
            if dtype.key.is_nested or dtype.value.is_nested:
                raise NotImplementedError(
                    "nested map key/value types are not supported "
                    "(one nesting level; documented deviation)"
                )
            lengths = [0 if v is None else len(v) for v in values]
            offsets = np.zeros(len(values) + 1, np.int32)
            np.cumsum(lengths, out=offsets[1:])
            flat_k: list = []
            flat_v: list = []
            for v in values:
                if v is not None:
                    for k, val in v.items():
                        flat_k.append(k)
                        flat_v.append(val)
            if any(x is None for x in flat_k):
                raise NotImplementedError("NULL map keys are invalid")
            kchild = cls.from_pylist(flat_k, dtype.key)
            vchild = cls.from_pylist(flat_v, dtype.value)
            from presto_tpu.exec.staging import bucket_capacity

            vcap = bucket_capacity(len(flat_k))
            kchild = _pad_flat_child(kchild, vcap)
            vchild = _pad_flat_child(vchild, vcap)
            isnull = np.array([v is None for v in values], bool)
            return cls(
                data=cls.placeholder_data(len(values)),
                valid=None if not isnull.any() else jnp.asarray(~isnull),
                dtype=dtype,
                offsets=jnp.asarray(offsets),
                children=(kchild, vchild),
            )
        if dtype.is_row:
            # python dicts (by field name) or sequences (positional)
            if any(t.is_nested for _, t in dtype.fields):
                raise NotImplementedError(
                    "nested row field types are not supported "
                    "(one nesting level; documented deviation)"
                )
            isnull = np.array([v is None for v in values], bool)
            children = []
            for i, (fname, ftype) in enumerate(dtype.fields):
                fv = [
                    None
                    if v is None
                    else (v[fname] if isinstance(v, dict) else v[i])
                    for v in values
                ]
                children.append(cls.from_pylist(fv, ftype))
            return cls(
                data=cls.placeholder_data(len(values)),
                valid=None if not isnull.any() else jnp.asarray(~isnull),
                dtype=dtype,
                children=tuple(children),
            )
        if dtype.is_string:
            ids, valid, dictionary = encode_strings(values)
            v = None if valid.all() else valid
            return cls.from_numpy(ids, dtype, v, dictionary)
        isnull = np.array([v is None for v in values], dtype=bool)
        if dtype.is_decimal:
            # SQL half-up rounding, exact via decimal.Decimal (float
            # multiply mis-rounds e.g. 0.005 at scale 2).
            import decimal as _dec

            q = _dec.Decimal(1).scaleb(-dtype.scale)
            # default context precision (28) is too small for long
            # decimals: quantize at int128 width
            with _dec.localcontext() as ctx:
                ctx.prec = 50
                filled = [
                    0
                    if v is None
                    else int(
                        _dec.Decimal(str(v)).quantize(
                            q, rounding=_dec.ROUND_HALF_UP
                        ).scaleb(dtype.scale)
                    )
                    for v in values
                ]
            if dtype.is_long_decimal:
                arr = T.int128_limbs(filled)  # (n, 2) limb pairs
            else:
                arr = np.asarray(filled, dtype=np.int64)
        else:
            filled = [0 if v is None else v for v in values]
            arr = np.asarray(filled).astype(dtype.np_dtype)
        v = None if not isnull.any() else ~isnull
        return cls.from_numpy(arr, dtype, v)

    def to_numpy(self, n: Optional[int] = None):
        """Materialise first n rows host-side as (values, valid) numpy pair.
        Dictionary ids and decimal scaling are NOT decoded here — see
        Page.to_pylist for full decoding."""
        data = np.asarray(self.data[:n] if n is not None else self.data)
        if self.valid is None:
            valid = np.ones(len(data), dtype=bool)
        else:
            valid = np.asarray(self.valid[:n] if n is not None else self.valid)
        return data, valid


@partial(
    jax.tree_util.register_dataclass,
    data_fields=["blocks", "num_valid", "live"],
    meta_fields=["names"],
)
@dataclasses.dataclass
class Page:
    """An ordered set of equal-capacity Blocks + live-row count.

    ``names`` is static (tuple of column names); ``blocks`` is the matching
    tuple of Blocks. Two liveness representations (SURVEY.md §7 "Design
    stance": selection is a mask/selected-indices pair):

    - **prefix form** (``live is None``): the first ``num_valid`` rows are
      live, the rest is padding. Required at program outputs, exchanges,
      and host materialization.
    - **masked form** (``live`` is a bool (capacity,) array): live rows
      are scattered in place; ``num_valid == sum(live)`` is the live
      COUNT, not a prefix length. Filters produce this form lazily — on
      TPU the nonzero+gather compaction costs far more than the masked
      reads downstream kernels do anyway, so rows stay put until an op
      genuinely needs prefix order (``compact_page``).
    """

    blocks: tuple
    num_valid: jnp.ndarray  # scalar int32: prefix length / live count
    names: tuple
    live: Optional[jnp.ndarray] = None  # bool (capacity,): masked form

    @property
    def capacity(self) -> int:
        return self.blocks[0].capacity if self.blocks else 0

    @property
    def num_columns(self) -> int:
        return len(self.blocks)

    def block(self, name: str) -> Block:
        return self.blocks[self.names.index(name)]

    def __contains__(self, name: str) -> bool:
        return name in self.names

    def row_mask(self) -> jnp.ndarray:
        """Boolean mask over capacity: True for live rows."""
        if self.live is not None:
            return self.live
        return jnp.arange(self.capacity, dtype=jnp.int32) < self.num_valid

    @property
    def is_host(self) -> bool:
        """True when block data already lives host-side as numpy (a
        materialized page) — fetches/materialization are no-ops then."""
        return bool(self.blocks) and isinstance(
            self.blocks[0].data, np.ndarray
        )

    def prefix_leaves(self, k) -> list:
        """Flat [data[:k], valid[:k]?, ...] leaf list for a batched
        device->host fetch of the first ``k`` rows — the ONE shape every
        materialization path fetches (round-trip discipline). Array
        blocks fetch offsets[:k+1] plus the FULL flat values array
        (their live extent is data-dependent; the padded fetch trades
        bytes for the round trip). A leaf that ``k`` covers is returned
        as it is: slicing a device array is Python work even when it
        cuts nothing off."""

        def head(x, n):
            return x if n >= x.shape[0] else x[:n]

        leaves = []
        for blk in self.blocks:
            if blk.dtype.is_map:
                leaves.append(head(blk.offsets, k + 1))
                for ch in blk.children:
                    leaves.append(ch.data)
                    if ch.valid is not None:
                        leaves.append(ch.valid)
            elif blk.dtype.is_row:
                for ch in blk.children:
                    leaves.append(head(ch.data, k))
                    if ch.valid is not None:
                        leaves.append(head(ch.valid, k))
            elif blk.offsets is not None:
                leaves.append(head(blk.offsets, k + 1))
                leaves.append(blk.data)
            else:
                leaves.append(head(blk.data, k))
            if blk.valid is not None:
                leaves.append(head(blk.valid, k))
        return leaves

    def with_blocks(self, names: Sequence[str], blocks: Sequence[Block]) -> "Page":
        return Page(
            blocks=tuple(blocks),
            num_valid=self.num_valid,
            names=tuple(names),
        )

    @classmethod
    def from_pydict(
        cls, data: Dict[str, Sequence], schema: Dict[str, T.DataType],
        capacity: Optional[int] = None,
    ) -> "Page":
        """Test/ingest helper: build a page from {name: python values}.

        Pads every column to ``capacity`` (default: exact length)."""
        names = tuple(schema.keys())
        n = len(next(iter(data.values()))) if data else 0
        cap = capacity if capacity is not None else max(n, 1)
        n = min(n, cap)  # truncated blocks must truncate the live count too
        blocks = []
        for name in names:
            vals = list(data[name])
            vals = vals + [None] * (cap - n) if cap > n else vals[:cap]
            b = Block.from_pylist(vals, schema[name])
            # padding validity is irrelevant (masked by num_valid) but keep
            # masks only when real nulls exist in the live region
            if b.valid is not None:
                live_valid = np.asarray(b.valid)[:n]
                if live_valid.all():
                    b = dataclasses.replace(b, valid=None)
            blocks.append(b)
        return cls(
            blocks=tuple(blocks),
            num_valid=jnp.asarray(n, dtype=jnp.int32),
            names=names,
        )

    def to_pylist(self) -> List[dict]:
        """Decode live rows to a list of {name: python value} dicts
        (dictionary ids -> strings, decimals -> Decimal-free floats kept
        exact via int/10**s, dates -> datetime.date)."""
        import datetime

        if self.live is not None:
            # masked form: select host-side (numpy boolean index is cheap
            # once the arrays are fetched; no device compaction needed)
            idx = np.nonzero(np.asarray(self.live))[0]
        else:
            idx = np.arange(int(self.num_valid))
        n = len(idx)
        out_cols = {}
        for name, blk in zip(self.names, self.blocks):
            if blk.dtype.is_map:
                off = np.asarray(blk.offsets)
                kc, vc = blk.children
                kdata = np.asarray(kc.data)
                vdata = np.asarray(vc.data)
                vvalid = (
                    None if vc.valid is None else np.asarray(vc.valid)
                )
                rvalid = (
                    np.ones(blk.capacity, bool)
                    if blk.valid is None
                    else np.asarray(blk.valid)
                )
                col = []
                for i in idx:
                    if not rvalid[i]:
                        col.append(None)
                        continue
                    d = {}
                    for j in range(int(off[i]), int(off[i + 1])):
                        k = _decode_value(
                            kdata[j], blk.dtype.key, kc.dictionary
                        )
                        v = (
                            None
                            if vvalid is not None and not vvalid[j]
                            else _decode_value(
                                vdata[j], blk.dtype.value, vc.dictionary
                            )
                        )
                        d[k] = v
                    col.append(d)
                out_cols[name] = col
                continue
            if blk.dtype.is_row:
                rvalid = (
                    np.ones(blk.capacity, bool)
                    if blk.valid is None
                    else np.asarray(blk.valid)
                )
                fdatas = []
                for (fname, ftype), ch in zip(
                    blk.dtype.fields, blk.children
                ):
                    fdatas.append(
                        (
                            fname,
                            ftype,
                            np.asarray(ch.data),
                            None
                            if ch.valid is None
                            else np.asarray(ch.valid),
                            ch.dictionary,
                        )
                    )
                col = []
                for i in idx:
                    if not rvalid[i]:
                        col.append(None)
                        continue
                    col.append(
                        {
                            fname: (
                                None
                                if fvalid is not None and not fvalid[i]
                                else _decode_value(fd[i], ftype, fdic)
                            )
                            for fname, ftype, fd, fvalid, fdic in fdatas
                        }
                    )
                out_cols[name] = col
                continue
            if blk.dtype.is_array:
                off = np.asarray(blk.offsets)
                vals = np.asarray(blk.data)
                rvalid = (
                    np.ones(blk.capacity, bool)
                    if blk.valid is None
                    else np.asarray(blk.valid)
                )
                et = blk.dtype.element
                col = []
                for i in idx:
                    if not rvalid[i]:
                        col.append(None)
                        continue
                    col.append(
                        [
                            _decode_value(v, et, blk.dictionary)
                            for v in vals[off[i]: off[i + 1]]
                        ]
                    )
                out_cols[name] = col
                continue
            data, valid = blk.to_numpy(None)
            data, valid = data[idx], valid[idx]
            col = []
            for i in range(n):
                if not valid[i]:
                    col.append(None)
                    continue
                col.append(
                    _decode_value(data[i], blk.dtype, blk.dictionary)
                )
            out_cols[name] = col
        return [
            {name: out_cols[name][i] for name in self.names} for i in range(n)
        ]

    def schema(self) -> Dict[str, T.DataType]:
        return {n: b.dtype for n, b in zip(self.names, self.blocks)}


def _decode_value(v, t: T.DataType, dictionary: Optional[Dictionary]):
    """One device value -> python value (shared by scalar columns and
    array elements)."""
    import datetime

    if t.is_string:
        return str(dictionary.values[int(v)])
    if t.is_long_decimal:
        # exact: int/10**s would lose precision past 2^53, and the
        # default context (prec 28) rounds scaleb
        import decimal as _dec

        unscaled = T.int128_value(int(v[0]), int(v[1]))
        with _dec.localcontext() as ctx:
            ctx.prec = 50
            return _dec.Decimal(unscaled).scaleb(-t.scale)
    if t.is_decimal:
        return int(v) / (10 ** t.scale)
    if t.name == "date":
        return datetime.date(1970, 1, 1) + datetime.timedelta(
            days=int(v)
        )
    if t.name == "boolean":
        return bool(v)
    if t.is_integer or t.name == "timestamp":
        return int(v)
    return float(v)


def nonzero_1d(mask: jnp.ndarray, size: int, fill_value) -> jnp.ndarray:
    """``jnp.nonzero(mask, size=size, fill_value=fill_value)[0]`` for a
    1-D mask, bit for bit, minus its tail ``(flat // 1) % len(mask)``.
    For one dimension that tail only touches slots the fill value
    overwrites anyway, but it is an int64 divide and remainder, which
    the v5e compiler expands into thousands of 32-bit ops per call and
    spends minutes of code generation on (PR 22, compiled for the
    described chip: one 2048-row fragment went from 137 s to seconds)."""
    mask = mask if mask.dtype == jnp.bool_ else (mask != 0)
    if mask.shape[0] == 0 or size == 0:
        return jnp.zeros((size,), int)
    flat = jnp.cumsum(jnp.bincount(jnp.cumsum(mask), length=size))
    return jnp.where(jnp.arange(size) >= mask.sum(), fill_value, flat)


def compact_page(page: Page, out_capacity: Optional[int] = None) -> Page:
    """Masked form -> prefix form: gather live rows to the front
    (static-shape ``jnp.nonzero``). Identity for prefix-form pages.

    This is the one place the selection-mask design pays the gather; ops
    that can consume masks never call it (SURVEY.md §7 "Design stance")."""
    if page.live is None:
        if out_capacity is not None and out_capacity != page.capacity:
            return pad_capacity(page, out_capacity)
        return page
    cap = out_capacity if out_capacity is not None else page.capacity
    sel = nonzero_1d(page.live, cap, 0)
    blocks = []
    for blk in page.blocks:
        if blk.offsets is not None:
            blocks.append(
                _gather_array_block(blk, sel, page.num_valid)
            )
            continue
        if blk.dtype.is_row:
            blocks.append(_gather_row_block(blk, sel, page.num_valid))
            continue
        blocks.append(
            dataclasses.replace(
                blk,
                data=blk.data[sel],
                valid=None if blk.valid is None else blk.valid[sel],
            )
        )
    return Page(
        blocks=tuple(blocks),
        num_valid=jnp.minimum(page.num_valid, cap).astype(jnp.int32),
        names=page.names,
    )


def compact_page_window(page: Page, window: int) -> Page:
    """Masked/prefix form -> a prefix-form page of AT MOST ``window``
    rows: the first ``window`` live rows in order, ``num_valid``
    clamped to the window.

    The micro-batch program boundary (exec/local_runner batched
    entries): ``compact_page``'s full-capacity ``nonzero`` + gather is
    the dominant cost of a selective program — ~100x an elementwise
    pass on CPU — and a batched dispatch would pay it PER LANE for
    rows the demux never reads (the demux fetches at most the
    speculative window; a lane whose true count exceeds the window
    falls out of the batch and re-runs scalar). One cumsum + a
    window-sized searchsorted/gather instead: rows beyond the live
    count hold junk (masked by num_valid), exactly like compact_page's
    fill rows. Nested blocks keep the general compaction path."""
    if page.live is None:
        return pad_capacity(page, window)
    if any(
        b.offsets is not None or b.children for b in page.blocks
    ):
        return compact_page(page, window)
    cs = jnp.cumsum(page.live.astype(jnp.int32))
    sel = jnp.searchsorted(
        cs, jnp.arange(1, window + 1, dtype=jnp.int32)
    )
    sel = jnp.minimum(sel, page.capacity - 1).astype(jnp.int32)
    blocks = [
        dataclasses.replace(
            blk,
            data=blk.data[sel],
            valid=None if blk.valid is None else blk.valid[sel],
        )
        for blk in page.blocks
    ]
    return Page(
        blocks=tuple(blocks),
        num_valid=jnp.minimum(page.num_valid, window).astype(
            jnp.int32
        ),
        names=page.names,
    )


def _gather_array_block(
    blk: Block, sel: jnp.ndarray, num_live
) -> Block:
    """Row-gather an array/map block: new offsets from the selected
    rows' lengths, values re-laid-out by the prefix-sum +
    inverse-searchsorted expansion (the engine's standard static-shape
    gather-of-segments). ``sel`` fill entries (padding rows) contribute
    length 0 via the ``num_live`` cutoff. Map blocks apply the same
    flat-axis gather to both children."""
    cap = sel.shape[0]
    off = blk.offsets
    lengths = off[1:] - off[:-1]
    sel_len = jnp.where(
        jnp.arange(cap) < num_live, lengths[sel], 0
    ).astype(jnp.int32)
    new_off = jnp.concatenate(
        [jnp.zeros((1,), jnp.int32), jnp.cumsum(sel_len).astype(jnp.int32)]
    )
    vcap = (
        blk.children[0].data.shape[0]
        if blk.dtype.is_map
        else blk.data.shape[0]
    )
    j = jnp.arange(vcap, dtype=jnp.int32)
    p = jnp.searchsorted(new_off[1:], j, side="right")
    p = jnp.minimum(p, cap - 1)
    src = off[sel[p]] + (j - new_off[p])
    src = jnp.clip(src, 0, vcap - 1)
    if blk.dtype.is_map:
        children = tuple(
            dataclasses.replace(
                ch,
                data=ch.data[src],
                valid=None if ch.valid is None else ch.valid[src],
            )
            for ch in blk.children
        )
        return dataclasses.replace(
            blk,
            data=Block.placeholder_data(cap),
            valid=None if blk.valid is None else blk.valid[sel],
            offsets=new_off,
            children=children,
        )
    return dataclasses.replace(
        blk,
        data=blk.data[src],
        valid=None if blk.valid is None else blk.valid[sel],
        offsets=new_off,
    )


def _gather_row_block(blk: Block, sel: jnp.ndarray, num_live) -> Block:
    """Row-gather a row (struct) block: children gather positionally
    with the parent. ``num_live`` zeroes the lengths of sel's fill
    entries in any offsets-bearing child (same invariant as
    _gather_array_block)."""
    children = tuple(
        _gather_row_block(ch, sel, num_live)
        if ch.dtype.is_row
        else (
            _gather_array_block(ch, sel, num_live)
            if ch.offsets is not None
            else dataclasses.replace(
                ch,
                data=ch.data[sel],
                valid=None if ch.valid is None else ch.valid[sel],
            )
        )
        for ch in blk.children
    )
    return dataclasses.replace(
        blk,
        data=Block.placeholder_data(sel.shape[0]),
        valid=None if blk.valid is None else blk.valid[sel],
        children=children,
    )


def _rebucket_row_block(blk: Block, capacity: int) -> Block:
    """Row-axis pad/slice of a row block and its children."""
    cap = blk.capacity
    if capacity == cap:
        return blk

    def fit(ch: Block) -> Block:
        if ch.dtype.is_row:
            return _rebucket_row_block(ch, capacity)
        if ch.offsets is not None:
            if capacity > cap:
                offs = jnp.pad(
                    ch.offsets, [(0, capacity - cap)], mode="edge"
                )
            else:
                offs = ch.offsets[: capacity + 1]
            return dataclasses.replace(
                ch, offsets=offs, valid=_fit_valid(ch.valid)
            )
        if capacity > cap:
            pad = [(0, capacity - cap)] + [(0, 0)] * (ch.data.ndim - 1)
            return dataclasses.replace(
                ch, data=jnp.pad(ch.data, pad), valid=_fit_valid(ch.valid)
            )
        return dataclasses.replace(
            ch, data=ch.data[:capacity], valid=_fit_valid(ch.valid)
        )

    def _fit_valid(v):
        if v is None:
            return None
        if capacity > cap:
            return jnp.pad(v, [(0, capacity - cap)])
        return v[:capacity]

    return dataclasses.replace(
        blk,
        data=Block.placeholder_data(capacity),
        valid=_fit_valid(blk.valid),
        children=tuple(fit(ch) for ch in blk.children),
    )


def pad_capacity(page: Page, capacity: int) -> Page:
    """Re-bucket a page to a new (>= live rows) capacity host-side.

    This is the fragment-boundary shape-step: selective filters hand a
    large-capacity page to a smaller compiled bucket. Runs on host between
    fragments (device->device realloc via XLA pad/slice). Prefix form
    only (masked pages go through compact_page)."""
    if page.live is not None:
        return compact_page(page, capacity)
    blocks = []
    for blk in page.blocks:
        cap = blk.capacity
        if capacity == cap:
            blocks.append(blk)
        elif blk.offsets is not None:
            # array block: re-bucket the ROW axis (offsets); the flat
            # values array keeps its own capacity. Shrink slices
            # (monotonic prefix stays valid); grow edge-pads so padding
            # rows read as empty
            if capacity > cap:
                offsets = jnp.pad(
                    blk.offsets, [(0, capacity - cap)], mode="edge"
                )
            else:
                offsets = blk.offsets[: capacity + 1]
            valid = (
                None
                if blk.valid is None
                else (
                    jnp.pad(blk.valid, [(0, capacity - cap)])
                    if capacity > cap
                    else blk.valid[:capacity]
                )
            )
            if blk.dtype.is_map:
                blk = dataclasses.replace(
                    blk, data=Block.placeholder_data(capacity)
                )
            blocks.append(
                dataclasses.replace(blk, offsets=offsets, valid=valid)
            )
        elif blk.dtype.is_row:
            blocks.append(_rebucket_row_block(blk, capacity))
        elif capacity > cap:
            # row-axis pad only (long decimals are (cap, 2) limb pairs)
            pad = [(0, capacity - cap)] + [(0, 0)] * (blk.data.ndim - 1)
            data = jnp.pad(blk.data, pad)
            valid = (
                None
                if blk.valid is None
                else jnp.pad(blk.valid, [(0, capacity - cap)])
            )
            blocks.append(dataclasses.replace(blk, data=data, valid=valid))
        else:
            data = blk.data[:capacity]
            valid = None if blk.valid is None else blk.valid[:capacity]
            blocks.append(dataclasses.replace(blk, data=data, valid=valid))
    return Page(
        blocks=tuple(blocks),
        num_valid=jnp.minimum(page.num_valid, capacity).astype(jnp.int32),
        names=page.names,
    )
