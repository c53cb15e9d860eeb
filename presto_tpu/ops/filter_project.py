"""Fused filter + project over a Page.

Reference parity: ``ScanFilterAndProjectOperator`` / ``FilterAndProject-
Operator`` driven by the bytecode-compiled ``PageProcessor`` (selected
positions + projected blocks) — SURVEY.md §2.1, §3.3.

TPU-first shape: the predicate lowers to a boolean mask. By default the
filter is LAZY — survivors stay in place and the output page carries the
selection mask (``Page.live``), because on TPU the nonzero+gather
compaction costs orders of magnitude more than the masked reads
downstream kernels (agg/join/sort/window all take ``row_mask()``) do
anyway. ``lazy=False`` forces the eager compact-to-front form for
consumers that need a dense prefix. Projections are evaluated over the
full page — XLA fuses mask, select and projection into one kernel, which
is exactly what the reference's JIT'd PageProcessor does on CPU.
"""

from __future__ import annotations

import dataclasses
from typing import List, Optional, Sequence, Tuple

import jax.numpy as jnp

from presto_tpu import types as T
from presto_tpu.expr import ColumnRef, Expr, ExprLowerer, eval_predicate
from presto_tpu.page import Block, Page, nonzero_1d


def project(
    page: Page, projections: Sequence[Tuple[str, Expr]]
) -> Page:
    """Pure projection (no selection)."""
    lowerer = ExprLowerer(page)
    names, blocks = [], []
    for name, expr in projections:
        if isinstance(expr, ColumnRef) and expr.dtype.is_nested:
            # array/map/row columns pass through whole (offsets +
            # flat/child blocks); non-identity nested expressions have
            # no lane form
            blocks.append(page.block(expr.name))
            names.append(name)
            continue
        data, valid = lowerer.eval(expr)
        data = jnp.broadcast_to(data, _col_shape(page, expr))
        if valid is not None:
            valid = jnp.broadcast_to(valid, (page.capacity,))
        blocks.append(
            Block(
                data=data,
                valid=valid,
                dtype=expr.dtype,
                dictionary=(
                    lowerer.dictionary_of(expr)
                    if expr.dtype.is_string
                    else None
                ),
            )
        )
        names.append(name)
    return Page(
        blocks=tuple(blocks),
        num_valid=page.num_valid,
        names=tuple(names),
        live=page.live,
    )


def _col_shape(page: Page, expr: Expr):
    """Column data shape: long decimals carry (capacity, 2) limb pairs."""
    if expr.dtype.is_long_decimal:
        return (page.capacity, 2)
    return (page.capacity,)


def unnest(
    page: Page,
    elements: Sequence[Expr],
    out_name: str,
    out_type,
    ordinality_name: Optional[str] = None,
) -> Page:
    """CROSS JOIN UNNEST(ARRAY[e1..ek]) — static-width row expansion
    (reference: UnnestOperator; see plan.nodes.UnnestNode).

    Every input row yields exactly k = len(elements) output rows, so the
    output capacity is a static ``capacity * k`` and the whole expansion
    is repeat/stack/reshape — no dynamic shapes for XLA. Row i expands
    to positions [i*k, (i+1)*k): parent columns repeat, the unnest
    column interleaves the k element expressions, ordinality tiles
    1..k. Liveness: an output row is live iff its parent row is
    (Presto emits NULL elements as rows; arrays here are never NULL)."""
    import numpy as np

    from presto_tpu.page import Dictionary

    from presto_tpu.expr import Literal

    k = len(elements)
    cap = page.capacity
    lowerer = ExprLowerer(page)
    datas, valids, dicts = [], [], []
    for el in elements:
        if out_type.is_string and isinstance(el, Literal):
            # bare string literal: no dictionary context exists in the
            # page, so synthesize a one-entry dictionary (or all-NULL)
            if el.value is None:
                datas.append(jnp.zeros((cap,), jnp.int32))
                valids.append(jnp.zeros((cap,), bool))
                dicts.append(None)
            else:
                datas.append(jnp.zeros((cap,), jnp.int32))
                valids.append(None)
                dicts.append(
                    Dictionary(np.asarray([el.value], dtype=object))
                )
            continue
        d, v = lowerer.eval(el)
        datas.append(
            jnp.broadcast_to(
                d, (cap, 2) if out_type.is_long_decimal else (cap,)
            )
        )
        valids.append(
            None if v is None else jnp.broadcast_to(v, (cap,))
        )
        dicts.append(
            lowerer.dictionary_of(el) if out_type.is_string else None
        )

    out_dict = None
    if out_type.is_string:
        # union the per-element dictionaries host-side (static pytree
        # metadata) and remap each element's ids through a device LUT
        values = np.unique(
            np.concatenate(
                [
                    np.asarray(d.values, dtype=object)
                    if d is not None and len(d.values)
                    else np.empty(0, dtype=object)
                    for d in dicts
                ]
            ).astype(str)
        )
        out_dict = Dictionary(values.astype(object))
        remapped = []
        for d, ids in zip(dicts, datas):
            if d is None or len(d.values) == 0:
                remapped.append(jnp.zeros((cap,), ids.dtype))
                continue
            lut = jnp.asarray(
                np.searchsorted(
                    values, np.asarray(d.values).astype(str)
                ).astype(np.int32)
            )
            remapped.append(lut[jnp.clip(ids, 0, len(d.values) - 1)])
        datas = remapped

    def expand(x):
        # axis=0: repeat ROWS (long-decimal blocks are (cap, 2) limb
        # pairs; default axis=None would flatten and interleave limbs)
        return jnp.repeat(x, k, axis=0, total_repeat_length=cap * k)

    blocks = []
    names = []
    for name, blk in zip(page.names, page.blocks):
        blocks.append(
            Block(
                data=expand(blk.data),
                valid=None if blk.valid is None else expand(blk.valid),
                dtype=blk.dtype,
                dictionary=blk.dictionary,
            )
        )
        names.append(name)
    # interleave the k element columns: stack -> (cap, k, ...) ->
    # (cap*k, ...) — trailing dims carry long-decimal limb pairs
    tail = datas[0].shape[1:]
    el_data = jnp.stack(datas, axis=1).reshape((cap * k,) + tail)
    if any(v is not None for v in valids):
        el_valid = jnp.stack(
            [
                jnp.ones((cap,), bool) if v is None else v
                for v in valids
            ],
            axis=1,
        ).reshape(cap * k)
    else:
        el_valid = None
    blocks.append(
        Block(
            data=el_data, valid=el_valid, dtype=out_type,
            dictionary=out_dict,
        )
    )
    names.append(out_name)
    if ordinality_name is not None:
        blocks.append(
            Block(
                data=jnp.tile(
                    jnp.arange(1, k + 1, dtype=jnp.int64), cap
                ),
                valid=None,
                dtype=T.BIGINT,
            )
        )
        names.append(ordinality_name)
    return Page(
        blocks=tuple(blocks),
        num_valid=(page.num_valid * k).astype(jnp.int32),
        names=tuple(names),
        live=expand(page.row_mask()),
    )


def unnest_column(
    page: Page,
    array_column: str,
    out_name: str,
    out_type,
    ordinality_name: Optional[str],
    out_capacity: int,
):
    """UNNEST of a physical array column (reference: UnnestOperator
    over ArrayBlock): per-row length expansion via the engine's
    prefix-sum + inverse-searchsorted trick, under the capacity-bucket
    protocol. Returns (page, overflow). NULL / dead rows contribute 0
    output rows (Presto: NULL arrays emit nothing)."""
    blk = page.block(array_column)
    off = blk.offsets
    lengths = (off[1:] - off[:-1]).astype(jnp.int64)
    live = page.row_mask()
    if blk.valid is not None:
        live = live & blk.valid
    m = jnp.where(live, lengths, 0)
    total = jnp.cumsum(m)
    out_count = total[-1] if page.capacity else jnp.asarray(0, jnp.int64)
    overflow = out_count > out_capacity

    j = jnp.arange(out_capacity, dtype=jnp.int64)
    p_idx = jnp.searchsorted(total, j, side="right")
    p_idx = jnp.minimum(p_idx, page.capacity - 1)
    prev = jnp.where(p_idx > 0, total[jnp.maximum(p_idx - 1, 0)], 0)
    offset = j - prev  # position within the parent row's array

    vcap = max(blk.data.shape[0], 1)
    src = jnp.clip(
        off[p_idx].astype(jnp.int64) + offset, 0, vcap - 1
    )

    blocks, names = [], []
    for name, b in zip(page.names, page.blocks):
        if b.offsets is not None or b.children is not None:
            # nested columns do not ride through the expansion (flat
            # repeats could exceed value capacity; row children would
            # need their own gather); UnnestNode.output_schema drops
            # them identically, so a post-unnest reference fails at
            # PLAN time, not here
            continue
        blocks.append(
            dataclasses.replace(
                b,
                data=b.data[p_idx],
                valid=None if b.valid is None else b.valid[p_idx],
            )
        )
        names.append(name)
    blocks.append(
        Block(
            data=blk.data[src],
            valid=None,
            dtype=out_type,
            dictionary=blk.dictionary,
        )
    )
    names.append(out_name)
    if ordinality_name is not None:
        blocks.append(
            Block(
                data=offset + 1, valid=None, dtype=T.BIGINT
            )
        )
        names.append(ordinality_name)
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


def union_all(pages: Sequence[Page]) -> Page:
    """UNION ALL: concatenate pages (reference: UnionNode). Inputs are
    schema-aligned by the planner (same names/types per position);
    liveness concatenates as masks (no compaction), capacities add.
    String columns re-encode through a trace-time union dictionary
    (per-input dictionaries are static metadata, so the remap LUTs are
    constants)."""
    import numpy as np

    from presto_tpu.page import Dictionary

    first = pages[0]
    blocks: List[Block] = []
    for ci, name in enumerate(first.names):
        blks = [p.blocks[ci] for p in pages]
        if any(
            b.offsets is not None or b.children is not None
            for b in blks
        ):
            raise NotImplementedError(
                f"nested column {name} through UNION is not supported"
            )
        dictionary = None
        if first.blocks[ci].dtype.is_string:
            dicts = [b.dictionary for b in blks]
            values = np.unique(
                np.concatenate(
                    [
                        np.asarray(d.values, object)
                        if d is not None and len(d.values)
                        else np.empty(0, object)
                        for d in dicts
                    ]
                ).astype(str)
            )
            dictionary = Dictionary(values.astype(object))
            datas = []
            for b, d in zip(blks, dicts):
                if d is None or len(d.values) == 0:
                    datas.append(jnp.zeros_like(b.data))
                    continue
                lut = jnp.asarray(
                    np.searchsorted(
                        values, np.asarray(d.values).astype(str)
                    ).astype(np.int32)
                )
                datas.append(
                    lut[jnp.clip(b.data, 0, len(d.values) - 1)]
                )
        else:
            datas = [b.data for b in blks]
        data = jnp.concatenate(datas, axis=0)
        if any(b.valid is not None for b in blks):
            valid = jnp.concatenate(
                [
                    b.valid
                    if b.valid is not None
                    else jnp.ones((b.capacity,), jnp.bool_)
                    for b in blks
                ]
            )
        else:
            valid = None
        blocks.append(
            Block(
                data=data,
                valid=valid,
                dtype=first.blocks[ci].dtype,
                dictionary=dictionary,
            )
        )
    live = jnp.concatenate([p.row_mask() for p in pages])
    num = sum(
        (p.num_valid for p in pages), jnp.asarray(0, jnp.int32)
    ).astype(jnp.int32)
    return Page(
        blocks=tuple(blocks),
        num_valid=num,
        names=first.names,
        live=live,
    )


def filter_project(
    page: Page,
    predicate: Optional[Expr],
    projections: Sequence[Tuple[str, Expr]],
    out_capacity: Optional[int] = None,
    lazy: bool = True,
) -> Page:
    """Filter by ``predicate`` (None = keep all live rows), then project.

    ``lazy=True`` (default) returns the masked form (rows in place,
    ``Page.live`` selection mask) — no gather. ``lazy=False`` compacts
    survivors to the front. Output capacity defaults to input capacity;
    pass a smaller ``out_capacity`` when the planner knows a tighter
    bound (static shape step-down without a host round-trip; implies
    eager compaction)."""
    if predicate is None:
        out = project(page, projections)
        if out_capacity is not None and out_capacity != page.capacity:
            from presto_tpu.page import compact_page

            out = compact_page(out, out_capacity)
        return out

    # eval_predicate already ANDs row_mask(), which honors Page.live
    mask = eval_predicate(predicate, page)
    count = jnp.sum(mask).astype(jnp.int32)

    if lazy and out_capacity is None:
        out = project(page, projections)
        return dataclasses.replace(out, num_valid=count, live=mask)

    cap = out_capacity if out_capacity is not None else page.capacity
    sel = nonzero_1d(mask, cap, 0)

    lowerer = ExprLowerer(page)
    names, blocks = [], []
    for name, expr in projections:
        if isinstance(expr, ColumnRef) and expr.dtype.is_nested:
            from presto_tpu.page import (
                _gather_array_block,
                _gather_row_block,
            )

            blk = page.block(expr.name)
            blocks.append(
                _gather_row_block(blk, sel, count)
                if expr.dtype.is_row
                else _gather_array_block(blk, sel, count)
            )
            names.append(name)
            continue
        data, valid = lowerer.eval(expr)
        data = jnp.broadcast_to(data, _col_shape(page, expr))[sel]
        if valid is not None:
            valid = jnp.broadcast_to(valid, (page.capacity,))[sel]
        blocks.append(
            Block(
                data=data,
                valid=valid,
                dtype=expr.dtype,
                dictionary=(
                    lowerer.dictionary_of(expr)
                    if expr.dtype.is_string
                    else None
                ),
            )
        )
        names.append(name)
    return Page(
        blocks=tuple(blocks),
        num_valid=jnp.minimum(count, cap),
        names=tuple(names),
    )
