"""In-slice exchange: Presto's shuffle fabric as ICI collectives.

Reference parity: the exchange layer — ``PartitionedOutputOperator`` /
``OutputBuffer`` on the producer side and ``ExchangeClient`` /
``ExchangeOperator`` on the consumer side, plus the exchange *types*
REPARTITION / REPLICATE / GATHER (SURVEY.md §2.1 "Exchange", §2.5,
§3.4).

TPU-first redesign (SURVEY.md §7 step 6): there is no data plane. Inside
a slice the shuffle *is* a collective inside the compiled program:

- REPARTITION  -> bucket-scatter rows by destination + ``all_to_all``
- REPLICATE    -> ``all_gather`` of the page + local compaction
- GATHER       -> the fragment boundary: stacked per-shard output is
  compacted on the consumer (see ``compact_flat``)

All shapes are static: each worker sends exactly ``bucket_cap`` rows to
every peer; per-destination counts ride along, and a count exceeding
``bucket_cap`` raises the engine-wide overflow flag (host re-runs with a
larger balance factor — the capacity-bucket protocol of SURVEY.md §7
"Hard parts: dynamic shapes/skew").

Rows are hashed with a splitmix64-style mixer over the *orderable int64*
image of each key column (nulls encoded as a distinguished value), so
equal keys — including NULL group keys — always land on the same worker.
"""

from __future__ import annotations

import dataclasses
from functools import partial
from typing import Dict, List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax import shard_map as _shard_map

from presto_tpu.ops.common import (
    float_bits_i64,
    lexsort_u32,
    orderable_i64,
)
from presto_tpu.page import Block, Dictionary, Page, nonzero_1d

_NULL_SENTINEL = 0xA5A5_A5A5_DEAD_BEEF


def _mix64(h: jnp.ndarray) -> jnp.ndarray:
    """splitmix64 finalizer (public-domain constant schedule)."""
    h = h ^ (h >> jnp.uint64(30))
    h = h * jnp.uint64(0xBF58476D1CE4E5B9)
    h = h ^ (h >> jnp.uint64(27))
    h = h * jnp.uint64(0x94D049BB133111EB)
    return h ^ (h >> jnp.uint64(31))


def partition_hash(page: Page, key_cols: Sequence[str]) -> jnp.ndarray:
    """uint64 hash per row over the key columns.

    Grouping-consistent: a function of the normalized key values only
    (NULLs normalized to a sentinel), so equal keys hash equally on every
    worker and both sides of a join.
    """
    from presto_tpu.ops.common import key_lanes

    h = jnp.full((page.capacity,), 0x9E3779B97F4A7C15, dtype=jnp.uint64)
    for c in key_cols:
        blk = page.block(c)
        # long decimals contribute both int64 limb lanes (key_lanes),
        # so equal int128 values hash equally; other types are one lane
        for lane in key_lanes(blk.data, blk.dtype):
            x = lane.astype(jnp.uint64)
            if blk.valid is not None:
                x = jnp.where(blk.valid, x, jnp.uint64(_NULL_SENTINEL))
            h = _mix64(h ^ x)
    return h


def compact_flat(
    page: Page, live: jnp.ndarray, num_valid: jnp.ndarray
) -> Page:
    """Compact rows where ``live`` to the front (static-shape nonzero)."""
    sel = nonzero_1d(live, page.capacity, 0)
    blocks = []
    for blk in page.blocks:
        blocks.append(
            dataclasses.replace(
                blk,
                data=blk.data[sel],
                valid=None if blk.valid is None else blk.valid[sel],
            )
        )
    return Page(
        blocks=tuple(blocks),
        num_valid=num_valid.astype(jnp.int32),
        names=page.names,
    )


def segmented_live_mask(counts: jnp.ndarray, seg_cap: int) -> jnp.ndarray:
    """Flat live mask over ``len(counts)`` segments of ``seg_cap`` rows:
    row j of segment i is live iff j < counts[i]."""
    n = counts.shape[0]
    j = jnp.arange(seg_cap, dtype=jnp.int32)[None, :]
    return (j < counts[:, None].astype(jnp.int32)).reshape(n * seg_cap)


def partition_exchange(
    page: Page,
    dest: jnp.ndarray,
    n: int,
    axis: str,
    bucket_cap: int,
) -> Tuple[Page, jnp.ndarray]:
    """REPARTITION: route each live row to worker ``dest[row]``.

    Returns (page', overflow): page' has capacity ``n * bucket_cap`` and
    holds every row routed *to* this worker; overflow is True when any
    outgoing bucket exceeded ``bucket_cap`` (surplus rows dropped — the
    host must re-run with a larger balance factor).
    """
    cap = page.capacity
    live = page.row_mask()
    d = jnp.where(live, dest.astype(jnp.int32), n)  # dead rows -> trash
    # rows grouped by destination (d is in [0, n])
    order = lexsort_u32([d.astype(jnp.uint32)])
    d_s = d[order]
    # offset of each sorted row within its destination's bucket
    offset = jnp.arange(cap, dtype=jnp.int32) - jnp.searchsorted(
        d_s, d_s, side="left"
    ).astype(jnp.int32)
    counts = jax.ops.segment_sum(
        jnp.ones((cap,), jnp.int32), d, num_segments=n + 1
    )[:n]
    overflow = jnp.any(counts > bucket_cap)
    slot = d_s.astype(jnp.int64) * bucket_cap + offset
    sendable = (d_s < n) & (offset < bucket_cap)
    slot = jnp.where(sendable, slot, n * bucket_cap)  # OOB -> dropped

    out_counts = jax.lax.all_to_all(
        jnp.minimum(counts, bucket_cap), axis, 0, 0
    )
    num_valid = jnp.sum(out_counts)
    live_recv = segmented_live_mask(out_counts, bucket_cap)

    blocks: List[Block] = []
    for blk in page.blocks:
        data_s = blk.data[order]
        sent = (
            jnp.zeros((n * bucket_cap,), blk.data.dtype)
            .at[slot]
            .set(data_s, mode="drop")
        )
        recv = jax.lax.all_to_all(
            sent.reshape(n, bucket_cap), axis, 0, 0
        ).reshape(n * bucket_cap)
        if blk.valid is None:
            valid = None
        else:
            v_s = blk.valid[order]
            v_sent = (
                jnp.zeros((n * bucket_cap,), jnp.bool_)
                .at[slot]
                .set(v_s, mode="drop")
            )
            valid = jax.lax.all_to_all(
                v_sent.reshape(n, bucket_cap), axis, 0, 0
            ).reshape(n * bucket_cap)
        blocks.append(dataclasses.replace(blk, data=recv, valid=valid))

    routed = Page(
        blocks=tuple(blocks),
        num_valid=num_valid.astype(jnp.int32),
        names=page.names,
    )
    # compact received segments so downstream kernels see a dense prefix
    return compact_flat(routed, live_recv, num_valid), overflow


def replicate(page: Page, n: int, axis: str) -> Page:
    """REPLICATE: all_gather every worker's live rows; each worker ends
    with the identical concatenation (capacity n * page.capacity).

    Mask-aware: a masked-form input (lazy filter upstream) gathers its
    selection mask alongside the data instead of assuming prefix order."""
    cap = page.capacity
    counts = jax.lax.all_gather(page.num_valid, axis)  # (n,)
    blocks: List[Block] = []
    for blk in page.blocks:
        data = jax.lax.all_gather(blk.data, axis).reshape(n * cap)
        valid = (
            None
            if blk.valid is None
            else jax.lax.all_gather(blk.valid, axis).reshape(n * cap)
        )
        blocks.append(dataclasses.replace(blk, data=data, valid=valid))
    gathered = Page(
        blocks=tuple(blocks),
        num_valid=jnp.sum(counts).astype(jnp.int32),
        names=page.names,
    )
    if page.live is not None:
        live = jax.lax.all_gather(page.live, axis).reshape(n * cap)
    else:
        live = segmented_live_mask(counts, cap)
    return compact_flat(gathered, live, gathered.num_valid)


def gather_stacked(
    page_flat: Page, counts: jnp.ndarray, shard_cap: int, replicated: bool
) -> Page:
    """GATHER (the fragment boundary, consumer side): turn a stacked
    fragment output — flat leaves of shape (n * shard_cap,) plus per-shard
    counts (n,) — into one dense page.

    replicated fragments contribute shard 0 only; partitioned fragments
    concatenate every shard's live prefix.
    """
    n = counts.shape[0]
    if replicated:
        blocks = [
            dataclasses.replace(
                blk,
                data=blk.data[:shard_cap],
                valid=None if blk.valid is None else blk.valid[:shard_cap],
            )
            for blk in page_flat.blocks
        ]
        return Page(
            blocks=tuple(blocks),
            num_valid=counts[0].astype(jnp.int32),
            names=page_flat.names,
        )
    live = segmented_live_mask(counts, shard_cap)
    return compact_flat(page_flat, live, jnp.sum(counts))


# --------------------------------------------------------------------
# ICI-native collective shuffle: the device-side half of the unified
# exchange SPI (server/exchange_spi.py).
#
# Co-located workers (one slice, one host process driving the device
# mesh) exchange partitioned join/agg/distinct output WITHOUT the host
# round trip: the producer computes each row's destination partition in
# a compiled program (``bucket_dest``) and hands the device-resident
# page to the in-slice exchange segment; each consumer gathers its
# partition's rows straight out of the producers' device pages with a
# compiled select-and-scatter (``ici_append``) — the all-to-all data
# movement happens device-to-device over ICI when the pages live on
# different chips, with zero serialization, zero zlib, zero HTTP.
#
# CORRECTNESS CONTRACT: ``bucket_dest`` must assign every row to the
# SAME partition as the host wire path's ``exec.streaming._bucket_of``.
# Attempts of one logical producer may run on either path (an ICI
# producer's retry can land on a cross-slice worker), and merge tasks
# for different partitions pick attempts independently — if the two
# hash functions ever disagreed, a retried stage could duplicate or
# lose rows across partitions. ``_wire_hash_image`` therefore
# replicates ``streaming._col_hash_input`` bit-for-bit (same mixer,
# same NULL/dictionary/float/limb handling); tests pin the equality.


def wire_crc_table(dictionary) -> "jnp.ndarray":
    """Per-value crc32 table of a page dictionary, as a device uint64
    array — the dictionary-id hash image of ``_col_hash_input`` (ids
    hash by VALUE, so partitioning agrees across producers whose
    dictionaries differ)."""
    import zlib

    import numpy as np

    vals = np.asarray(dictionary.values, object)
    return jnp.asarray(
        np.asarray(
            [zlib.crc32(str(v).encode()) for v in vals], np.uint64
        )
    )


def _wire_hash_image(
    blk: Block, crc_table: Optional[jnp.ndarray]
) -> jnp.ndarray:
    """uint64 per-row image of one key block, replicating
    ``exec.streaming._col_hash_input`` exactly (see contract above).

    ``crc_table`` is the ``wire_crc_table`` of the block's dictionary
    (None for non-dictionary blocks) — passed separately so jitted
    callers can strip host-side ``Dictionary`` objects from the page
    pytree (a static-aux dictionary would fork the compile cache per
    producer batch)."""
    data = blk.data
    if crc_table is not None:
        if crc_table.shape[0] == 0:  # all-NULL column: empty dictionary
            img = jnp.zeros((data.shape[0],), jnp.uint64)
        else:
            ids = jnp.clip(
                data.astype(jnp.int64), 0, crc_table.shape[0] - 1
            )
            img = crc_table[ids]
    elif data.ndim == 2 and data.shape[1] == 2:
        # long-decimal limb pairs: mix the hi limb, fold in lo
        hi = jax.lax.bitcast_convert_type(
            data[:, 0].astype(jnp.int64), jnp.uint64
        )
        lo = jax.lax.bitcast_convert_type(
            data[:, 1].astype(jnp.int64), jnp.uint64
        )
        img = _mix64(hi) ^ lo
    elif blk.dtype.name in ("double", "real"):
        # -0.0 hashes like +0.0 (one bit pattern)
        img = float_bits_i64(data).astype(jnp.uint64)
    else:
        img = jax.lax.bitcast_convert_type(
            data.astype(jnp.int64), jnp.uint64
        )
    if blk.valid is not None:
        img = jnp.where(blk.valid, img, jnp.uint64(0))
    return img


@partial(jax.jit, static_argnames=("key_cols",))
def bucket_dest(
    page: Page,
    crc_tables: Dict[str, jnp.ndarray],
    n_buckets: jnp.ndarray,
    key_cols: tuple,
) -> jnp.ndarray:
    """Per-row destination partition, == ``streaming._bucket_of`` on
    the same rows. ``page`` must be dictionary-stripped
    (``strip_dictionaries``); dictionary key columns hash through
    their entry in ``crc_tables``. Dead rows get arbitrary (masked)
    destinations."""
    h = jnp.full((page.capacity,), 0x9E3779B97F4A7C15, jnp.uint64)
    for c in key_cols:
        h = h ^ _mix64(_wire_hash_image(page.block(c), crc_tables.get(c)))
        h = _mix64(h)
    return (h % n_buckets.astype(jnp.uint64)).astype(jnp.int32)


def strip_dictionaries(page: Page) -> Page:
    """Drop host-side Dictionary objects from every block: dictionaries
    are static jit metadata, and per-batch producer dictionaries would
    fork the ICI kernels' compile cache per batch. The caller carries
    dictionaries out of band (crc tables in, union remaps in, the union
    dictionary re-attached to the merged page host-side)."""
    return dataclasses.replace(
        page,
        blocks=tuple(
            dataclasses.replace(b, dictionary=None) for b in page.blocks
        ),
    )


#: static segment count for the one-shot per-partition count kernel —
#: partition fan-outs beyond this take the HTTP wire path (the
#: scheduler's transport selection enforces it)
MAX_ICI_PARTS = 64


@jax.jit
def ici_partition_counts(page: Page, dest: jnp.ndarray) -> jnp.ndarray:
    """Live-row count per partition, shape (MAX_ICI_PARTS,) — one
    fetch sizes every consumer's merge buffer."""
    live = page.row_mask()
    d = jnp.where(live, dest, jnp.int32(-1))
    return jax.ops.segment_sum(
        jnp.ones((page.capacity,), jnp.int32),
        d + 1,
        num_segments=MAX_ICI_PARTS + 1,
    )[1:]


# --------------------------------------------------------------------
# Single-program collective stages (exchange-plane tentpole): when a
# merge stage's producers all share the mesh, the N-per-source gather
# passes above (``ici_append`` in a host loop) collapse into ONE
# compiled program whose ``jax.lax.all_to_all`` IS the exchange.
#
# The host contributes three dispatches per stage (a counts pass, the
# collective program, one take per partition) instead of
# 2 x batches x partitions; row order and zero-padding are pinned to
# the per-source path (flat batch order, stable within destination),
# so the output is bit-identical to ``device_merge`` and therefore to
# the HTTP wire path's payload concatenation.

_COLLECTIVE_AXIS = "xparts"

#: compiled collective-gather programs, keyed by (nparts, caps, column
#: signature, mesh devices) — one compile per stage *shape*, reused by
#: every merge task of the stage and by later stages of the same shape
_COLLECTIVE_PROGRAMS: Dict[tuple, object] = {}


@partial(jax.jit, static_argnames=("nparts",))
def collective_counts(pages, dests, nparts: int) -> jnp.ndarray:
    """Per-batch per-partition live-row counts, shape
    ``(len(pages), nparts)`` — ONE dispatch sizes the whole stage's
    collective buffers (vs one ``ici_partition_counts`` per batch)."""
    per = []
    for pg, dest in zip(pages, dests):
        live = pg.row_mask()
        d = jnp.where(live, dest.astype(jnp.int32), jnp.int32(-1))
        per.append(
            jax.ops.segment_sum(
                jnp.ones((pg.capacity,), jnp.int32),
                d + 1,
                num_segments=nparts + 1,
            )[1:]
        )
    return jnp.stack(per)


def _collective_signature(pages, dests, remaps) -> tuple:
    """Static shape fingerprint of a batch set: the compile-cache key
    half that the input pytrees determine. ``remaps`` is one dict per
    batch (each producer batch remaps through its OWN dictionary)."""
    sig = []
    for pg, dest, rmps in zip(pages, dests, remaps):
        cols = []
        for name, blk in zip(pg.names, pg.blocks):
            rmp = rmps.get(name)
            cols.append(
                (
                    name,
                    str(blk.data.dtype),
                    tuple(blk.data.shape[1:]),
                    blk.valid is not None,
                    None if rmp is None else int(rmp.shape[0]),
                )
            )
        sig.append((int(pg.capacity), str(dest.dtype), tuple(cols)))
    return tuple(sig)


def _concat_routed(pages, dests, remaps, dtypes, nparts, total_pad):
    """Trace-time concat of every batch's columns + destinations into
    flat ``(total_pad,)`` leaves: dead/padding rows carry the trash
    destination ``nparts``, dictionary ids pass through their union
    remap, and every column lands on its schema dtype."""
    ds = []
    for pg, dest in zip(pages, dests):
        live = pg.row_mask()
        ds.append(jnp.where(live, dest.astype(jnp.int32), jnp.int32(nparts)))
    D = jnp.concatenate(ds)
    pad = total_pad - D.shape[0]
    if pad:
        D = jnp.concatenate([D, jnp.full((pad,), nparts, jnp.int32)])

    names = pages[0].names
    any_valid = {
        name: any(pg.block(name).valid is not None for pg in pages)
        for name in names
    }
    cols, vals, vnames = [], [], []
    for name in names:
        parts = []
        vparts = []
        for pg, rmps in zip(pages, remaps):
            blk = pg.block(name)
            d = blk.data
            rmp = rmps.get(name)
            if rmp is not None:
                d = rmp[
                    jnp.clip(d.astype(jnp.int64), 0, rmp.shape[0] - 1)
                ]
            parts.append(d.astype(dtypes[name]))
            if any_valid[name]:
                vparts.append(
                    blk.valid
                    if blk.valid is not None
                    else jnp.ones((pg.capacity,), jnp.bool_)
                )
        col = jnp.concatenate(parts)
        if pad:
            col = jnp.concatenate(
                [col, jnp.zeros((pad,) + col.shape[1:], col.dtype)]
            )
        cols.append(col)
        if any_valid[name]:
            v = jnp.concatenate(vparts)
            if pad:
                v = jnp.concatenate([v, jnp.zeros((pad,), jnp.bool_)])
            vals.append(v)
            vnames.append(name)
    return cols, vals, tuple(vnames), D


def _route_flat(flat, order, slot, nslots):
    """Scatter sorted rows into their partition slots (zero slab, OOB
    dropped) — shared by the fused variant and each shard_map rank."""
    data_s = flat[order]
    return (
        jnp.zeros((nslots,) + flat.shape[1:], flat.dtype)
        .at[slot]
        .set(data_s, mode="drop")
    )


def _dest_slots(D, nparts: int, seg_cap: int):
    """Stable destination grouping: sort rows by destination, compute
    each row's offset within its destination, and the flat slot
    ``dest * seg_cap + offset`` (trash/overflow rows land OOB)."""
    n = D.shape[0]
    order = lexsort_u32([D.astype(jnp.uint32)])
    d_s = D[order]
    offset = jnp.arange(n, dtype=jnp.int32) - jnp.searchsorted(
        d_s, d_s, side="left"
    ).astype(jnp.int32)
    slot = d_s.astype(jnp.int64) * seg_cap + offset
    sendable = (d_s < nparts) & (offset < seg_cap)
    slot = jnp.where(sendable, slot, nparts * seg_cap)
    counts = jax.ops.segment_sum(
        jnp.ones((n,), jnp.int32), D, num_segments=nparts + 1
    )[:nparts]
    return order, slot, counts


def _make_collective_program(
    sig, dtype_items, nparts: int, out_cap: int, mesh
):
    """Compile the stage's single collective program.

    With a mesh (>= nparts devices): the concatenated rows shard over
    the ``xparts`` axis and each rank bucket-scatters its rows by
    destination, ``jax.lax.all_to_all`` moves every bucket to its
    owner rank, and each rank compacts what it received — the exchange
    happens in-program, device-to-device. Without a mesh the same
    routing runs as one fused argsort-scatter (still a single
    program, no collective). Both return per-column stacked
    ``(nparts, out_cap)`` slabs, partition p's rows on row p in flat
    batch order, zero-padded past the partition's count."""
    dtypes = dict(dtype_items)

    def run(pages, dests, remaps):
        if mesh is not None:
            total = sum(pg.capacity for pg in pages)
            shard_cap = -(-total // nparts)
            total_pad = nparts * shard_cap
        else:
            total_pad = sum(pg.capacity for pg in pages)
        cols, vals, vnames, D = _concat_routed(
            pages, dests, remaps, dtypes, nparts, total_pad
        )
        names = pages[0].names

        if mesh is None:
            order, slot, _ = _dest_slots(D, nparts, out_cap)
            out = {}
            for name, col in zip(names, cols):
                out[name] = _route_flat(
                    col, order, slot, nparts * out_cap
                ).reshape((nparts, out_cap) + col.shape[1:])
            for name, v in zip(vnames, vals):
                out[name + "#valid"] = _route_flat(
                    v, order, slot, nparts * out_cap
                ).reshape(nparts, out_cap)
            return out

        def rank(cols, vals, D):
            order, slot, counts = _dest_slots(D, nparts, shard_cap)
            # counts[j] rows leave this rank for rank j; after the
            # exchange, out_counts[i] rows arrived from rank i
            out_counts = jax.lax.all_to_all(
                counts, _COLLECTIVE_AXIS, 0, 0
            )
            live_recv = segmented_live_mask(out_counts, shard_cap)
            sel = nonzero_1d(live_recv, out_cap, nparts * shard_cap)

            def exchange(flat):
                sent = _route_flat(flat, order, slot, nparts * shard_cap)
                recv = jax.lax.all_to_all(
                    sent.reshape((nparts, shard_cap) + flat.shape[1:]),
                    _COLLECTIVE_AXIS,
                    0,
                    0,
                ).reshape((nparts * shard_cap,) + flat.shape[1:])
                # compact received rank-major segments to the dense
                # zero-padded prefix (OOB sel = padding -> fill 0)
                return recv.at[sel].get(mode="fill", fill_value=0)

            return (
                tuple(exchange(c) for c in cols),
                tuple(exchange(v) for v in vals),
            )

        spec = jax.sharding.PartitionSpec(_COLLECTIVE_AXIS)
        mapped = _shard_map(
            rank,
            mesh=mesh,
            in_specs=(
                tuple(spec for _ in cols),
                tuple(spec for _ in vals),
                spec,
            ),
            out_specs=(
                tuple(spec for _ in cols),
                tuple(spec for _ in vals),
            ),
        )
        ocols, ovals = mapped(tuple(cols), tuple(vals), D)
        out = {}
        for name, col in zip(names, ocols):
            out[name] = col.reshape((nparts, out_cap) + col.shape[2:])
        for name, v in zip(vnames, ovals):
            out[name + "#valid"] = v.reshape(nparts, out_cap)
        return out

    return jax.jit(run)


def collective_gather(pages, dests, remaps, dtypes, nparts: int, out_cap: int):
    """THE single-program exchange: route every batch's rows to their
    destination partitions in one compiled program.

    ``pages`` are dictionary-stripped producer pages in flat batch
    order, ``dests`` their ``bucket_dest`` vectors, ``remaps`` one
    dict per batch of column name -> union-dictionary id remap
    (absent = identity, applied in-program), ``dtypes`` column name ->
    target numpy dtype. Returns
    ``{name: (nparts, out_cap, ...), name + "#valid": ...}`` stacked
    slabs. Raises on trace/compile failure — callers fail open to the
    per-source ``ici_append`` path."""
    sig = _collective_signature(pages, dests, remaps)
    dtype_items = tuple(sorted((k, str(v)) for k, v in dtypes.items()))
    devices = jax.devices()
    use_mesh = nparts > 1 and len(devices) >= nparts
    key = (
        nparts,
        out_cap,
        sig,
        dtype_items,
        tuple(id(d) for d in devices[:nparts]) if use_mesh else None,
    )
    fn = _COLLECTIVE_PROGRAMS.get(key)
    if fn is None:
        import numpy as np

        mesh = (
            jax.sharding.Mesh(
                np.array(devices[:nparts]), (_COLLECTIVE_AXIS,)
            )
            if use_mesh
            else None
        )
        fn = _make_collective_program(
            sig, dtype_items, nparts, out_cap, mesh
        )
        _COLLECTIVE_PROGRAMS[key] = fn
    return fn(pages, dests, remaps)


@partial(jax.jit, static_argnames=("names", "pcap"))
def collective_take(out, names: tuple, part, pcap: int):
    """Slice one partition's rows out of the stacked collective output
    (static per-partition capacity ``pcap`` keeps the downstream
    fragment's capacity buckets identical to the per-source path)."""
    res = {}
    for name in names:
        v = out.get(name + "#valid")
        res[name] = {
            "data": out[name][part][:pcap],
            "valid": None if v is None else v[part][:pcap],
        }
    return res


@partial(jax.jit, donate_argnums=(0,))
def ici_append(
    out: Dict[str, dict],
    page: Page,
    dest: jnp.ndarray,
    part: jnp.ndarray,
    offset: jnp.ndarray,
    remaps: Dict[str, Optional[jnp.ndarray]],
) -> Dict[str, dict]:
    """Scatter one producer page's rows for partition ``part`` into the
    consumer's merge buffer at ``offset`` (the receive side of the
    all-to-all: rows move device-to-device here, already partitioned,
    never through the host).

    ``out`` maps column name -> {"data": array, "valid": array|None}
    (donated: updated in place buffer-wise); ``page`` is dictionary-
    stripped; ``remaps`` carries per-column id remap tables into the
    union dictionary (None = identity). Selected rows keep producer
    row order, so the merged buffer is bit-identical to the HTTP wire
    path's payload concatenation."""
    live = page.row_mask() & (dest == part)
    count = jnp.sum(live).astype(jnp.int32)
    cap = page.capacity
    sel = nonzero_1d(live, cap, 0)
    idx = jnp.arange(cap, dtype=jnp.int32)
    new_out = {}
    for name, blk in zip(page.names, page.blocks):
        slot = out[name]
        ocap = slot["data"].shape[0]
        pos = jnp.where(idx < count, offset.astype(jnp.int32) + idx, ocap)
        d = blk.data[sel]
        rmp = remaps.get(name)
        if rmp is not None:
            d = rmp[
                jnp.clip(d.astype(jnp.int64), 0, rmp.shape[0] - 1)
            ].astype(slot["data"].dtype)
        data = slot["data"].at[pos].set(
            d.astype(slot["data"].dtype), mode="drop"
        )
        valid = slot["valid"]
        if valid is not None:
            v = (
                blk.valid[sel]
                if blk.valid is not None
                else jnp.ones((cap,), jnp.bool_)
            )
            valid = valid.at[pos].set(v, mode="drop")
        new_out[name] = {"data": data, "valid": valid}
    return new_out


def union_dictionaries(pages, names):
    """Per column the dictionary every page's ids go into, the sorted
    union of theirs (merge_payloads' rule; one dictionary the pages
    share — the batches of one staged table — is its own union, so the
    1.5 M values of TPC-H Q18's ``c_name`` are never rebuilt), the
    per-page remap tables into it (None where a page's ids stay), and
    which columns have a validity mask. Returns ``(union, remaps,
    has_valid)``."""
    union, has_valid = {}, {}
    for name in names:
        dicts = {}
        for pg in pages:
            d = pg.block(name).dictionary
            if d is not None:
                dicts[id(d)] = d
        if len(dicts) == 1:
            (union[name],) = dicts.values()
        else:
            union[name] = (
                Dictionary(np.asarray(sorted(set().union(
                    *(tuple(d.values) for d in dicts.values())
                )), object))
                if dicts else None
            )
        has_valid[name] = any(pg.block(name).valid is not None for pg in pages)
    tables = {}  # (column, id of a page's dictionary) -> remap

    def remap(name, d):
        u = union[name]
        if u is None or d is None or d is u:
            return None
        if (name, id(d)) not in tables:
            tables[(name, id(d))] = jnp.asarray(np.searchsorted(
                np.asarray(u.values, object), np.asarray(d.values, object)
            ).astype(np.int64))
        return tables[(name, id(d))]

    remaps = [
        {name: remap(name, pg.block(name).dictionary) for name in names}
        for pg in pages
    ]
    return union, remaps, has_valid


def lay_end_to_end(pages, counts, schema, page_rows=None):
    """Prefix-form pages with ``counts`` live rows laid end to end on the
    device in ONE program (``agg_merge``): each page's rows up to the
    bucket of the largest count, in a buffer that holds the last write
    whole — the bucket of the total plus that bucket, plus ``page_rows``
    where that exceeds ``page_rows`` (room for ``agg_merge_chunk``'s
    slices). Dictionary ids go into the union dictionary. Returns the
    page (its row count the total) and the slots the program read."""
    from presto_tpu.exec.staging import bucket_capacity

    names = tuple(schema.keys())
    union, remaps, has_valid = union_dictionaries(pages, names)
    total = int(sum(counts))
    # a page with no live row writes nothing (the first stands in when
    # none has one: the buffer is then all padding)
    keep = [i for i, c in enumerate(counts) if c] or [0]
    widest = bucket_capacity(max(counts[i] for i in keep))
    sizes = tuple(min(widest, pages[i].capacity) for i in keep)
    cap = bucket_capacity(total + max(sizes))
    if page_rows is not None and cap > page_rows:
        cap = bucket_capacity(total + max(max(sizes), page_rows))
    offsets = np.concatenate(
        [[0], np.cumsum([counts[i] for i in keep])[:-1]]
    ).astype(np.int32)
    out = agg_merge(
        tuple(strip_dictionaries(pages[i]) for i in keep),
        jnp.asarray(offsets),
        tuple(
            {k: v for k, v in remaps[i].items() if v is not None}
            for i in keep
        ),
        dtypes=tuple(
            (n, np.dtype(pages[keep[0]].block(n).data.dtype)) for n in names
        ),
        valid=tuple(n for n in names if has_valid[n]),
        cap=cap,
        sizes=sizes,
    )
    page = Page(
        blocks=tuple(
            Block(data=out[n]["data"], valid=out[n]["valid"],
                  dtype=schema[n], dictionary=union[n])
            for n in names
        ),
        num_valid=jnp.asarray(total, jnp.int32),
        names=names,
    )
    return page, sum(sizes)


@partial(jax.jit, static_argnames=("dtypes", "valid", "cap", "sizes"))
def agg_merge(
    pages: Tuple[Page, ...],
    offsets: jnp.ndarray,
    remaps: Tuple[Dict[str, jnp.ndarray], ...],
    dtypes: Tuple[Tuple[str, object], ...],
    valid: Tuple[str, ...],
    cap: int,
    sizes: Tuple[int, ...],
) -> Dict[str, dict]:
    """One worker's partial pages laid end to end in ONE buffer of
    ``cap`` rows, for the final aggregation that merges them
    (``server.exchange_spi.device_merge``, one partition): the first
    ``sizes[i]`` rows of page ``i`` are written at ``offsets[i]``, the
    sum of the live rows of the pages before it. A page's rows past its
    live ones are overwritten by the next page or lie past the total,
    so the buffer's live prefix is the pages' live rows in order — what
    ``ici_append`` gathers row by row, with no gather and no scatter.
    The caller sizes ``cap`` to hold the last write whole (an
    out-of-range start would be clamped, not dropped). ``remaps`` maps a
    dictionary column's ids into the union dictionary, page by page;
    ``valid`` names the columns with a validity mask."""
    out = {}
    for name, dtype in dtypes:
        first = pages[0].block(name).data
        tail = first.shape[1:]
        buf = jnp.zeros((cap,) + tail, dtype)
        vbuf = jnp.zeros((cap,), jnp.bool_) if name in valid else None
        for i, (pg, size) in enumerate(zip(pages, sizes)):
            blk = pg.block(name)
            d = blk.data[:size]
            rmp = remaps[i].get(name)
            if rmp is not None and rmp.shape[0]:
                d = rmp[jnp.clip(d.astype(jnp.int64), 0, rmp.shape[0] - 1)]
            start = (offsets[i],) + (0,) * len(tail)
            buf = jax.lax.dynamic_update_slice(buf, d.astype(dtype), start)
            if vbuf is not None:
                v = (
                    blk.valid[:size]
                    if blk.valid is not None
                    else jnp.ones((size,), jnp.bool_)
                )
                vbuf = jax.lax.dynamic_update_slice(vbuf, v, (offsets[i],))
        out[name] = {"data": buf, "valid": vbuf}
    return out


@partial(jax.jit, static_argnames=("keys", "step", "window"))
def agg_merge_sort(
    cols: Dict[str, tuple],
    total: jnp.ndarray,
    keys: Tuple[str, ...],
    step: int,
    window: int,
):
    """The rows of a merged page of partial groups (``agg_merge``'s
    buffer, ``total`` live rows first) sorted by their group ``keys``
    (NULL a value of its own, padding last), and where to cut them into
    chunks of whole groups: the first row of a group at or after every
    ``step`` rows, found in the ``window`` rows from there. ``cols``
    maps a column to ``(data, valid)``. Returns the sorted columns and
    the chunks' bounds, ``total`` where nothing is left."""
    cap = next(iter(cols.values()))[0].shape[0]
    dead = jnp.arange(cap, dtype=jnp.int32) >= total
    sort_keys = [dead]
    for k in keys:
        d, v = cols[k]
        if v is not None:
            sort_keys += [~v, jnp.where(v, d, jnp.zeros_like(d))]
        else:
            sort_keys.append(d)
    # the other columns ride; a key comes back from its sorted operands
    rest = [n for n in cols if n not in keys]
    rides = [a for n in rest for a in cols[n] if a is not None]
    out = jax.lax.sort(
        tuple(sort_keys) + tuple(rides), num_keys=len(sort_keys),
        is_stable=True,
    )
    skeys, rode = list(out[: len(sort_keys)]), list(out[len(sort_keys):])
    first = jnp.zeros((cap,), jnp.bool_).at[0].set(True)
    for a in skeys:
        first = first | jnp.concatenate(
            [jnp.ones((1,), jnp.bool_), a[1:] != a[:-1]]
        )
    first = first | dead
    # bounds past the last window read padding's True: `total` at most
    padded = jnp.concatenate([first, jnp.ones((window,), jnp.bool_)])
    bounds = [jnp.int32(0)]
    for c in range(1, -(-cap // step)):
        at = jax.lax.dynamic_slice(padded, (c * step,), (window,))
        bounds.append(jnp.minimum(
            jnp.int32(c * step) + jnp.argmax(at).astype(jnp.int32), total
        ))
    bounds.append(total.astype(jnp.int32))
    sorted_cols = {}
    skeys.pop(0)
    for k in keys:
        if cols[k][1] is not None:
            nulls, d = skeys.pop(0), skeys.pop(0)
            sorted_cols[k] = (d, ~nulls)
        else:
            sorted_cols[k] = (skeys.pop(0), None)
    for n in rest:
        d, v = cols[n]
        sorted_cols[n] = (rode.pop(0), None if v is None else rode.pop(0))
    return {n: sorted_cols[n] for n in cols}, jnp.stack(bounds)


@partial(jax.jit, static_argnames=("rows",))
def agg_merge_chunk(cols: Dict[str, tuple], start: jnp.ndarray, rows: int):
    """``rows`` rows of ``agg_merge_sort``'s columns from ``start`` (its
    buffer holds ``rows`` rows past its live ones, so no start is
    clamped)."""
    return {
        n: (
            jax.lax.dynamic_slice_in_dim(d, start, rows),
            None if v is None else jax.lax.dynamic_slice_in_dim(v, start, rows),
        )
        for n, (d, v) in cols.items()
    }
