"""Unified exchange SPI: one producer/consumer surface over the three
shuffle transports.

Reference parity: the exchange layer — ``PartitionedOutputOperator`` /
``OutputBuffer`` on the producer side, ``ExchangeClient`` on the
consumer side (SURVEY.md §2.5). The reference has exactly one data
plane (serialized pages over HTTP); this engine has three, unified
here behind one emit/fetch surface:

- **ICI** (in-slice): co-located workers — one slice, one host process
  driving the device mesh — exchange partitioned output as
  device-resident pages through the :class:`IciSegment`. The producer
  computes per-row destinations in a compiled program
  (``parallel.exchange.bucket_dest``) and the consumer gathers its
  partition straight out of the producers' device pages
  (``parallel.exchange.ici_append``): no host copy, no serialization,
  no zlib, no HTTP — the bytes that would have crossed the wire are
  counted in ``exchange.ici_bytes_elided`` instead.
- **HTTP** (cross-slice / cross-host): the classic serialized page
  wire (``pages_wire`` + token-acked pulls), byte-counted in
  ``exchange.http_shuffle_bytes``.
- **Spool** (recovery): the durable ``ExchangeSpool`` tee under
  ``retry_policy=TASK`` — ICI producers still tee serialized frames so
  a dead in-slice peer's partitions recover exactly like HTTP ones.

Transport *selection* is NOT made here: the scheduler
(``server/scheduler.py``) owns it per stage, and the chosen slice
rides ``FragmentSpec.ici_slice`` (empty = HTTP, the bit-exact legacy
path). This module enforces the contract mechanically: a worker whose
own slice does not match the spec's, a partition fan-out beyond the
kernel bound, or an ineligible page shape falls back to the HTTP lane
and counts ``exchange.ici_fallbacks`` — ICI is an optimization, never
a correctness dependency (a consumer that finds no sealed segment
entry falls back to HTTP, then to the spool, exactly like a dead HTTP
peer today).
"""

from __future__ import annotations

import logging
import threading
import time
from typing import Dict, List, Optional

import numpy as np

from presto_tpu.utils import tracing
from presto_tpu.utils.metrics import REGISTRY
from presto_tpu.utils.telemetry import DEVICE

log = logging.getLogger("presto_tpu.exchange")


def _fetch_dest(dest, nr: int):
    """The ONE destination-vector fetch of the ICI lane (a small
    device->host control transfer per batch), accounted on the
    device-plane telemetry counters. ``None`` (one partition) sends
    every row to partition 0 and reads nothing."""
    import jax

    if dest is None:
        return np.zeros(nr, np.int64)
    arr = np.asarray(jax.device_get(dest))
    DEVICE.count_d2h(int(arr.nbytes))
    return arr[:nr].astype(np.int64)


def _count_dispatch(n: int = 1, fold=None) -> None:
    """Device-program launch accounting for the exchange lane's
    compiled kernels, on the same choke-point counters the fragment
    runner feeds; ``fold`` is the runner's per-query stat folder
    (``_fold_device_stat``) when the caller has one — EXPLAIN ANALYZE's
    per-query ``device.dispatches`` is the proof the single-program
    path dispatches less."""
    DEVICE.count_dispatch(n)
    if fold is not None:
        fold(device_dispatches=n)


def default_slice_id() -> str:
    """Slice identity announced on discovery: co-location means ONE
    host process driving one device mesh (the in-slice segment is
    process-local), so the default identity is platform + pid.
    ``exchange.slice-id`` overrides it for topologies that need an
    explicit name; a wrong override is safe — a cross-process fetch
    misses the segment and falls back to HTTP."""
    import os

    import jax

    try:
        platform = jax.devices()[0].platform
    except Exception:  # backend not initialized: HTTP-only worker
        return ""
    return f"{platform}-{os.getpid()}"


def device_coords() -> list:
    """Device coordinates announced beside the slice id (topology
    observability; the scheduler groups by slice id alone)."""
    import jax

    try:
        return [int(d.id) for d in jax.devices()]
    except Exception:
        return []


# ------------------------------------------------------------ segment


class IciSegment:
    """Process-global registry of device-resident partitioned output.

    One entry per producer task attempt: the raw output pages plus
    their per-row destination arrays, sealed when the task FINISHES
    (mirroring the spool's commit-before-terminal-state ordering, so a
    consumer that observes FINISHED can trust sealed-or-never).
    Entries die with the task: DELETE/abort discards them, drain
    materializes unconsumed partitions to the HTTP buffers first.
    """

    def __init__(self):
        self._cond = threading.Condition()
        self._entries: Dict[str, dict] = {}

    def publish(
        self,
        slice_id: str,
        task_id: str,
        nparts: int,
        page,
        dest,
        nbytes: int,
        on_consumed=None,
    ) -> None:
        with self._cond:
            e = self._entries.get(task_id)
            if e is None:
                e = {
                    "slice": slice_id,
                    "nparts": nparts,
                    "batches": [],
                    "bytes": 0,
                    "sealed": False,
                    "consumed": set(),
                    "on_consumed": on_consumed,
                }
                self._entries[task_id] = e
            e["batches"].append((page, dest))
            e["bytes"] += nbytes
            if on_consumed is not None:
                e["on_consumed"] = on_consumed
            self._cond.notify_all()

    def seal(self, slice_id: str, task_id: str, nparts: int) -> None:
        """Producer finished cleanly: the entry may serve consumers.
        A zero-output producer (empty range, fully-filtered batch)
        never published — sealing creates an empty sealed entry so its
        consumers learn 'complete, zero rows' in-slice instead of
        paying an HTTP round trip to an empty buffer."""
        with self._cond:
            e = self._entries.get(task_id)
            if e is None:
                e = {
                    "slice": slice_id,
                    "nparts": nparts,
                    "batches": [],
                    "bytes": 0,
                    "sealed": True,
                    "consumed": set(),
                    "on_consumed": None,
                }
                self._entries[task_id] = e
            e["sealed"] = True
            self._cond.notify_all()

    def discard(self, task_id: str) -> int:
        """Drop an entry (task failed/aborted/DELETEd or drain
        materialized it); returns the accounted bytes freed so the
        caller can release its pool reservation."""
        with self._cond:
            e = self._entries.pop(task_id, None)
            self._cond.notify_all()
            return e["bytes"] if e is not None else 0

    def peek(self, slice_id: str, task_id: str) -> str:
        """'sealed' | 'open' | 'absent' | 'foreign' (present but
        published under a different slice — a misconfigured override,
        never served)."""
        with self._cond:
            e = self._entries.get(task_id)
            if e is None:
                return "absent"
            if e["slice"] != slice_id:
                return "foreign"
            return "sealed" if e["sealed"] else "open"

    def take(self, slice_id: str, task_id: str, part: int):
        """Consume one partition of a sealed entry: returns the
        ``[(page, dest), ...]`` batch list (device arrays, shared
        immutable) or None. Marks the partition consumed — a draining
        producer knows an ICI consumer already has these rows."""
        with self._cond:
            e = self._entries.get(task_id)
            if e is None or not e["sealed"] or e["slice"] != slice_id:
                return None
            e["consumed"].add(int(part))
            cb = e["on_consumed"]
            batches = list(e["batches"])
        if cb is not None:
            try:
                cb(int(part))
            except Exception:  # consumed-tracking must never fail a read
                pass
        return batches

    def snapshot(self, task_id: str) -> Optional[dict]:
        """Entry view for the drain-materialize path."""
        with self._cond:
            e = self._entries.get(task_id)
            if e is None:
                return None
            return {
                "batches": list(e["batches"]),
                "nparts": e["nparts"],
                "consumed": set(e["consumed"]),
                "bytes": e["bytes"],
            }

    def task_ids(self) -> List[str]:
        with self._cond:
            return list(self._entries)

    def wait(self, timeout: float) -> None:
        with self._cond:
            self._cond.wait(timeout)

    def stats(self) -> dict:
        with self._cond:
            return {
                "entries": len(self._entries),
                "bytes": sum(e["bytes"] for e in self._entries.values()),
                "hits": int(REGISTRY.counter("exchange.ici_edges").total),
                "misses": int(
                    REGISTRY.counter("exchange.ici_fallbacks").total
                ),
                "bytes_elided": int(
                    REGISTRY.counter("exchange.ici_bytes_elided").total
                ),
            }


#: the ONE in-slice exchange segment of this process (= this slice)
SEGMENT = IciSegment()


# ----------------------------------------------------- producer side


def _wire_row_bytes(page) -> int:
    """Approximate serialized bytes per row (raw typed buffers +
    packed validity) — what the HTTP wire would have moved; feeds
    ``exchange.ici_bytes_elided``."""
    total = 0
    for blk in page.blocks:
        width = blk.data.dtype.itemsize
        if blk.data.ndim == 2:
            width *= blk.data.shape[1]
        total += width
        if blk.valid is not None:
            total += 1
    return total


def _page_eligible(page) -> bool:
    """ICI-transportable page shape: fixed-width scalar blocks only
    (array/map/row blocks keep the serialized wire, which already
    handles offsets rebase and child blocks)."""
    for blk in page.blocks:
        if blk.offsets is not None or blk.children:
            return False
    return True


def _serialize_partition_slices(payload, schema, nrows, buckets):
    """Host-side partition slicing + serialization shared by the HTTP
    emit lane, the ICI spool tee, and drain materialization: yields
    ``(partition, frame, n)`` per non-empty partition, in partition
    order (np.unique), rows in producer order — the wire contract both
    transports and the spool agree on."""
    from presto_tpu.exec import streaming as S
    from presto_tpu.server import pages_wire

    for b in np.unique(buckets):
        mask = buckets == b
        sliced = S._slice_payload(payload, schema, mask)
        n = int(mask.sum())
        cols = pages_wire.payload_to_wire_columns(sliced, schema, n)
        yield int(b), pages_wire.serialize_page(cols, n), n


def keeps_on_device(spec, slice_id: str) -> bool:
    """Whether a producer's partitioned output stays on this worker's
    device whole, as it leaves the batch's program: one partition
    (``scheduler.device_merge_slice``: one worker merges its own
    pages) of fixed-width columns, on the lane the scheduler chose."""
    return (
        slice_id != ""
        and spec.ici_slice == slice_id
        and spec.n_partitions == 1
        and all(
            not (t.is_array or t.is_map or t.is_row)
            for t in spec.fragment.output_schema().values()
        )
    )


def emit_partitioned(
    task, out, *, slice_id: str, pool, fold=None, n=None
) -> None:
    """The ONE partitioned-output emit (reference:
    PartitionedOutputOperator): routes this batch onto the transport
    the scheduler chose for the stage.

    ICI lane (``spec.ici_slice`` == this worker's slice): the output
    page stays device-resident — a compiled program assigns per-row
    destinations and the (page, dest) pair enters the in-slice
    segment; consumers gather their rows device-to-device. The spool
    tee still serializes under ``retry_policy=TASK`` (durability needs
    bytes on disk; the data plane between live peers stays on device).

    One partition (one worker merging its own pages) routes nothing:
    the page enters the segment with no destination vector (``None``),
    and ``n``, its live rows as the batch's read brought them, spares
    a read of the device.

    HTTP lane (everything else): serialize, slice per partition, offer
    to the per-partition output buffers — bit-exact legacy behavior.
    """
    import jax

    from presto_tpu.exec import streaming as S
    from presto_tpu.exec.staging import page_nbytes

    spec = task.spec
    ici_wanted = bool(spec.ici_slice)
    if ici_wanted and _ici_emit_ok(spec, out, slice_id):
        from presto_tpu.parallel import exchange as X

        import jax.numpy as jnp

        n = int(out.num_valid) if n is None else int(n)
        if n == 0:
            return
        dest = None
        if spec.n_partitions > 1:
            keys = tuple(spec.partition_keys)
            crc = {
                c: X.wire_crc_table(out.block(c).dictionary)
                for c in keys
                if out.block(c).dictionary is not None
            }
            stripped = X.strip_dictionaries(out)
            dest = X.bucket_dest(
                stripped, crc, jnp.asarray(spec.n_partitions), keys
            )
            _count_dispatch(1, fold)
        nbytes = page_nbytes(out) + (0 if dest is None else int(dest.nbytes))
        if pool is not None:
            # same accounting as HTTP shuffle buffers: the pages are
            # stage-lifetime, reserved under the task's buffer key and
            # freed at DELETE (or at drain materialization)
            pool.reserve(task.buf_key, nbytes)

        def consumed(part: int) -> None:
            with task.cond:
                if part < len(task.complete_served):
                    task.complete_served[part] = True

        SEGMENT.publish(
            slice_id,
            spec.task_id,
            spec.n_partitions,
            out,
            dest,
            nbytes,
            on_consumed=consumed,
        )
        with task.cond:
            aborted = task.state == "ABORTED"
        if aborted:
            # a DELETE raced this batch (offer_page's abort
            # discipline): its discard ran before our publish, so the
            # re-published entry and its reservation would outlive the
            # task — undo both; any DELETE after this check discards
            # the entry itself
            freed = SEGMENT.discard(spec.task_id)
            if pool is not None and freed:
                pool.release(task.buf_key, freed)
            raise RuntimeError("task aborted")
        wire_bytes = n * _wire_row_bytes(out)
        REGISTRY.counter("exchange.ici_bytes_elided").update(
            wire_bytes
        )
        with task.cond:
            task.stats.output_rows += n
            # wire-equivalent bytes, comparable to the HTTP lane's
            # serialized counting (the device-capacity bytes are pool
            # accounting, not output volume)
            task.stats.output_bytes += wire_bytes
        if task._spool is not None:
            # durable tee: serialized frames on the shared spool dir,
            # sliced by the SAME device-computed destinations (the
            # device and host hashes are pinned equal, but recovery
            # must match what live consumers gathered, not re-derive).
            # With a drain attached the SPL1 serialization runs on its
            # background thread — durability stops charging the device
            # loop; the pre-commit flush keeps commit-marker-last.
            spool = task._spool
            tid = spec.task_id

            def tee(page=out, dvec=dest):
                payload, schema, nr = S._page_to_payload(page)
                bk = _fetch_dest(dvec, nr)
                for part, frame, _ in _serialize_partition_slices(
                    payload, schema, nr, bk
                ):
                    spool.append(tid, part, frame)

            drain = getattr(task, "_spool_drain", None)
            if drain is not None:
                drain.submit(tid, tee)
            else:
                tee()
        return

    if ici_wanted:
        # scheduler planned ICI but this attempt cannot honor it (a
        # retry landed cross-slice, or the shape is ineligible): the
        # HTTP lane is always correct
        REGISTRY.counter("exchange.ici_fallbacks").update()

    payload, schema, nrows = S._page_to_payload(out)
    if nrows == 0:
        return
    buckets = S._bucket_of(
        payload, list(spec.partition_keys), nrows, spec.n_partitions
    )
    for part, frame, n in _serialize_partition_slices(
        payload, schema, nrows, buckets
    ):
        task.offer_page(frame, part=part)
        REGISTRY.counter("exchange.http_shuffle_bytes").update(
            len(frame)
        )
        with task.cond:
            task.stats.output_rows += n


def _ici_emit_ok(spec, out, slice_id: str) -> bool:
    from presto_tpu.parallel import exchange as X

    return (
        slice_id != ""
        and spec.ici_slice == slice_id
        and 1 <= spec.n_partitions <= X.MAX_ICI_PARTS
        and _page_eligible(out)
        and all(k in out.names for k in spec.partition_keys)
    )


def emit_gather(task, out, *, slice_id: str, pool, fold=None) -> bool:
    """Single-partition (gather) output onto the ICI lane: when the
    root stage is co-located with the coordinator, its final gather is
    one more ICI edge — the output page stays device-resident under an
    all-zero destination vector and the coordinator takes partition 0
    straight from the segment, no serialization and no HTTP.

    Returns True when the batch entered the segment (or was empty —
    the seal carries 'complete, zero rows'), False when the ICI lane
    cannot carry this page: the caller keeps the serialized buffer
    path, which is always correct.
    """
    import jax.numpy as jnp

    from presto_tpu.exec.staging import page_nbytes

    spec = task.spec
    if (
        slice_id == ""
        or spec.ici_slice != slice_id
        or spec.n_partitions != 1
        or not _page_eligible(out)
    ):
        if spec.ici_slice and spec.n_partitions == 1:
            REGISTRY.counter("exchange.ici_fallbacks").update()
        return False
    n = int(out.num_valid)
    if n == 0:
        return True
    dest = jnp.zeros((out.capacity,), jnp.int32)
    nbytes = page_nbytes(out) + int(dest.nbytes)
    if pool is not None:
        pool.reserve(task.buf_key, nbytes)

    def consumed(part: int) -> None:
        with task.cond:
            if part < len(task.complete_served):
                task.complete_served[part] = True

    SEGMENT.publish(
        slice_id,
        spec.task_id,
        1,
        out,
        dest,
        nbytes,
        on_consumed=consumed,
    )
    with task.cond:
        aborted = task.state == "ABORTED"
    if aborted:
        # same DELETE race discipline as emit_partitioned
        freed = SEGMENT.discard(spec.task_id)
        if pool is not None and freed:
            pool.release(task.buf_key, freed)
        raise RuntimeError("task aborted")
    wire_bytes = n * _wire_row_bytes(out)
    REGISTRY.counter("exchange.ici_bytes_elided").update(wire_bytes)
    with task.cond:
        task.stats.output_rows += n
        task.stats.output_bytes += wire_bytes
    return True


def seal_task(slice_id: str, task_id: str, nparts: int) -> None:
    """Producer FINISHED cleanly: seal before the terminal state is
    visible (same ordering as the spool commit — FINISHED must imply
    the in-slice copy is complete)."""
    SEGMENT.seal(slice_id, task_id, nparts)


def discard_task(task_id: str) -> int:
    """Task failed/aborted/DELETEd: drop its segment entry (and any
    collective-stage slabs built over it — a retried producer's new
    attempt republishes different batches); returns bytes to release
    from the task's pool reservation."""
    COLLECTIVE.discard_task(task_id)
    return SEGMENT.discard(task_id)


# Degrading a task's ICI edges to the HTTP wire happens in two
# halves so the commit is atomic: ``serialize_ici_frames`` is a pure
# read (no buffer side effects — an exception leaves nothing torn and
# the degrade can simply retry), ``buffer_frames`` reserves once and
# appends everything under ONE lock hold (pullers observe the buffers
# either empty or complete, never a torn prefix that could flip
# X-Complete early). Two callers drive the pair through
# ``WorkerServer._materialize_ici``: a DRAINING producer (its ICI
# edges must fall back so the zero-failure-drain contract holds) and
# the results handler's lazy path — an HTTP pull of a FINISHED ICI
# task (a merge retry that landed cross-slice) must see the real
# pages, never an empty-but-complete buffer. EVERY partition
# materializes, including ones an ICI consumer already took:
# partitioned buffers serve retried merge attempts from token 0 by
# contract, exactly like the HTTP lane's DELETE-lifetime buffers.


def serialize_ici_frames(task):
    """First half: the task's in-segment batches as
    ``[(partition, frame), ...]`` serialized wire frames, or None when
    no segment entry exists. Pure read — no buffers touched, no
    reservations made."""
    import jax

    from presto_tpu.exec import streaming as S

    snap = SEGMENT.snapshot(task.spec.task_id)
    if snap is None:
        return None
    frames = []
    for page, dest in snap["batches"]:
        payload, schema, nr = S._page_to_payload(page)
        bk = _fetch_dest(dest, nr)
        for part, frame, _ in _serialize_partition_slices(
            payload, schema, nr, bk
        ):
            frames.append((part, frame))
    return frames


def buffer_frames(task, frames, pool) -> int:
    """Second half: commit serialized frames to the task's
    per-partition HTTP buffers — one reservation for the whole set
    (direct appends, NOT offer_page: the spool tee already ran at
    produce time; teeing again would double-serve recovery), one
    locked append of everything, then the segment entry drops and its
    device-byte reservation releases."""
    total = sum(len(f) for _, f in frames)
    if pool is not None and total:
        pool.reserve(task.buf_key, total)
    with task.cond:
        for part, frame in frames:
            task.parts[part].append(frame)
    for _, frame in frames:
        REGISTRY.counter("exchange.http_shuffle_bytes").update(
            len(frame)
        )
    freed = SEGMENT.discard(task.spec.task_id)
    if freed and pool is not None:
        pool.release(task.buf_key, freed)
    if frames:
        REGISTRY.counter("exchange.ici_materialized").update()
    return len(frames)


# ----------------------------------------------------- consumer side


def ici_fetch(
    slice_id: str,
    spec,
    src_task: str,
    deadline: float,
    probe,
):
    """Consumer half of the ICI transport: wait for the producer's
    segment entry to seal, then take this merge task's partition.

    Returns the ``[(page, dest), ...]`` batch list, or None — the
    caller falls back to the HTTP pull (then the spool), exactly the
    recovery ladder a dead HTTP peer takes today. ``probe()`` answers
    whether the producer attempt is still alive (True = keep waiting,
    False = terminal/unreachable); it is only consulted between waits,
    so the control-plane HTTP stays off the hot path."""
    if not spec.ici_slice or spec.ici_slice != slice_id:
        return None
    last_probe = 0.0
    while True:
        st = SEGMENT.peek(slice_id, src_task)
        if st == "sealed":
            got = SEGMENT.take(slice_id, src_task, spec.partition)
            if got is not None:
                REGISTRY.counter("exchange.ici_edges").update()
                return got
            break
        if st == "foreign":
            break
        now = time.monotonic()
        if now > deadline:
            break
        if now - last_probe > 0.5:
            last_probe = now
            alive = probe()
            if alive is False:
                # terminal: the producer seals BEFORE publishing
                # FINISHED, so sealed-or-never is decidable now
                if SEGMENT.peek(slice_id, src_task) == "sealed":
                    continue
                break
        with tracing.wait("exchange_spi.ici_fetch"):
            SEGMENT.wait(0.05)
    REGISTRY.counter("exchange.ici_fallbacks").update()
    return None


def ici_batches_to_payloads(batches, part: int, schema):
    """Degrade an ICI batch list to host wire payloads
    ``[(payload, schema, nrows), ...]`` — the shape
    ``pages_wire.merge_payloads`` consumes. Used when a merge group
    mixes transports (some sources fell back to HTTP) or exceeds the
    device budget (the grouped host merge takes over): still zero
    serialization and zero HTTP, one device->host fetch."""
    import jax

    from presto_tpu.exec import streaming as S

    out = []
    for page, dest in batches:
        payload, pschema, nr = S._page_to_payload(page)
        bk = _fetch_dest(dest, nr)
        mask = bk == part
        n = int(mask.sum())
        if n == 0:
            continue
        out.append((S._slice_payload(payload, pschema, mask), pschema, n))
    return out


def device_merge(
    batches_by_source, part: int, schema, max_rows=None, fold=None,
    page_rows=None,
):
    """Build the merge task's input page ON DEVICE from ICI batches:
    per-source partition rows gather-scattered into one zero-padded
    buffer (``parallel.exchange.ici_append``), dictionary ids remapped
    into the sorted union dictionary — the same union, row order, and
    capacity bucket the HTTP path's ``merge_payloads`` + ``stage_page``
    produce, so the downstream fragment compiles and computes
    identically. Batches with no destination vector (one partition: a
    worker merging its own pages, ``scheduler.device_merge_slice``) are
    laid end to end by one program (``parallel.exchange.
    lay_end_to_end``); where that buffer would hold more than
    ``page_rows`` rows it is made ``page_rows`` larger still, room for
    the page-sized slices ``merge_chunks`` cuts it into.

    Returns ``(page, total_rows)``, or None when the partition exceeds
    ``max_rows`` — the caller degrades to the grouped host merge
    (``ici_batches_to_payloads`` + ``grouped_final_merge``), the same
    memory-funnel discipline the HTTP gather applies.
    """
    import jax
    import jax.numpy as jnp

    from presto_tpu.exec.staging import bucket_capacity
    from presto_tpu.page import Block, Page
    from presto_tpu.parallel import exchange as X

    flat: List[tuple] = [
        b for src in batches_by_source for b in src
    ]
    names = tuple(schema.keys())
    # one partition (every destination None: a worker merging its own
    # pages) routes nothing: the live counts are the pages' own
    whole = all(d is None for _, d in flat)
    # one small device->host fetch sizes the buffer (counts only —
    # the data plane stays on device)
    if whole:
        count_vecs = jax.device_get([pg.num_valid for pg, _ in flat])
    else:
        count_vecs = jax.device_get(
            [X.ici_partition_counts(pg, d) for pg, d in flat]
        )
        _count_dispatch(len(flat), fold)
    d2h = sum(int(np.asarray(c).nbytes) for c in count_vecs)
    DEVICE.count_d2h(d2h)
    if fold is not None:
        fold(device_d2h_bytes=d2h)
    counts = [
        int(np.asarray(c) if whole else np.asarray(c)[part])
        for c in count_vecs
    ]
    total = int(sum(counts))
    if max_rows is not None and total > max_rows:
        return None
    if whole:
        with tracing.phase("dispatch"):
            page, slots = X.lay_end_to_end(
                [pg for pg, _ in flat], counts, schema, page_rows
            )
        _count_dispatch(1, fold)
        DEVICE.count_agg_merge(slots)
        return page, total
    cap = bucket_capacity(total)
    # per-column union dictionary + per-source remap tables, exactly
    # merge_payloads' sorted-union searchsorted
    union, all_remaps, has_valid = X.union_dictionaries(
        [pg for pg, _ in flat], names
    )
    out = {}
    for name in names:
        t = schema[name]
        tail = (2,) if getattr(t, "is_long_decimal", False) else ()
        for pg, _ in flat:
            d = pg.block(name).data
            tail = (d.shape[1],) if d.ndim == 2 else ()
            break
        out[name] = {
            "data": jnp.zeros((cap,) + tail, t.np_dtype),
            "valid": (
                jnp.zeros((cap,), jnp.bool_)
                if has_valid[name]
                else None
            ),
        }
    offset = 0
    for (pg, dest), cnt, remaps in zip(flat, counts, all_remaps):
        out = X.ici_append(
            out,
            X.strip_dictionaries(pg),
            dest,
            jnp.asarray(part, jnp.int32),
            jnp.asarray(offset, jnp.int32),
            remaps,
        )
        _count_dispatch(1, fold)
        offset += cnt
    DEVICE.count_agg_merge(sum(pg.capacity for pg, _ in flat))
    blocks = []
    for name in names:
        blocks.append(
            Block(
                data=out[name]["data"],
                valid=out[name]["valid"],
                dtype=schema[name],
                dictionary=union[name],
            )
        )
    page = Page(
        blocks=tuple(blocks),
        num_valid=jnp.asarray(total, jnp.int32),
        names=names,
    )
    return page, total


def merge_chunks(page, keys, total: int, page_rows: int, batches: int,
                 fold=None):
    """A merged page of partial groups larger than a batch's page, as
    page-sized pages for the final aggregation, each holding whole
    groups: its rows sorted by the group ``keys`` in one program, then
    cut where a group starts near every ``page_rows - batches - 1``
    rows (a group has at most one row a merged batch, so one starts in
    the next ``batches + 1`` rows). A final aggregation over more rows
    than a page compiles for many minutes (its scans), a sort does not.
    None where a key cannot be sorted as it is stored (a float's NaN
    and -0.0, a long decimal's two limbs) or the pages were too many:
    the caller runs the final aggregation over the whole page."""
    import jax
    import jax.numpy as jnp

    from presto_tpu.page import Block, Page
    from presto_tpu.parallel import exchange as X

    window = batches + 1
    step = page_rows - window
    names = tuple(page.names)
    blocks = {n: page.block(n) for n in names}
    if step < page_rows // 2 or any(
        blocks[k].data.ndim != 1
        or not (
            jnp.issubdtype(blocks[k].data.dtype, jnp.integer)
            or blocks[k].data.dtype == jnp.bool_
        )
        for k in keys
    ):
        return None
    with tracing.phase("dispatch"):
        cols, starts = X.agg_merge_sort(
            {n: (b.data, b.valid) for n, b in blocks.items()},
            jnp.asarray(total, jnp.int32),
            keys=tuple(keys), step=step, window=window,
        )
    _count_dispatch(1, fold)
    with tracing.phase("fetch", site="merge_chunks"):
        starts = np.asarray(jax.device_get(starts))
    DEVICE.count_d2h(int(starts.nbytes))
    out = []
    for lo, hi in zip(starts[:-1], starts[1:]):
        if hi <= lo:
            continue
        with tracing.phase("dispatch"):
            part = X.agg_merge_chunk(cols, jnp.asarray(lo, jnp.int32),
                                     rows=page_rows)
        _count_dispatch(1, fold)
        out.append(Page(
            blocks=tuple(
                Block(data=part[n][0], valid=part[n][1],
                      dtype=blocks[n].dtype, dictionary=blocks[n].dictionary)
                for n in names
            ),
            num_valid=jnp.asarray(hi - lo, jnp.int32),
            names=names,
        ))
    return out


# ------------------------------------------------- collective stages


class _CollectiveCache:
    """One single-program exchange per (slice, producer set): the
    first merge task of a stage builds the collective program's output
    slabs (ONE ``shard_map``/``all_to_all`` dispatch for every batch of
    every producer — ``parallel.exchange.collective_gather``); sibling
    merge tasks take their partitions from the same slabs instead of
    re-gathering per source. Entries wrap device arrays and die when
    every partition is served or when any producer task is discarded
    (a retried attempt republishes different batches). Build failures
    and size refusals are cached too, so siblings fail open to the
    per-source path without re-tracing."""

    def __init__(self):
        self._cond = threading.Condition()
        self._entries: Dict[tuple, dict] = {}

    def lookup(self, key, builder):
        """The built stage entry for ``key`` (or None when the build
        failed/refused). The first caller builds OUTSIDE the lock;
        concurrent siblings wait on the condition instead of building
        twice."""
        with self._cond:
            while True:
                e = self._entries.get(key)
                if e is None:
                    e = {
                        "state": "building",
                        "entry": None,
                        "served": set(),
                    }
                    self._entries[key] = e
                    break
                if e["state"] == "building":
                    with tracing.wait("exchange_spi.collective_build"):
                        self._cond.wait(1.0)
                    continue
                return e["entry"]
        built = None
        try:
            built = builder()
        except Exception as exc:
            log.info(
                "collective stage build failed (%s); "
                "falling back to the per-source gather",
                exc,
            )
            REGISTRY.counter("exchange.collective_fallbacks").update()
        with self._cond:
            e["state"] = "ready" if built is not None else "failed"
            e["entry"] = built
            self._cond.notify_all()
        return built

    def served(self, key, part: int, nparts: int) -> None:
        with self._cond:
            e = self._entries.get(key)
            if e is None or e["state"] == "building":
                return
            e["served"].add(int(part))
            if len(e["served"]) >= int(nparts):
                self._entries.pop(key, None)

    def discard_task(self, task_id: str) -> None:
        with self._cond:
            for k in [k for k in self._entries if task_id in k[1]]:
                self._entries.pop(k, None)

    def stats(self) -> dict:
        with self._cond:
            return {"entries": len(self._entries)}


#: the ONE collective-stage cache of this process (= this slice)
COLLECTIVE = _CollectiveCache()


def _build_collective(flat, batch_src, schema, nparts, max_rows, fold):
    """Dispatch the single-program exchange over ``flat`` (all batches
    of all ICI sources, source-major order): one counts program sizes
    the slabs, one collective program routes every row — versus one
    counts + one append program PER BATCH on the per-source path.
    Returns the stage entry dict, or None when any partition would
    exceed ``max_rows`` (the caller degrades to the grouped host
    merge, the same memory funnel the per-source path applies)."""
    import jax
    import jax.numpy as jnp

    from presto_tpu.exec.staging import bucket_capacity
    from presto_tpu.parallel import exchange as X

    names = tuple(schema.keys())
    pages = tuple(X.strip_dictionaries(pg) for pg, _ in flat)
    dests = tuple(d for _, d in flat)

    # per-column union dictionary + per-batch remap tables — the
    # sorted-union searchsorted discipline merge_payloads pins; the
    # remap itself applies IN-PROGRAM
    union: Dict[str, Optional[list]] = {}
    has_valid: Dict[str, bool] = {}
    for name in names:
        dicts = []
        anyv = False
        for pg, _ in flat:
            blk = pg.block(name)
            if blk.dictionary is not None:
                dicts.append(tuple(blk.dictionary.values))
            if blk.valid is not None:
                anyv = True
        union[name] = sorted(set().union(*dicts)) if dicts else None
        has_valid[name] = anyv
    remaps = []
    for pg, _ in flat:
        rm = {}
        for name in names:
            u = union[name]
            blk = pg.block(name)
            if u is not None and blk.dictionary is not None:
                uarr = np.asarray(u, object)
                vals = np.asarray(blk.dictionary.values, object)
                rm[name] = jnp.asarray(
                    np.searchsorted(uarr, vals).astype(np.int64)
                )
        remaps.append(rm)

    counts = np.asarray(
        jax.device_get(X.collective_counts(pages, dests, nparts))
    )
    _count_dispatch(1, fold)
    DEVICE.count_d2h(int(counts.nbytes))
    if fold is not None:
        fold(device_d2h_bytes=int(counts.nbytes))
    totals = counts.sum(axis=0)
    peak = int(totals.max(initial=0))
    if max_rows is not None and peak > max_rows:
        return None
    out_cap = bucket_capacity(peak)
    dtypes = {name: schema[name].np_dtype for name in names}
    out = X.collective_gather(
        pages, dests, tuple(remaps), dtypes, nparts, out_cap
    )
    _count_dispatch(1, fold)
    REGISTRY.counter("exchange.collective_stages").update()
    return {
        "out": out,
        "counts": counts,
        "totals": totals,
        "union": union,
        "names": names,
        "batch_src": tuple(batch_src),
    }


def _collective_page(entry, part: int, schema, fold):
    """One partition of a built stage entry as a Page — a single
    static-shape slice program per partition, same union dictionary,
    row order (flat batch order) and capacity bucket as
    :func:`device_merge`."""
    import jax.numpy as jnp

    from presto_tpu.exec.staging import bucket_capacity
    from presto_tpu.page import Block, Dictionary, Page

    from presto_tpu.parallel import exchange as X

    total = int(entry["totals"][part])
    pcap = bucket_capacity(total)
    taken = X.collective_take(
        entry["out"],
        entry["names"],
        jnp.asarray(part, jnp.int32),
        pcap,
    )
    _count_dispatch(1, fold)
    blocks = []
    for name in entry["names"]:
        u = entry["union"][name]
        blocks.append(
            Block(
                data=taken[name]["data"],
                valid=taken[name]["valid"],
                dtype=schema[name],
                dictionary=(
                    Dictionary(np.asarray(u, object))
                    if u is not None
                    else None
                ),
            )
        )
    return (
        Page(
            blocks=tuple(blocks),
            num_valid=jnp.asarray(total, jnp.int32),
            names=entry["names"],
        ),
        total,
    )


def _collective_flat(batches_by_source):
    """Source-major flattening shared by the collective entry points —
    the flat batch order IS the output row order, so it must match the
    merge task's source order exactly."""
    flat: List[tuple] = []
    batch_src: List[int] = []
    for i, src in enumerate(batches_by_source):
        for b in src:
            flat.append(b)
            batch_src.append(i)
    return flat, batch_src


def collective_merge(
    slice_id: str,
    srcs,
    batches_by_source,
    part: int,
    schema,
    nparts: int,
    max_rows=None,
    fold=None,
):
    """Single-program variant of :func:`device_merge`: ONE collective
    dispatch routes every source's batches for ALL partitions at once;
    this merge task takes partition ``part`` from the shared slabs.
    Bit-identical output (union dictionaries, flat-batch row order,
    zero-padded capacity bucket). Returns ``(page, total)`` or None —
    the caller falls back to :func:`device_merge` (then the grouped
    host merge), the PR 14 per-source path."""
    flat, batch_src = _collective_flat(batches_by_source)
    if not flat:
        return None
    key = (slice_id, tuple(srcs), int(nparts))
    entry = COLLECTIVE.lookup(
        key,
        lambda: _build_collective(
            flat, batch_src, schema, nparts, max_rows, fold
        ),
    )
    got = None
    if entry is not None:
        try:
            got = _collective_page(entry, part, schema, fold)
        except Exception as exc:
            log.info(
                "collective take failed (%s); per-source fallback", exc
            )
            REGISTRY.counter("exchange.collective_fallbacks").update()
    COLLECTIVE.served(key, part, nparts)
    return got


def collective_payloads(
    slice_id: str,
    srcs,
    batches_by_source,
    part: int,
    schema,
    nparts: int,
    fold=None,
):
    """Mixed-transport splice: the ICI sources' share of ``part`` out
    of the SAME collective program, degraded to host wire payloads —
    one (possibly empty) ``[(payload, schema, nrows), ...]`` list per
    source, index-aligned with ``batches_by_source`` — ready to
    interleave with the HTTP sources' payloads under
    ``merge_payloads``'s union-merge discipline (bit-equal to the wire
    path). Returns None when the collective program is unavailable;
    the caller degrades to :func:`ici_batches_to_payloads` per
    source."""
    from presto_tpu.exec import streaming as S

    flat, batch_src = _collective_flat(batches_by_source)
    if not flat:
        return None
    key = (slice_id, tuple(srcs), int(nparts))
    entry = COLLECTIVE.lookup(
        key,
        lambda: _build_collective(
            flat, batch_src, schema, nparts, None, fold
        ),
    )
    got = None
    if entry is not None:
        try:
            page, total = _collective_page(entry, part, schema, fold)
            payload, pschema, nr = S._page_to_payload(page)
            out = []
            start = 0
            nsrc = len(batches_by_source)
            for i in range(nsrc):
                n_i = int(
                    sum(
                        entry["counts"][b][part]
                        for b in range(len(flat))
                        if entry["batch_src"][b] == i
                    )
                )
                if n_i:
                    mask = np.zeros((nr,), bool)
                    mask[start : start + n_i] = True
                    out.append(
                        [
                            (
                                S._slice_payload(
                                    payload, pschema, mask
                                ),
                                pschema,
                                n_i,
                            )
                        ]
                    )
                else:
                    out.append([])
                start += n_i
            got = out
        except Exception as exc:
            log.info(
                "collective splice failed (%s); per-source fallback",
                exc,
            )
            REGISTRY.counter("exchange.collective_fallbacks").update()
    COLLECTIVE.served(key, part, nparts)
    return got


def ici_gather(slice_id: str, spec, deadline: float, probe, fold=None):
    """Coordinator half of the ICI gather edge: when the root stage's
    single-partition output is co-located, take it straight from the
    segment — the final gather stops paying serialization + HTTP.

    Returns host payloads ``[(payload, schema, nrows), ...]`` (the
    shape the result assembly consumes), or None — the caller falls
    back to the HTTP pull, which remains fully correct (the worker
    lazily materializes the segment on first HTTP read)."""
    if (
        not slice_id
        or spec.ici_slice != slice_id
        or spec.n_partitions != 1
    ):
        return None
    src = spec.task_id
    last_probe = 0.0
    while True:
        st = SEGMENT.peek(slice_id, src)
        if st == "sealed":
            got = SEGMENT.take(slice_id, src, 0)
            if got is not None:
                REGISTRY.counter("exchange.ici_edges").update()
                return ici_batches_to_payloads(got, 0, None)
            break
        if st == "foreign":
            break
        now = time.monotonic()
        if now > deadline:
            break
        if now - last_probe > 0.5:
            last_probe = now
            alive = probe()
            if alive is False:
                if SEGMENT.peek(slice_id, src) == "sealed":
                    continue
                break
        with tracing.wait("exchange_spi.ici_gather"):
            SEGMENT.wait(0.05)
    REGISTRY.counter("exchange.ici_fallbacks").update()
    return None
