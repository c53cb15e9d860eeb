"""Host -> device page staging.

Reference parity: the page-source -> Page boundary (ConnectorPageSource
feeding the operator pipeline, SURVEY.md §3.3) plus the native worker's
page staging (SURVEY.md §2.3 "presto_cpp ... page staging").

SPI column payloads (see connectors.spi.Connector.create_page_source):
- numeric numpy array in *native repr* (unscaled ints for decimals,
  epoch-days for dates) -> zero-copy device put
- object numpy array of Python values (None = NULL) -> logical ingest
- DictColumn (pre-encoded ids + sorted dictionary) -> direct

Capacity bucketing: capacities are rounded up to power-of-two buckets so
every split of similar size reuses the same compiled fragment
(SURVEY.md §7 "Hard parts: dynamic shapes" — bucketed padding).
"""

from __future__ import annotations

import collections
import dataclasses
import queue
import threading
from typing import Dict, List, NamedTuple, Optional

import jax.numpy as jnp
import numpy as np

from presto_tpu import types as T
from presto_tpu.connectors.tpch import DictColumn
from presto_tpu.page import Block, Dictionary, Page
from presto_tpu.utils import tracing
from presto_tpu.utils.telemetry import DEVICE

MIN_BUCKET = 1 << 10

#: default device-resident split-cache budget (tier-1 key
#: ``staging.cache-bytes`` overrides). 4GB: big enough that the
#: columns TPC-H Q1 and Q6 scan of SF10's ``lineitem`` (their union,
#: held once by column) stay resident across statements instead of
#: re-staging every pass, while staying well under v5e HBM (16GB) and
#: the 8GB default memory pool, so cache fills never crowd out running
#: queries (sizes measured on the chip: PERF.md §4)
DEFAULT_CACHE_BYTES = 4 << 30

#: split batches the streamed scan loops stage ahead of the batch the
#: device is executing (:func:`prefetch_iter`'s ``depth``)
PREFETCH_DEPTH = 2


@dataclasses.dataclass
class ArrayColumn:
    """Array-column staging payload: int32 offsets (n+1) over flat
    values (+ optional per-ROW validity and element dictionary values).
    The wire/staging twin of Block.offsets (reference: ArrayBlock)."""

    offsets: np.ndarray
    values: np.ndarray
    valid: Optional[np.ndarray] = None
    dict_values: Optional[tuple] = None

    def __getitem__(self, sl: slice) -> "ArrayColumn":
        """Row-slice (wire chunking): offsets rebase to the slice."""
        lo = sl.start or 0
        n = len(self.offsets) - 1
        hi = min(sl.stop if sl.stop is not None else n, n)
        off = np.asarray(self.offsets[lo : hi + 1], np.int32)
        base = int(off[0]) if len(off) else 0
        end = int(off[-1]) if len(off) else base
        return ArrayColumn(
            offsets=off - base,
            values=np.asarray(self.values)[base:end],
            valid=None if self.valid is None else self.valid[lo:hi],
            dict_values=self.dict_values,
        )


@dataclasses.dataclass
class MaskedColumn:
    """Native-representation column + validity mask (+ optional
    dictionary values): the exchange-wire staging form — keeps decimals
    scaled/exact where an object array would round-trip through Python
    values (pages_wire.deserialize_page produces these)."""

    data: np.ndarray
    valid: np.ndarray
    values: Optional[tuple] = None  # dictionary values when string-typed


def obj_array(values) -> np.ndarray:
    """Element-wise object ndarray (np.asarray would collapse
    equal-length list values — array columns — into a 2-D array)."""
    arr = np.empty(len(values), dtype=object)
    for i, v in enumerate(values):
        arr[i] = v
    return arr


def bucket_capacity(n: int) -> int:
    """Round up to the next power-of-two bucket (min 1024)."""
    cap = MIN_BUCKET
    while cap < n:
        cap <<= 1
    return cap


def stage_page(
    data: Dict[str, object],
    schema: Dict[str, T.DataType],
    capacity: Optional[int] = None,
) -> Page:
    """Build a device Page from SPI column payloads."""
    with tracing.phase("staging", site="stage_page"):
        return _stage_page(data, schema, capacity)


def _stage_page(
    data: Dict[str, object],
    schema: Dict[str, T.DataType],
    capacity: Optional[int],
) -> Page:
    from presto_tpu.connectors.spi import payload_len

    names = tuple(schema.keys())
    n = 0
    for v in data.values():
        n = payload_len(v)
        break
    cap = capacity if capacity is not None else bucket_capacity(n)
    blocks = []
    for name in names:
        t = schema[name]
        v = data[name]
        if isinstance(v, ArrayColumn):
            off = np.asarray(v.offsets, np.int32)
            offsets = np.full(cap + 1, off[-1] if len(off) else 0,
                              np.int32)
            offsets[: len(off)] = off
            valid = None
            if v.valid is not None:
                vpad = np.zeros(cap, bool)
                vpad[: len(v.valid)] = v.valid
                valid = jnp.asarray(vpad)
            vals = np.asarray(v.values, t.element.np_dtype)
            # bucket the VALUE axis too: exact element counts would
            # make every distinct total a fresh XLA input shape
            vcap = bucket_capacity(len(vals))
            vpadded = np.zeros(vcap, t.element.np_dtype)
            vpadded[: len(vals)] = vals
            blocks.append(
                Block(
                    data=jnp.asarray(vpadded),
                    valid=valid,
                    dtype=t,
                    dictionary=(
                        Dictionary(np.asarray(v.dict_values, object))
                        if v.dict_values is not None
                        else None
                    ),
                    offsets=jnp.asarray(offsets),
                )
            )
            continue
        if isinstance(v, MaskedColumn):
            arr = v.data.astype(t.np_dtype, copy=False)
            # long decimals carry (n, 2) limb pairs; pad on axis 0
            padded = np.zeros((cap,) + arr.shape[1:], dtype=t.np_dtype)
            padded[: len(arr)] = arr
            vpad = np.zeros(cap, dtype=bool)
            vpad[: len(arr)] = v.valid
            blocks.append(
                Block(
                    data=jnp.asarray(padded),
                    valid=jnp.asarray(vpad),
                    dtype=t,
                    dictionary=(
                        Dictionary(v.values) if v.values is not None else None
                    ),
                )
            )
        elif isinstance(v, DictColumn):
            ids = np.asarray(v.ids, dtype=np.int32)
            pad = np.zeros(cap - len(ids), dtype=np.int32)
            blocks.append(
                Block(
                    data=jnp.asarray(np.concatenate([ids, pad])),
                    valid=None,
                    dtype=t,
                    dictionary=Dictionary(v.values),
                )
            )
        elif isinstance(v, np.ndarray) and v.dtype != object:
            arr = v.astype(t.np_dtype, copy=False)
            padded = np.zeros((cap,) + arr.shape[1:], dtype=t.np_dtype)
            padded[: len(arr)] = arr
            blocks.append(
                Block(data=jnp.asarray(padded), valid=None, dtype=t)
            )
        else:
            vals = list(v) + [None] * (cap - len(v))
            blocks.append(Block.from_pylist(vals, t))
    page = Page(
        blocks=tuple(blocks),
        num_valid=jnp.asarray(n, jnp.int32),
        names=names,
    )
    # device-plane accounting (utils/telemetry.py): the h2d transfer
    # this staging paid and the capacity-bucket padding the device
    # will compute over; guarded so the disabled plane skips even the
    # nbytes walk
    if DEVICE.enabled:
        DEVICE.count_h2d(page_nbytes(page))
        DEVICE.count_padding(n, cap)
    return page


def merge_column_chunks(parts: List[object], dtype=None):
    """Concatenate one column's per-split payload chunks — a
    single-column view over ``pages_wire.merge_payloads`` (ONE
    implementation of the union-dictionary + id-remap + masked-mix
    merge; this wrapper exists for split-payload callers that work
    column-at-a-time). ``dtype`` only matters for the empty case."""
    from presto_tpu.server.pages_wire import merge_payloads

    if len(parts) == 1:
        return parts[0]
    merged = merge_payloads(
        [({"c": p}, None, 0) for p in parts],
        {"c": dtype or T.BIGINT},
    )
    return merged["c"]


def page_to_host(staged, nbytes: int):
    """Pull a cache entry's device buffers back to host RAM (the spill
    write of the host-spill lane). Entries are pytrees (a whole-table
    ``Page`` or one :class:`StagedColumn`), so the transfer is one
    generic device_get over data/validity/offsets/children — static
    aux (dtype, dictionary, names) rides along untouched."""
    import jax

    DEVICE.count_d2h(nbytes)
    return jax.device_get(staged)


def host_to_page(host, nbytes: int):
    """Restage a spilled host pytree back onto the device (the staged
    twin of :func:`page_to_host`; lives HERE so every host->device
    transfer stays in this module — tools/check_device_puts.py)."""
    import jax

    staged = jax.tree_util.tree_map(jnp.asarray, host)
    DEVICE.count_h2d(nbytes)
    return staged


def stage_params(params: tuple) -> tuple:
    """A fragment program's hoisted parameter vector on the device,
    for a task that runs many batches through one program: handed
    over from the host, every scalar of it is a transfer of its own
    at every call (local_runner._dispatch)."""
    import jax

    staged = jax.device_put(params)
    DEVICE.count_h2d(
        sum(int(getattr(p, "nbytes", 0)) for p in params)
    )
    return staged


def block_nbytes(b: Block) -> int:
    """Device bytes one staged column holds (data/validity/offsets
    buffers, recursing into array/map/row children)."""
    n = int(b.data.nbytes)
    if b.valid is not None:
        n += int(b.valid.nbytes)
    if b.offsets is not None:
        n += int(b.offsets.nbytes)
    for child in b.children or ():
        n += block_nbytes(child)
    return n


def page_nbytes(page: Page) -> int:
    """Device bytes a staged page holds — the accounting unit for the
    split cache and the memory pool."""
    return sum(block_nbytes(b) for b in page.blocks)


class StagedColumn(NamedTuple):
    """One column of one split range on the device: the split cache's
    entry for streamed scans. Statements that scan different column
    sets of a table share what they have in common, so what stays
    resident is the union of the columns, not a page per statement
    shape. ``num_valid`` is the row count of the range as it was
    staged (a device scalar, so a page built from resident columns
    moves nothing host->device)."""

    block: Block
    num_valid: jnp.ndarray


def columns_of_page(page: Page) -> Dict[str, StagedColumn]:
    """A freshly staged page of one split range as cache entries."""
    return {
        name: StagedColumn(block, page.num_valid)
        for name, block in zip(page.names, page.blocks)
    }


def page_of_columns(names, parts: Dict[str, StagedColumn]) -> Page:
    """The ``Page`` a whole staging of ``names`` would have built,
    assembled from per-column entries of the same split range."""
    return Page(
        blocks=tuple(parts[n].block for n in names),
        num_valid=parts[names[0]].num_valid,
        names=tuple(names),
    )


class SplitCache:
    """Device-resident cache of staged columns with an LRU byte budget.

    Reference parity: the split-level half of the reference's
    fragment-result / raw-data caching tier (Alluxio-style local cache
    on the native worker, SURVEY.md §7 host->device staging as the
    TPU-native analogue of disk I/O). An entry of a streamed scan is
    ONE COLUMN of one split range (a :class:`StagedColumn` keyed by
    ``(table handle, column, lo, hi, capacity bucket, tpu_offload)``,
    ``LocalQueryRunner.stage_split``), so scans with different column
    sets share what overlaps; a table under ``max_device_rows`` is one
    whole staged ``Page`` (``_load_table``). A hit skips BOTH the
    connector read and the host->device transfer.

    Budget discipline: entries charge the byte budget (LRU eviction at
    the boundary) AND reserve against the node :class:`MemoryPool`
    under the shared ``table-cache`` owner via ``try_reserve`` — a
    cache fill must never kill a running query to make room; a full
    pool just means the page is not cached. ``reserve_required=True``
    (whole-table loads, the historical behavior) uses the raising
    ``reserve`` instead, so a table that cannot fit fails the query
    the same way it always has.

    Metrics: ``staging.cache_hit`` / ``staging.cache_miss`` /
    ``staging.cache_evict`` counters plus the ``staging.cache_bytes``
    occupancy distribution; live occupancy is served by
    ``system.runtime.caches``.

    Host-spill lane (cluster memory governance): with a non-zero
    ``spill_bytes`` budget, an evicted entry — LRU budget pressure or
    a running query's pool-pressure reclaim — moves its page to a
    host-RAM spill store (``page_to_host``) instead of being dropped:
    its HBM reservation is released immediately, but a later ``get``
    restages the host copy (``host_to_page``) and re-admits it under
    the normal budget/pool discipline — the data gets slower, not
    dead. Spilled bytes are accounted (``spill_*`` stats fields),
    metered (``spill.*`` metrics), and visible in
    ``system.runtime.caches`` / ``system.runtime.memory``.
    """

    #: pool owner shared by every cached page (excluded from the
    #: coordinator's kill-largest victim scan)
    OWNER = "table-cache"

    def __init__(self, budget_bytes: int = DEFAULT_CACHE_BYTES,
                 pool=None, spill_bytes: int = 0):
        self.budget = int(budget_bytes)
        self.pool = pool
        self._lock = threading.RLock()
        self._entries: "collections.OrderedDict" = collections.OrderedDict()
        #: key -> pin count: entries serving an EXECUTING batch are
        #: pinned — eviction must not release their pool accounting
        #: while the page is live on device (over-commit). Write
        #: invalidation still drops pinned entries (correctness wins).
        self._pins: Dict = {}
        self._bytes = 0
        self.hits = 0
        self.misses = 0
        self.evictions = 0
        #: host-RAM spill store: key -> (host pytree, nbytes). 0
        #: budget = the lane is off and eviction drops pages exactly
        #: as before (tier-1: memory.host-spill-bytes)
        self.spill_budget = int(spill_bytes)
        self._spill: "collections.OrderedDict" = collections.OrderedDict()
        self._spill_bytes = 0
        #: bumped by invalidate()/clear(): a restage that started
        #: before a write must not re-admit (or re-spill) its pre-write
        #: copy after the invalidation — the DMA runs outside the lock
        self._epoch = 0
        self.spills = 0
        self.restages = 0
        #: optional ``(nbytes) -> None`` hook: attributes restage
        #: traffic to the active query/task stats sink (the runner
        #: wires it so per-query spilled bytes surface in QueryInfo)
        self.on_restage = None
        if pool is not None and hasattr(pool, "add_pressure_hook"):
            # yield cached bytes to running queries on pool pressure:
            # a query's raising reserve evicts LRU cache entries
            # before the kill-largest policy fires — droppable cache
            # must never cost a live query its reservation
            pool.add_pressure_hook(self.evict_bytes)
        DEVICE.track_cache(self)

    def set_spill_budget(self, nbytes: int) -> None:
        """(Re)size the host-spill budget (the worker wires the tier-1
        ``memory.host-spill-bytes`` key here after construction)."""
        with self._lock:
            self.spill_budget = int(nbytes)
            while self._spill_bytes > self.spill_budget:
                if not self._drop_one_spilled():
                    break

    # ------------------------------------------------------------ access

    def get(self, key, pin: bool = False) -> Optional[Page]:
        """Cached page for ``key`` (refreshes LRU order), or None.
        Counts hit/miss metrics — call once per staging decision.
        ``pin=True`` marks the entry in-use until :meth:`unpin`."""
        from presto_tpu.utils.metrics import REGISTRY

        with self._lock:
            entry = self._entries.get(key)
            if entry is not None:
                self._entries.move_to_end(key)
                if pin:
                    self._pins[key] = self._pins.get(key, 0) + 1
                self.hits += 1
                REGISTRY.counter("staging.cache_hit").update()
                return entry[0]
            # remove from the spill store BEFORE re-admission: put()
            # may evict (and re-spill) other entries to make room, and
            # its spill traffic must never pop THIS key out from under
            # the accounting below (a double subtraction). A racing
            # get() for the same key sees a plain miss and re-stages
            # its own copy — the documented duplicate-staging shape.
            got = self._spill.pop(key, None)
            if got is not None:
                self._spill_bytes -= got[1]
                epoch = self._epoch
            else:
                self.misses += 1
                REGISTRY.counter("staging.cache_miss").update()
                return None
        page = self._restage_spilled(key, got, pin, epoch)
        if page is not None:
            # the host copy saved the connector read AND is back on
            # device: a (slower) hit, not a miss
            with self._lock:
                self.hits += 1
            REGISTRY.counter("staging.cache_hit").update()
            return page
        with self._lock:
            self.misses += 1
        REGISTRY.counter("staging.cache_miss").update()
        return None

    def _restage_spilled(self, key, got, pin: bool,
                         epoch: int) -> Optional[Page]:
        """Restage a popped spill entry to device and re-admit it under
        the normal budget/pool discipline. Runs with NO cache lock held
        — the host->device copy is a multi-MB DMA and must not stall
        concurrent scans (the same discipline as :meth:`evict_bytes`'s
        spill copies). Returns None when re-admission does not fit (the
        host copy goes back to the spill store and the caller falls
        back to a plain miss — correct, just slower) or when a write
        invalidated the table mid-restage (``epoch`` guard: the stale
        pre-write copy is dropped and the miss re-stages fresh data)."""
        from presto_tpu.utils.metrics import REGISTRY

        host, nbytes = got
        page = host_to_page(host, nbytes)  # DMA, no lock held
        if not self.put(key, page, nbytes, pin=pin, expect_epoch=epoch):
            with self._lock:
                if self._epoch != epoch:
                    # invalidated mid-restage: nothing of the
                    # pre-write copy may survive, in cache OR spill
                    return None
                # no device room: the host copy stays spilled
                # (re-inserted as newest; trim back under budget if
                # re-admission's eviction traffic overfilled the
                # store meanwhile). Pop-subtract any copy that landed
                # under this key while the lock was dropped — a plain
                # assignment would leak its bytes into _spill_bytes
                prev = self._spill.pop(key, None)
                if prev is not None:
                    self._spill_bytes -= prev[1]
                self._spill[key] = (host, nbytes)
                self._spill_bytes += nbytes
                while self._spill_bytes > self.spill_budget:
                    if not self._drop_one_spilled():
                        break
            return None
        with self._lock:
            self.restages += 1
            spill_now = self._spill_bytes
        REGISTRY.counter("spill.pages_restaged").update()
        REGISTRY.counter("spill.bytes_restaged").update(nbytes)
        REGISTRY.distribution("spill.pool_bytes").add(spill_now)
        if self.on_restage is not None:
            try:
                self.on_restage(nbytes)
            except Exception:
                pass  # attribution must never fail the staging path
        return page

    def _spill_insert(self, key, host, nbytes: int) -> bool:
        """Admit an already-copied host tree into the spill store,
        trimming older entries under the budget (caller holds the
        lock; the device->host copy happened in the caller)."""
        from presto_tpu.utils.metrics import REGISTRY

        while self._spill_bytes + nbytes > self.spill_budget:
            if not self._drop_one_spilled():
                return False
        old = self._spill.pop(key, None)
        if old is not None:
            # replacing a copy under the same key: its bytes leave the
            # store with it (or _spill_bytes inflates forever)
            self._spill_bytes -= old[1]
        self._spill[key] = (host, nbytes)
        self._spill_bytes += nbytes
        self.spills += 1
        REGISTRY.counter("spill.pages_spilled").update()
        REGISTRY.counter("spill.bytes_spilled").update(nbytes)
        REGISTRY.distribution("spill.pool_bytes").add(self._spill_bytes)
        return True

    def _drop_one_spilled(self) -> bool:
        """Drop the oldest spilled entry (caller holds the lock)."""
        from presto_tpu.utils.metrics import REGISTRY

        if not self._spill:
            return False
        _key, (_host, nbytes) = self._spill.popitem(last=False)
        self._spill_bytes -= nbytes
        REGISTRY.counter("spill.pages_dropped").update()
        return True

    def unpin(self, key) -> None:
        """Drop one pin (no-op for unknown/already-invalidated keys)."""
        with self._lock:
            n = self._pins.get(key, 0) - 1
            if n > 0:
                self._pins[key] = n
            else:
                self._pins.pop(key, None)

    def put(self, key, page: Page, nbytes: Optional[int] = None,
            reserve_required: bool = False, pin: bool = False,
            expect_epoch: Optional[int] = None) -> bool:
        """Insert a staged page, evicting LRU entries past the budget
        (pinned entries are skipped — their pages are live on device).
        Returns True when the page is now cache-owned (its bytes are
        reserved under :attr:`OWNER`); False when it did not fit — the
        page still serves the current caller either way. ``pin=True``
        marks the fresh entry in-use until :meth:`unpin`.
        ``expect_epoch`` (the restage path) refuses the insert when an
        invalidation landed since the caller snapshotted the epoch."""
        from presto_tpu.utils.metrics import REGISTRY

        nbytes = page_nbytes(page) if nbytes is None else int(nbytes)
        with self._lock:
            if nbytes > self.budget:
                return False
            if self._pins.get(key):
                # a concurrent duplicate staging of an entry that is
                # EXECUTING on device: replacing it would release its
                # pool accounting mid-flight — the caller keeps (and
                # accounts) its own copy instead
                return False
        # reserve OUTSIDE the cache lock (and BEFORE the budget
        # eviction — a failed pool reservation must not have emptied
        # the cache for nothing): a raising reserve can run pressure
        # hooks (including this cache's own evict_bytes) or block on
        # the governance lane, and neither may stall concurrent scans
        # behind the cache lock
        if self.pool is not None:
            if reserve_required:
                # raising reserve (pressure hook + kill-largest may
                # fire): a whole-table load that cannot fit is a
                # query failure, as it was before the cache existed
                self.pool.reserve(self.OWNER, nbytes)
            elif not self.pool.try_reserve(self.OWNER, nbytes):
                return False
        dropped: list = []
        epoch = -1
        try:
            with self._lock:
                epoch = self._epoch
                if (
                    expect_epoch is not None
                    and self._epoch != expect_epoch
                ):
                    # a write invalidated this table while the caller
                    # was copying: the page is pre-write — don't cache
                    if self.pool is not None:
                        self.pool.release(self.OWNER, nbytes)
                    return False
                if self._pins.get(key):
                    # pinned by a racing duplicate staging since the
                    # pre-check: undo the reservation, keep their copy
                    if self.pool is not None:
                        self.pool.release(self.OWNER, nbytes)
                    return False
                old = self._entries.pop(key, None)
                if old is not None:
                    self._release(old[1])
                while self._bytes + nbytes > self.budget:
                    if not self._evict_one_unpinned(dropped):
                        # every resident entry is pinned: the budget
                        # cannot be met — undo the reservation and
                        # don't cache
                        if self.pool is not None:
                            self.pool.release(self.OWNER, nbytes)
                        return False
                self._entries[key] = (page, nbytes)
                if pin:
                    self._pins[key] = self._pins.get(key, 0) + 1
                self._bytes += nbytes
                REGISTRY.distribution("staging.cache_bytes").add(
                    self._bytes
                )
                return True
        finally:
            # evicted pages offload to the host spill store with no
            # lock held (device->host DMA) — on success AND on the
            # all-pinned failure path (their device bytes are gone
            # either way)
            self._spill_dropped(dropped, epoch)

    # -------------------------------------------------------- maintenance

    def _release(self, nbytes: int) -> None:
        self._bytes -= nbytes
        if self.pool is not None:
            self.pool.release(self.OWNER, nbytes)

    def _evict_one_unpinned(self, dropped: list) -> bool:
        """Evict the least-recently-used UNPINNED entry (caller holds
        the lock). Returns False when none is evictable. The evicted
        (key, page, nbytes) is appended to ``dropped`` — the caller
        hands the batch to :meth:`_spill_dropped` AFTER releasing the
        lock (degrade before you drop, but never DMA under the lock);
        the DEVICE bytes free right now either way."""
        from presto_tpu.utils.metrics import REGISTRY

        key = next(
            (k for k in self._entries if not self._pins.get(k)), None
        )
        if key is None:
            return False
        page, nbytes = self._entries.pop(key)
        dropped.append((key, page, nbytes))
        self._release(nbytes)
        self.evictions += 1
        REGISTRY.counter("staging.cache_evict").update()
        return True

    def _spill_dropped(self, dropped: list, epoch: int) -> None:
        """Offload evicted pages to the host spill store. Called with
        NO cache lock held: the device->host copies are multi-MB DMA
        transfers and concurrent scans must not stall behind them (the
        page objects stay alive in ``dropped``, so the copy is safe
        after the accounting already freed). Lane off / page too big =
        plain drop, the legacy behavior. ``epoch`` was snapshotted by
        the caller while it held the lock popping these entries: a
        write that invalidates mid-copy must not find its table's
        pre-write pages re-admitted to the spill store afterwards."""
        for key, page, nbytes in dropped:
            if self.spill_budget <= 0 or nbytes > self.spill_budget:
                continue
            host = page_to_host(page, nbytes)  # DMA, no lock held
            with self._lock:
                if self._epoch != epoch:
                    return  # invalidated mid-copy: drop, don't re-admit
                self._spill_insert(key, host, nbytes)

    def evict_bytes(self, needed: int) -> int:
        """Evict unpinned LRU entries until at least ``needed`` bytes
        are freed (or none remain evictable) — the MemoryPool pressure
        hook: cached pages are droppable, so a running query's
        reservation reclaims them before any query gets killed.
        Returns the bytes actually freed."""
        from presto_tpu.utils.metrics import REGISTRY

        freed = 0
        evicted = 0
        dropped = []
        with self._lock:
            epoch = self._epoch
            while freed < needed:
                key = next(
                    (k for k in self._entries if not self._pins.get(k)),
                    None,
                )
                if key is None:
                    break
                page, nbytes = self._entries.pop(key)
                dropped.append((key, page, nbytes))
                self._release(nbytes)
                freed += nbytes
                evicted += 1
            self.evictions += evicted
        # host-spill lane: a blocked query's reservation reclaims the
        # DEVICE bytes above while the pages survive in host RAM —
        # over-capacity work gets slower, not dead. The device->host
        # copies run OUTSIDE the cache lock: this hook fires on the
        # memory-pressure hot path, and concurrent scans must not
        # stall behind multi-MB DMA transfers
        self._spill_dropped(dropped, epoch)
        if evicted:
            REGISTRY.counter("staging.cache_evict").update(evicted)
            REGISTRY.distribution("staging.cache_bytes").add(
                self._bytes
            )
        return freed

    def invalidate(self, handle) -> int:
        """Drop every entry of a written/dropped table (keys lead with
        the table handle), releasing their reservations. Returns the
        number of entries dropped. Matching is version-blind
        (``table_key``): a write must drop every SNAPSHOT's entries of
        the table, not just the exact pinned handle it was issued
        under."""
        tk = handle.table_key

        def _stale(k) -> bool:
            return getattr(k[0], "table_key", k[0]) == tk

        with self._lock:
            self._epoch += 1
            stale = [k for k in self._entries if _stale(k)]
            for k in stale:
                _page, nbytes = self._entries.pop(k)
                self._release(nbytes)
                self._pins.pop(k, None)
            # spilled copies of a written/dropped table are stale too
            for k in [k for k in self._spill if _stale(k)]:
                _host, nbytes = self._spill.pop(k)
                self._spill_bytes -= nbytes
            return len(stale)

    def clear(self) -> None:
        with self._lock:
            for _page, nbytes in self._entries.values():
                self._release(nbytes)
            self._entries.clear()
            self._pins.clear()
            self._spill.clear()
            self._spill_bytes = 0
            self._epoch += 1

    # ------------------------------------------------------------- stats

    def used_bytes(self) -> int:
        with self._lock:
            return self._bytes

    def spill_used_bytes(self) -> int:
        """Live host-RAM occupancy of the spill store (the heartbeat
        report's ``spilled_bytes``)."""
        with self._lock:
            return self._spill_bytes

    def stats(self) -> dict:
        with self._lock:
            return {
                "entries": len(self._entries),
                "bytes": self._bytes,
                "budget_bytes": self.budget,
                "hits": self.hits,
                "misses": self.misses,
                "evictions": self.evictions,
                "spill_entries": len(self._spill),
                "spill_bytes": self._spill_bytes,
                "spill_budget_bytes": self.spill_budget,
                "spills": self.spills,
                "restages": self.restages,
            }


def prefetch_iter(items, load_fn, depth: int, on_drop=None):
    """Pipelined prefetch staging: yield ``load_fn(item)`` for each
    item IN ORDER, staging up to ``depth`` items ahead on one
    background host thread — so the host converts/transfers split N+1
    while the device executes the compiled fragment over split N
    (SURVEY.md §7 "Hard parts: host->device staging", the
    double-buffering half of the worker hot-path optimization).

    ``depth <= 0`` is the exact serial path (stage, run, stage, run),
    bit-identical by construction since the same ``load_fn`` runs in
    the same order either way. The bounded queue caps staged-ahead
    residency to ``depth`` pages on top of whatever pool accounting
    ``load_fn`` itself performs; a staging error is re-raised at the
    consuming iteration it would have hit serially.

    Abandonment contract: closing the generator (loop exit or
    ``.close()``) stops the producer, JOINS it, and passes every
    staged-but-unconsumed result to ``on_drop`` — callers whose
    ``load_fn`` acquires resources (memory-pool reservations) release
    them there, and no ``load_fn`` call can outlive the iteration."""
    items = list(items)
    if depth <= 0 or len(items) <= 1:
        for it in items:
            yield load_fn(it)
        return
    q: "queue.Queue" = queue.Queue(maxsize=max(depth, 1))
    _END = object()
    stop = threading.Event()

    def _put(entry) -> bool:
        """Bounded put that gives up when the consumer went away (an
        aborted task must not leave this thread parked forever)."""
        while not stop.is_set():
            try:
                with tracing.wait("staging.prefetch_put"):
                    q.put(entry, timeout=0.1)
                return True
            except queue.Full:
                continue
        return False

    def producer():
        for it in items:
            if stop.is_set():
                return
            try:
                with tracing.phase("staging", site="prefetch"):
                    entry = (load_fn(it), None)
            except BaseException as e:  # re-raised consumer-side
                _put((None, e))
                return
            if not _put(entry):
                # consumer gone mid-flight: the staged result still
                # owns its resources — surrender it, don't leak it
                if on_drop is not None:
                    on_drop(entry[0])
                return
        _put((_END, None))

    t = threading.Thread(
        target=producer, name="staging-prefetch", daemon=True
    )
    t.start()
    try:
        while True:
            with tracing.wait("staging.prefetch_get"):
                page, err = q.get()
            if err is not None:
                raise err
            if page is _END:
                return
            yield page
    finally:
        stop.set()
        # join before returning: an in-flight load_fn must not touch
        # caller state (e.g. reserve pool bytes) after the driver
        # loop has moved on to its cleanup
        with tracing.wait("staging.prefetch_join"):
            t.join()
        while True:
            try:
                entry, err = q.get_nowait()
            except queue.Empty:
                break
            if err is None and entry is not _END and on_drop is not None:
                on_drop(entry)


def stage_sharded(tables, sharding):
    """Host pytrees -> device with an explicit sharding (the multi-chip
    staging twin of :func:`stage_page`; parallel.distributed_runner's
    scan placement). Lives here so every host->device transfer goes
    through this module (tools/check_device_puts.py enforces that)."""
    import jax

    out = [jax.device_put(t, sharding) for t in tables]
    if DEVICE.enabled:
        for t in jax.tree_util.tree_leaves(out):
            DEVICE.count_h2d(int(getattr(t, "nbytes", 0)))
    return out


class CatalogManager:
    """Mounted catalogs (reference: catalog config tier, SURVEY.md §5.6)."""

    def __init__(self):
        self._catalogs: Dict[str, object] = {}

    def register(self, name: str, connector) -> None:
        self._catalogs[name] = connector

    def get(self, name: str):
        if name not in self._catalogs:
            raise KeyError(f"catalog not found: {name}")
        return self._catalogs[name]

    def has(self, name: str) -> bool:
        return name in self._catalogs

    def names(self):
        return sorted(self._catalogs)
