"""Device-plane telemetry: execution accounting, cluster metrics
federation, and the time-series sampler.

Reference parity: the operability layer SURVEY.md §5.5 credits for
presto's production life — JMX beans scraped per node, federated by
the monitoring plane, and SQL-able via system tables. TPU-first
redesign: what matters on this engine is the *device plane* — program
dispatches, compile events, host<->device transfer bytes, and the
padding waste of capacity bucketing — none of which the reference
has an analogue for, and all of which ROADMAP item 1 ("dispatch
counts per query visibly down") needs a before/after probe on.

Three pieces, all host-side only (nothing here ever changes a
compiled program):

- :class:`DeviceTelemetry` — process-global counters incremented at
  the execution choke points (runner dispatch/fetch, staging
  transfers, ICI exchange fetches). ``enabled=False`` short-circuits
  every ``count_*`` method before it touches a counter, and callers
  guard their byte-size computations on ``enabled``, so the disabled
  plane costs one attribute read per site and the engine is bit-exact
  pre-PR either way.
- :func:`parse_prometheus` + :class:`MetricsFederation` — the
  coordinator scrapes worker ``/v1/metrics`` expositions and renders
  a per-node-labeled + cluster-summed exposition. Transport is
  injected (a ``fetch(uri) -> text`` callable), so this module stays
  out of the rpc plane.
- :class:`MetricsSampler` — a bounded ring buffer of
  ``(node, ts, name, value, rate)`` samples backing
  ``system.runtime.metrics_history``, with optional JSONL persistence
  in the journal/history segment idiom (append-only, torn-tail
  tolerant, rotate keeping the newest two segments).

Construction of these classes is confined to this module + audited
consumers (tools/analysis ``telemetry-plane`` pass), and the
``device.*`` / ``telemetry.*`` metric families register only here and
in utils/devicediag.py (``metric-names`` family confinement).
"""

from __future__ import annotations

import collections
import json
import os
import threading
import time
import weakref
from typing import Callable, Dict, Iterable, List, Optional, Tuple

from presto_tpu.utils import tracing
from presto_tpu.utils.metrics import REGISTRY

# ------------------------------------------------------- true compiles

_XLA_REQUEST = "/jax/core/compile/backend_compile_duration"
_XLA_CACHE_HIT = "/jax/compilation_cache/cache_hits"
_XLA_CACHE_LOAD_S = "/jax/compilation_cache/cache_retrieval_time_sec"
_xla_lock = threading.Lock()
_xla = {"requests": 0, "cache_loads": 0, "compile_s": 0.0}
_xla_installed = False


def _on_xla_event(event: str, **_kw) -> None:
    if event == _XLA_CACHE_HIT and DEVICE.enabled:
        with _xla_lock:
            _xla["cache_loads"] += 1


def _on_xla_duration(event: str, duration_secs: float, **_kw) -> None:
    # backend_compile_duration wraps compile_or_get_cached, so it fires
    # once a compile REQUEST, cache load or not; a load's retrieval
    # time is reported apart and comes back out of the compile time
    if event == _XLA_REQUEST:
        if DEVICE.enabled:
            with _xla_lock:
                _xla["requests"] += 1
                _xla["compile_s"] += duration_secs
    elif event == _XLA_CACHE_LOAD_S and DEVICE.enabled:
        with _xla_lock:
            _xla["compile_s"] -= duration_secs


def install_xla_listeners() -> None:
    """Count every XLA compile of the process through
    ``jax.monitoring`` — the engine's fragments, the mesh runner's
    programs and eager ops alike — and tell a true compile from a
    load out of the persistent cache. Called once, at import of
    ``presto_tpu``."""
    global _xla_installed
    if _xla_installed:
        return
    _xla_installed = True
    from jax import monitoring

    monitoring.register_event_listener(_on_xla_event)
    monitoring.register_event_duration_secs_listener(_on_xla_duration)


class DeviceTelemetry:
    """Process-global device-execution accounting.

    Per-query attribution does NOT live here: the runner folds the
    same quantities into its active stats sink (TaskStats worker-side,
    QueryStats locally) under its own locks — this class is the
    process-wide trajectory the bench and the metrics plane read."""

    def __init__(self):
        #: master gate (``telemetry.enabled``); True by default — the
        #: counters are host-side arithmetic on values the engine
        #: already holds. False restores bit-exact zero-delta.
        self.enabled = True
        self._dispatches = REGISTRY.counter("device.dispatches")
        self._compiles = REGISTRY.counter("device.compiles")
        self._compile_ms = REGISTRY.distribution("device.compile_ms")
        self._h2d = REGISTRY.counter("device.h2d_bytes")
        self._d2h = REGISTRY.counter("device.d2h_bytes")
        self._pad = REGISTRY.counter("device.pad_rows")
        self._live = REGISTRY.counter("device.live_rows")
        self._program_out = REGISTRY.counter("device.program_out_bytes")
        self._syncs = REGISTRY.counter("device.syncs")
        # pages of partial groups a cut aggregation emits, batch by batch
        self._agg_rows = REGISTRY.counter("device.agg_partial_rows")
        self._agg_slots = REGISTRY.counter("device.agg_out_slots")
        # join build sides the device sorts: prepared once a task for its
        # split batches, or built inside a program, once a dispatch
        self._join_builds = REGISTRY.counter("device.join_builds")
        self._join_build_slots = REGISTRY.counter("device.join_build_slots")
        # page-sized row moves of the grouped aggregations a program runs
        self._agg_row_moves = REGISTRY.counter("device.agg_row_moves")
        # key lanes the join probes of a program sort
        self._join_probe_lanes = REGISTRY.counter("device.join_probe_lanes")
        # partial groups a final aggregation merges: slots on the device,
        # rows on the host
        self._agg_merge_slots = REGISTRY.counter("device.agg_merge_slots")
        self._host_merge_rows = REGISTRY.counter("device.host_merge_rows")
        # does the results long-poll engage (server/rpc.pull_pages)
        self._results_waits = REGISTRY.counter("worker.results_waits")
        self._results_wait_timeouts = REGISTRY.counter(
            "worker.results_wait_timeouts"
        )
        self._pull_stalls = REGISTRY.counter("coordinator.pull_stalls")
        # a join's probe that ran unfiltered because the wait for its
        # dynamic filter ran out (server/coordinator.py): another
        # fragment, compiled where the process has not run it
        self._df_wait_expired = REGISTRY.counter("dynamic_filter.wait_expired")
        # residency by column of the staging cache (exec/staging.py)
        self._stage_col_hits = REGISTRY.counter("staging.col_hit")
        self._stage_col_misses = REGISTRY.counter("staging.col_miss")
        self._stage_evictions = REGISTRY.counter("staging.cache_evict")
        self._caches: "weakref.WeakSet" = weakref.WeakSet()
        self._caches_lock = threading.Lock()

    def set_enabled(self, flag: bool) -> None:
        self.enabled = bool(flag)
        tracing.set_accumulating(self.enabled)

    # ---------------------------------------------- choke-point hooks

    def count_dispatch(self, n: int = 1) -> None:
        """One compiled-program execution launched on the device."""
        if self.enabled:
            self._dispatches.update(n)

    def count_program_out(self, nbytes: int) -> None:
        """Static byte size of the page a dispatched program returns:
        what it allocates and writes on the device whether or not
        anyone fetches it (shapes only; nothing read from the device).
        """
        if self.enabled:
            self._program_out.update(int(nbytes))

    def count_sync(self) -> None:
        """One blocking ``jax.device_get`` of program outputs: the
        host waited for the device and the transfers (one a dispatched
        batch; a result longer than its speculative prefix costs a
        second)."""
        if self.enabled:
            self._syncs.update()

    def count_agg_page(self, rows: int, slots: int) -> None:
        """One page of partial groups a cut aggregation emitted for a
        batch: its live ``rows`` (the page's row count, among the
        control outputs the batch's one read brings anyway) and its
        static ``slots`` (a shape): nothing more is read from the
        device. Their ratio says how well the page is sized
        (ops.aggregation._out_capacity)."""
        if self.enabled:
            self._agg_rows.update(int(rows))
            self._agg_slots.update(int(slots))

    def count_join_build(self, slots: int) -> None:
        """One join build side the device sorted: prepared once for the
        split batches of a task (local_runner.prepare_builds, ``slots``
        after the cut to its matchable rows) or built inside a program
        (each dispatch of it). Its static ``slots``, a shape — nothing
        is read from the device."""
        if self.enabled:
            self._join_builds.update()
            self._join_build_slots.update(int(slots))

    def count_agg_row_moves(self, n: int) -> None:
        """The page-sized row moves — gathers by a sort's permutation or
        at group spans, ``nonzero_1d`` compactions, scatters — that the
        grouped aggregations of one dispatched program issue, as its
        trace counted them (``ops.aggregation.record_row_moves``):
        nothing is read from the device."""
        if self.enabled and n:
            self._agg_row_moves.update(int(n))

    def count_join_probe_lanes(self, n: int) -> None:
        """The key lanes that the join probes of one dispatched program
        sort — one a probe whose keys ride one uint32 lane, two one
        whose keys do not — as its trace counted them
        (``ops.join.record_probe_lanes``): nothing is read from the
        device."""
        if self.enabled and n:
            self._join_probe_lanes.update(int(n))

    def count_agg_merge(self, slots: int) -> None:
        """The slots of the pages of partial groups that one final
        aggregation merges on the device (``exchange_spi.device_merge``),
        as the merge program reads them: shapes only, nothing read from
        the device."""
        if self.enabled:
            self._agg_merge_slots.update(int(slots))

    def count_host_merge(self, rows: int) -> None:
        """The rows of pages of partial groups that a final aggregation
        merges on the host (``pages_wire.merge_payloads`` at the
        coordinator's gather, ``streaming.grouped_final_merge``), counted
        from the payloads it holds."""
        if self.enabled and rows:
            self._host_merge_rows.update(int(rows))

    def count_compile(self, ms: float) -> None:
        """A fresh compile-cache entry paid trace + XLA compile.

        ``ms`` is the first dispatch's host window (jit compiles
        lazily at first call, so compile time is only observable
        bundled with that dispatch — documented approximation)."""
        if self.enabled:
            self._compiles.update()
            self._compile_ms.add(float(ms))

    def count_h2d(self, nbytes: int) -> None:
        """Host -> device transfer (staging / restage / shard put)."""
        if self.enabled and nbytes > 0:
            self._h2d.update(int(nbytes))

    def count_d2h(self, nbytes: int) -> None:
        """Device -> host fetch (result gather, spill, ICI drain)."""
        if self.enabled and nbytes > 0:
            self._d2h.update(int(nbytes))

    def count_padding(self, live: int, capacity: int) -> None:
        """Capacity-bucket occupancy of one staged/produced page:
        ``capacity - live`` rows are padding the device computes over
        for nothing (pad-waste % = pad / (pad + live))."""
        if self.enabled and 0 <= live <= capacity:
            self._pad.update(int(capacity - live))
            self._live.update(int(live))

    def count_results_wait(self, timed_out: bool) -> None:
        """A results GET that the worker held on the task's condition;
        ``timed_out`` when its max-wait ran out with nothing to say."""
        if self.enabled:
            self._results_waits.update()
            if timed_out:
                self._results_wait_timeouts.update()

    def count_pull_stall(self) -> None:
        """The coordinator's look at a task whose held GET ran out."""
        if self.enabled:
            self._pull_stalls.update()

    def count_stage_columns(self, hits: int, misses: int) -> None:
        """One split batch looked up in the staging cache: the columns
        it found resident and those it had to read and stage."""
        if self.enabled:
            self._stage_col_hits.update(hits)
            self._stage_col_misses.update(misses)

    def track_cache(self, cache) -> None:
        """A live ``SplitCache`` whose ``used_bytes()`` the snapshot
        sums as ``stage_resident_bytes`` (held weakly)."""
        with self._caches_lock:
            self._caches.add(cache)

    # ------------------------------------------------------ snapshots

    def snapshot(self) -> Dict[str, float]:
        """Current totals (the bench diffs two of these around each
        measurement; tests assert zero delta when disabled).

        ``compiles`` / ``compile_ms`` are the engine's own first
        dispatches; ``xla_compiles`` counts what XLA really compiled
        process-wide (compile requests that were not loads from the
        persistent cache), ``xla_cache_loads`` the loads,
        ``xla_compile_ms`` the time in the compiles.
        ``worker.results_waits`` / ``worker.results_wait_timeouts`` /
        ``coordinator.pull_stalls`` say whether the results long-poll
        engages (waits with no time-outs and no stalls).
        ``dynamic_filter_wait_expired`` counts the joins whose probe ran
        unfiltered because the wait for the build's filter summary ran
        out (the registry's ``dynamic_filter.wait_expired``).
        ``program_out_bytes`` sums the static size of every dispatched
        fragment program's output page (beside ``d2h_bytes``, what of
        it was fetched), ``device_syncs`` the blocking fetches that
        brought it (local_runner._collect, materialize_page).
        ``agg_partial_rows`` / ``agg_out_slots`` sum the live rows and
        the static slots of the pages of partial groups that cut
        aggregations emitted, one a split batch (the served worker's
        scan task and the runner's own stream). ``join_builds`` /
        ``join_build_slots`` count the join build sides the device
        sorted — prepared once a task for its split batches, or inside
        a program at each dispatch — and their static slots.
        ``agg_row_moves`` counts the page-sized row moves the dispatched
        programs' grouped aggregations issue (0 where no aggregation
        takes the lane-by-lane branch). ``join_probe_lanes`` counts the
        key lanes the dispatched programs' join probes sort (one a probe
        on one uint32 lane, two on two). ``agg_merge_slots`` counts the
        slots of partial pages that final aggregations merge on the
        device, ``host_merge_rows`` the rows of those merged on the host.
        ``stage_col_hits`` / ``stage_col_misses`` count the columns a
        streamed split batch found resident in the staging cache or had
        to stage, ``stage_evictions`` the entries it dropped for room
        (the registry's ``staging.cache_evict``),
        ``stage_resident_bytes`` is what the process's staging caches
        hold now (a level, not a total). ``span_ms.*``,
        ``wait_ms.*`` and ``stmt_wall_ms`` are host time per layer
        (utils/tracing.py)."""
        with _xla_lock:
            xla = dict(_xla)
        with self._caches_lock:
            caches = list(self._caches) if self.enabled else ()
        out = {
            "dispatches": int(self._dispatches.total),
            "compiles": int(self._compiles.total),
            "compile_ms": float(self._compile_ms.values()["sum"]),
            "h2d_bytes": int(self._h2d.total),
            "d2h_bytes": int(self._d2h.total),
            "pad_rows": int(self._pad.total),
            "live_rows": int(self._live.total),
            "program_out_bytes": int(self._program_out.total),
            "device_syncs": int(self._syncs.total),
            "agg_partial_rows": int(self._agg_rows.total),
            "agg_out_slots": int(self._agg_slots.total),
            "join_builds": int(self._join_builds.total),
            "join_build_slots": int(self._join_build_slots.total),
            "agg_row_moves": int(self._agg_row_moves.total),
            "join_probe_lanes": int(self._join_probe_lanes.total),
            "agg_merge_slots": int(self._agg_merge_slots.total),
            "host_merge_rows": int(self._host_merge_rows.total),
            "xla_compiles": xla["requests"] - xla["cache_loads"],
            "xla_cache_loads": xla["cache_loads"],
            "xla_compile_ms": xla["compile_s"] * 1000.0,
            "worker.results_waits": int(self._results_waits.total),
            "worker.results_wait_timeouts": int(
                self._results_wait_timeouts.total
            ),
            "coordinator.pull_stalls": int(self._pull_stalls.total),
            "dynamic_filter_wait_expired": int(self._df_wait_expired.total),
            "stage_col_hits": int(self._stage_col_hits.total),
            "stage_col_misses": int(self._stage_col_misses.total),
            "stage_evictions": int(self._stage_evictions.total),
            "stage_resident_bytes": sum(c.used_bytes() for c in caches),
        }
        out.update(tracing.span_snapshot())
        return out


#: process-wide device-plane accounting (the ONE instance; servers
#: seed ``enabled`` from tier-1 config at boot)
DEVICE = DeviceTelemetry()


def device_snapshot() -> Dict[str, float]:
    """Module-level convenience for bench/tests."""
    return DEVICE.snapshot()


def pad_waste_pct(pad_rows: float, live_rows: float) -> float:
    """Padding share of device row slots actually computed over."""
    total = pad_rows + live_rows
    return (100.0 * pad_rows / total) if total > 0 else 0.0


# ---------------------------------------------------------- federation


def parse_prometheus(text: str) -> List[Tuple[str, str, float]]:
    """Parse a Prometheus text exposition into
    ``(sample_name, label_body, value)`` tuples. Comment/HELP/TYPE
    lines and malformed samples are skipped (scrapes must never
    fail on a partial body)."""
    out: List[Tuple[str, str, float]] = []
    for line in text.splitlines():
        line = line.strip()
        if not line or line.startswith("#"):
            continue
        head, _, val = line.rpartition(" ")
        if not head:
            continue
        try:
            value = float(val)
        except ValueError:
            continue
        if "{" in head:
            name, _, rest = head.partition("{")
            labels = rest.rstrip("}")
        else:
            name, labels = head, ""
        out.append((name, labels, value))
    return out


def _monotone(name: str) -> bool:
    """Samples safe to sum/rate across nodes: counters and summary
    sum/count streams (quantiles are not additive)."""
    return name.endswith(("_total", "_sum", "_count"))


class MetricsFederation:
    """Coordinator-side aggregation of per-node expositions.

    ``fetch`` is injected (``fetch(uri) -> exposition text``, raising
    on failure) so the transport — rpc policy, breakers — stays the
    coordinator's concern. A node whose scrape fails is dropped from
    that round (and counted on ``telemetry.scrape_failures``) rather
    than failing the federation."""

    def __init__(self, fetch: Callable[[str], str]):
        self._fetch = fetch
        self._failures = REGISTRY.counter("telemetry.scrape_failures")

    def scrape(
        self, nodes: Iterable[Tuple[str, str]]
    ) -> Dict[str, List[Tuple[str, str, float]]]:
        """``(node_id, metrics_uri)`` -> per-node parsed samples."""
        out: Dict[str, List[Tuple[str, str, float]]] = {}
        for node_id, uri in nodes:
            try:
                out[node_id] = parse_prometheus(self._fetch(uri))
            except Exception:
                self._failures.update()
        return out

    @staticmethod
    def render(by_node: Dict[str, List[Tuple[str, str, float]]]) -> str:
        """Per-node-labeled samples plus ``node="cluster"`` sums of
        every additive family — one exposition the dashboards scrape
        instead of N."""
        lines: List[str] = []
        sums: Dict[Tuple[str, str], float] = {}
        for node_id in sorted(by_node):
            for name, labels, value in by_node[node_id]:
                tag = f'node="{node_id}"'
                body = f"{tag},{labels}" if labels else tag
                lines.append(f"{name}{{{body}}} {value}")
                if _monotone(name):
                    key = (name, labels)
                    sums[key] = sums.get(key, 0.0) + value
        for (name, labels), value in sorted(sums.items()):
            body = 'node="cluster"' + (f",{labels}" if labels else "")
            lines.append(f"{name}{{{body}}} {value}")
        return "\n".join(lines) + "\n"


# ------------------------------------------------------------- sampler

#: rows per persisted segment before rotation (journal idiom: bounded
#: segments, newest two survive)
SEGMENT_ROWS = 4096


class MetricsSampler:
    """Bounded ring buffer of cluster metric samples — the backing
    store of ``system.runtime.metrics_history``.

    ``observe(node, pairs, ts)`` appends one row per (name, value)
    pair, computing ``rate`` against the previous sample of the same
    ``(node, name)`` stream (monotone streams only: a value that went
    backwards — a restarted worker — rates as 0 rather than negative).
    ``retention`` bounds TOTAL retained rows; the deque drops the
    oldest on overflow. With ``path`` set, every row also appends to a
    JSONL segment file (torn tails tolerated on read; rotation keeps
    ``path`` + ``path.1``)."""

    def __init__(
        self, retention: int = 4096, path: Optional[str] = None
    ):
        self._lock = threading.Lock()
        self._rows: "collections.deque" = collections.deque(
            maxlen=max(1, int(retention))
        )
        #: (node, name) -> (ts, value) of the previous observation
        self._last: Dict[Tuple[str, str], Tuple[float, float]] = {}
        self.path = path
        self._seg_rows = 0
        self._samples = REGISTRY.counter("telemetry.samples")

    def observe(
        self,
        node: str,
        pairs: Iterable[Tuple[str, float]],
        ts: Optional[float] = None,
    ) -> int:
        """Fold one scrape of ``node`` into the ring; returns rows
        appended."""
        if ts is None:
            ts = time.time()
        rows = []
        with self._lock:
            for name, value in pairs:
                value = float(value)
                prev = self._last.get((node, name))
                rate = 0.0
                if prev is not None and ts > prev[0] and value >= prev[1]:
                    rate = (value - prev[1]) / (ts - prev[0])
                self._last[(node, name)] = (ts, value)
                rows.append(
                    {
                        "node": node,
                        "ts": ts,
                        "name": name,
                        "value": value,
                        "rate": rate,
                    }
                )
            self._rows.extend(rows)
        # persistence OUTSIDE the ring lock (blocking-under-lock
        # discipline): the one writer is the coordinator's sampler
        # thread, so append order still matches ring order; a second
        # concurrent observer could only interleave whole lines, which
        # the ts-stamped read path tolerates
        if self.path and rows:
            self._persist(rows)
        self._samples.update(len(rows))
        return len(rows)

    def rows(self) -> List[dict]:
        """Retained samples, oldest first (the system-table view)."""
        with self._lock:
            return list(self._rows)

    # ------------------------------------------------- JSONL segments

    def _persist(self, rows: List[dict]) -> None:
        """Append + rotate, lock-free (single-writer: the sampler
        thread); all I/O errors are swallowed — persistence must never
        fail a scrape."""
        try:
            if self._seg_rows >= SEGMENT_ROWS:
                os.replace(self.path, self.path + ".1")
                self._seg_rows = 0
            with open(self.path, "a") as f:
                for r in rows:
                    f.write(json.dumps(r) + "\n")
            self._seg_rows += len(rows)
        except OSError:
            pass

    @staticmethod
    def read_persisted(path: str) -> List[dict]:
        """Replay persisted samples, oldest segment first, skipping
        torn/corrupt lines (the history-store read discipline)."""
        out: List[dict] = []
        for p in (path + ".1", path):
            try:
                with open(p) as f:
                    for line in f:
                        try:
                            out.append(json.loads(line))
                        except ValueError:
                            continue  # torn tail / partial write
            except OSError:
                continue
        return out
