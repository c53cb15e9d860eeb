"""Session & configuration system.

Reference parity: the three config tiers of SURVEY.md §5.6 —
  1. static node config (``etc/config.properties`` -> @Config POJOs),
  2. catalog config (``etc/catalog/*.properties``),
  3. per-query session properties (``SET SESSION k=v``,
     SystemSessionProperties).

Here: tier 1 = ``NodeConfig`` (dict + typed accessors, unknown keys fail
fast at boot, like airlift ConfigBinder); tier 3 = ``Session`` with typed,
validated, defaulted properties. The ``tpu_offload`` gate required by
BASELINE.json is a tier-3 property: when False, fragments execute on the
CPU backend (jax CPU), giving the reference's Java-worker/native-worker
dual-backend seam (SURVEY.md preamble) — same plans, different executor.
"""

from __future__ import annotations

import dataclasses
import re
from typing import Any, Callable, Dict, Optional


@dataclasses.dataclass(frozen=True)
class PropertyMetadata:
    """One typed session property (reference: PropertyMetadata<T>)."""

    name: str
    description: str
    py_type: type
    default: Any
    validate: Optional[Callable[[Any], None]] = None

    def coerce(self, value: Any) -> Any:
        if self.py_type is bool and isinstance(value, str):
            v = value.strip().lower()
            if v not in ("true", "false"):
                raise ValueError(f"{self.name}: expected boolean, got {value!r}")
            value = v == "true"
        else:
            value = self.py_type(value)
        if self.validate:
            self.validate(value)
        return value


def _positive(name):
    def check(v):
        if v <= 0:
            raise ValueError(f"{name} must be positive, got {v}")

    return check


def _non_negative(name):
    def check(v):
        if v < 0:
            raise ValueError(f"{name} must be >= 0, got {v}")

    return check


def _retry_policy_value(v):
    if str(v).upper() not in ("NONE", "TASK", "QUERY"):
        raise ValueError(
            f"retry_policy must be NONE | TASK | QUERY, got {v!r}"
        )


#: Engine-wide session properties (reference: SystemSessionProperties).
SYSTEM_SESSION_PROPERTIES: Dict[str, PropertyMetadata] = {
    p.name: p
    for p in [
        PropertyMetadata(
            "tpu_offload",
            "Execute plan fragments on the TPU backend (False = CPU oracle "
            "backend; the BASELINE.json per-session gate)",
            bool,
            True,
        ),
        PropertyMetadata(
            "speculative_result_rows",
            "Result-prefix rows piggybacked on the control fetch: "
            "results this small materialize in ONE device round trip "
            "(0 disables; round-trip and per-byte cost not measured "
            "on the chip)",
            int,
            1024,
            _non_negative("speculative_result_rows"),
        ),
        PropertyMetadata(
            "distributed_final",
            "Run keyed FINAL merges as a second worker stage reading "
            "hash partitions straight from producer workers "
            "(worker<->worker shuffle); False gathers partials at the "
            "coordinator",
            bool,
            True,
        ),
        PropertyMetadata(
            "split_queue_factor",
            "Scan ranges queued per worker for dynamic split placement "
            "(1 = static assignment; reference: SourcePartitionedScheduler "
            "split batching)",
            int,
            4,
            _positive("split_queue_factor"),
        ),
        PropertyMetadata(
            "join_distribution_type",
            "AUTOMATIC | PARTITIONED | BROADCAST (reference: AddExchanges "
            "join distribution choice)",
            str,
            "AUTOMATIC",
        ),
        PropertyMetadata(
            "join_max_broadcast_rows",
            "AUTOMATIC join distribution replicates the build side only "
            "when its estimated rows are at or below this bound; above "
            "it, qualifying joins run as hash-partitioned intermediate "
            "stages (reference: join_max_broadcast_table_size feeding "
            "AddExchanges' stats-driven choice, in rows not bytes "
            "because device pages are columnar and fixed-width)",
            int,
            1 << 21,
            _positive("join_max_broadcast_rows"),
        ),
        PropertyMetadata(
            "page_capacity",
            "Default device page capacity bucket (rows)",
            int,
            1 << 20,
            _positive("page_capacity"),
        ),
        PropertyMetadata(
            "hash_partition_count",
            "Number of partitions for hash-distributed exchanges "
            "(defaults to mesh device count at execution time when 0)",
            int,
            0,
        ),
        PropertyMetadata(
            "host_root_stage",
            "Run the final Output/Sort/Limit root stage host-side over "
            "the gathered result (the reference's single-partition root "
            "stage; avoids per-query XLA sort compiles)",
            bool,
            True,
        ),
        PropertyMetadata(
            "spill_enabled",
            "Allow larger-than-HBM execution: stream split batches "
            "through the compiled fragment and spill hash-bucketed "
            "partial states to host RAM (reference: spilling + grouped "
            "execution)",
            bool,
            True,
        ),
        PropertyMetadata(
            "max_device_rows",
            "Largest table staged whole into device memory; bigger "
            "scans use split-streamed execution (requires "
            "spill_enabled)",
            int,
            1 << 24,
            _positive("max_device_rows"),
        ),
        PropertyMetadata(
            "max_fragment_weight",
            "Largest plan weight compiled as ONE XLA program; heavier "
            "plans execute stage-at-a-time with device-resident "
            "intermediates (reference: tasks run fragments, never whole "
            "plans — SURVEY.md §3.3). 16 keeps single-heavy-op plans "
            "(Q1-class) whole while every multi-join plan fragments — "
            "a ~25-weight whole-plan program (Q3@SF1) compiles for many "
            "minutes where its fragments compile far faster (not "
            "measured on the chip). 0 compiles whole plans",
            int,
            16,
            _non_negative("max_fragment_weight"),
        ),
        PropertyMetadata(
            "enable_dynamic_filtering",
            "Stage-at-a-time joins fetch the executed build side's "
            "join-key min/max and pre-filter the probe side with the "
            "range (reference: dynamic filters flowing build->probe "
            "at runtime)",
            bool,
            True,
        ),
        PropertyMetadata(
            "dynamic_filtering_wait_ms",
            "Distributed dynamic filtering: how long probe split "
            "scheduling waits for the build-side filter summary before "
            "proceeding UNFILTERED (bounded — build-worker death or "
            "slowness degrades to the exact unfiltered plan, never "
            "blocks the query). Tier-1 twin: dynamic-filtering.wait-ms",
            float,
            2000.0,
            _non_negative("dynamic_filtering_wait_ms"),
        ),
        PropertyMetadata(
            "dynamic_filtering_ndv_limit",
            "Largest build-side distinct-value count kept as an "
            "IN-list summary (incl. dictionary string keys); above it "
            "only min/max bounds flow to the probe side. Tier-1 twin: "
            "dynamic-filtering.ndv-limit",
            int,
            64,
            _positive("dynamic_filtering_ndv_limit"),
        ),
        PropertyMetadata(
            "enable_plan_cache",
            "Parameterized plan cache + compiled-fragment reuse "
            "(plan/canonical.py): comparison/filter/projection literals "
            "hoist out of plans into runtime device inputs, so "
            "structurally identical statements reuse one planned and "
            "ONE compiled program, and warm PREPARE/EXECUTE does zero "
            "planning and zero compilation. False = pre-cache "
            "behavior: every literal variant plans and compiles its "
            "own program (bit-exact legacy path). Tier-1 twins: "
            "plan.cache-enabled, plan.cache-entries",
            bool,
            True,
        ),
        PropertyMetadata(
            "microbatch_wait_ms",
            "Micro-batched point-lookup serving: how long a dispatch-"
            "eligible statement may wait for concurrent same-"
            "fingerprint statements to group into ONE vmapped device "
            "dispatch (coordinator batch queue). 0 = off — the "
            "bit-exact pre-batching path, zero batches. Tier-1 twin: "
            "serving.microbatch-wait-ms",
            float,
            0.0,
            _non_negative("microbatch_wait_ms"),
        ),
        PropertyMetadata(
            "microbatch_max",
            "Largest micro-batch group (lanes of one batched "
            "dispatch). Tier-1 twin: serving.microbatch-max",
            int,
            16,
            _positive("microbatch_max"),
        ),
        PropertyMetadata(
            "enable_result_cache",
            "Serving-plane result reuse (server/result_cache.py): "
            "SELECT results cache on the canonical statement "
            "fingerprint x hoisted-literal vector x the snapshot ids "
            "pinned at plan time; a hit is zero planning and zero "
            "dispatch, invalidation is a snapshot/write-generation "
            "compare through the one audited write seam. False (the "
            "default) = bit-exact pre-cache behavior; every lane "
            "fails open to normal execution. Tier-1 twins: "
            "result-cache.enabled, result-cache.bytes",
            bool,
            False,
        ),
        PropertyMetadata(
            "result_cache_max_staleness_s",
            "Bounded-stale serving for cached SELECT results (the "
            "mview.max-staleness-s discipline generalized to tier-c "
            "reads): a result-cache entry invalidated by a write may "
            "still answer for this many seconds after going stale "
            "while ONE background refresh re-executes. 0 (the "
            "default) = stale entries never serve. Tier-1 twin: "
            "result-cache.max-staleness-s",
            float,
            0.0,
            _non_negative("result_cache_max_staleness_s"),
        ),
        PropertyMetadata(
            "mview_auto_rewrite",
            "MV-aware scan rewrite (server/result_cache.py): an "
            "eligible single-table aggregate SELECT whose shape "
            "matches a registered materialized view reads the "
            "maintained view instead of re-aggregating the base, "
            "without naming it — under the mview.max-staleness-s "
            "read-gate discipline (gate off = only provably-current "
            "views rewrite). False (the default) = no rewriting. "
            "Tier-1 twin: mview.auto-rewrite",
            bool,
            False,
        ),
        PropertyMetadata(
            "enable_operator_stats",
            "Trace per-operator output-row counters (plus static "
            "capacity/page-bytes) out of every compiled program and "
            "fold them into TaskStats/QueryStats as OperatorStats — "
            "the observability substrate history-based optimization "
            "reads. False = pre-PR programs with no counter outputs "
            "(one fewer traced scalar per operator)",
            bool,
            True,
        ),
        PropertyMetadata(
            "enable_history_stats",
            "Let optimizer.estimate_rows consult the query-history "
            "store (history.path) BEFORE connector stats: estimates "
            "for a previously-executed canonical plan shape come from "
            "observed actuals (Presto's history-based optimization). "
            "False — or no configured store — plans bit-exactly "
            "pre-history",
            bool,
            True,
        ),
        PropertyMetadata(
            "adaptive_enabled",
            "Adaptive execution (ROADMAP item 2 — Presto's HBO + "
            "adaptive-execution direction): statement-cache hits "
            "whose consulted history estimates have materially "
            "diverged REPLAN instead of serving the stale plan "
            "(epoch-versioned plan-cache entries), and the "
            "dynamic-filter build-summary barrier becomes a runtime "
            "decision point — observed build rows contradicting the "
            "estimate flip broadcast<->partitioned distribution, "
            "re-order the not-yet-scheduled join remainder, and "
            "resize the shuffle partition count. Every lane fails "
            "OPEN to the original plan. False (the default) = "
            "bit-exact pre-adaptive behavior",
            bool,
            False,
        ),
        PropertyMetadata(
            "adaptive_divergence_factor",
            "Relative change beyond which a learned/observed "
            "cardinality CONTRADICTS the estimate a plan was built "
            "on (symmetric ratio; shared by the replan seam and the "
            "runtime strategy switch). Tier-1 twin: "
            "adaptive.divergence-factor",
            float,
            4.0,
            _positive("adaptive_divergence_factor"),
        ),
        PropertyMetadata(
            "query_max_run_time_s",
            "Per-query wall-clock limit (seconds)",
            float,
            3600.0,
            _positive("query_max_run_time_s"),
        ),
        PropertyMetadata(
            "task_retry_budget",
            "Max task reassignments per query after connection-level "
            "worker failures (recoverable execution; generalizes the "
            "old retry-once-per-range — 0 disables retry entirely)",
            int,
            16,
            _non_negative("task_retry_budget"),
        ),
        PropertyMetadata(
            "speculation_enabled",
            "Straggler speculation on the gather path: re-launch a "
            "range on a second live worker when its task runs past the "
            "quantile-based threshold; first result wins, the loser is "
            "aborted (reference: MapReduce backup tasks)",
            bool,
            True,
        ),
        PropertyMetadata(
            "speculation_multiplier",
            "Straggler threshold = max(speculation_min_s, multiplier x "
            "p50 of this stage's completed-range durations)",
            float,
            4.0,
            _positive("speculation_multiplier"),
        ),
        PropertyMetadata(
            "speculation_min_s",
            "Floor of the straggler threshold (seconds) — speculation "
            "never fires on ranges faster than this",
            float,
            2.0,
            _positive("speculation_min_s"),
        ),
        PropertyMetadata(
            "retry_policy",
            "Fault-tolerant execution mode (reference: Trino Project "
            "Tardigrade's retry-policy). NONE = bit-for-bit legacy "
            "behavior; TASK = spool exchange pages (exchange.spool-path) "
            "and recover a dead worker mid-stage by rescheduling only "
            "the lost tasks, re-serving upstream inputs from the spool; "
            "QUERY = additionally allow a bounded full query restart as "
            "the last resort",
            str,
            "NONE",
            _retry_policy_value,
        ),
        PropertyMetadata(
            "query_retry_count",
            "Bounded full-query restarts under retry_policy=QUERY "
            "(0 disables query-level restart)",
            int,
            1,
            _non_negative("query_retry_count"),
        ),
        PropertyMetadata(
            "exchange_ici_enabled",
            "In-slice collective shuffle (server/exchange_spi.py): "
            "partitioned join/agg/distinct exchanges between workers "
            "co-located on one slice move device-to-device (no host "
            "copy, no serialization, no HTTP); cross-slice edges and "
            "recovery keep the HTTP/spool wire. False = bit-exact "
            "legacy HTTP shuffle. Seeded by tier-1 exchange.ici-enabled",
            bool,
            False,
        ),
        PropertyMetadata(
            "exchange_single_program",
            "Single-program collective stages (parallel/exchange.py): "
            "when every producer of a partitioned stage shares the "
            "mesh, the exchange compiles to ONE shard_map program "
            "whose all_to_all moves every partition in-program (one "
            "collective dispatch per stage instead of a per-source "
            "gather pass), transport settles per-EDGE (a lone "
            "cross-slice worker rides HTTP without demoting the "
            "co-located pairs), and the coordinator's final gather "
            "rides the ICI lane when the root stage is co-located. "
            "False = PR-14 per-source gather + all-or-nothing stage "
            "transport. Seeded by tier-1 exchange.single-program",
            bool,
            True,
        ),
    ]
}


class Session:
    """Per-query context: catalog/schema + typed session properties.

    Reference parity: presto Session + SystemSessionProperties resolution
    (typed, validated, defaulted from static config) — SURVEY.md §5.6.
    """

    def __init__(
        self,
        catalog: str = "tpch",
        schema: str = "tiny",
        properties: Optional[Dict[str, Any]] = None,
        user: str = "presto_tpu",
    ):
        self.catalog = catalog
        self.schema = schema
        self.user = user
        self._props: Dict[str, Any] = {}
        for k, v in (properties or {}).items():
            self.set(k, v)

    def set(self, name: str, value: Any) -> None:
        """SET SESSION name = value (unknown keys fail fast)."""
        meta = SYSTEM_SESSION_PROPERTIES.get(name)
        if meta is None:
            raise KeyError(f"unknown session property: {name}")
        self._props[name] = meta.coerce(value)

    def get(self, name: str) -> Any:
        meta = SYSTEM_SESSION_PROPERTIES.get(name)
        if meta is None:
            raise KeyError(f"unknown session property: {name}")
        return self._props.get(name, meta.default)

    def reset(self, name: str) -> None:
        self._props.pop(name, None)

    @property
    def tpu_offload(self) -> bool:
        return self.get("tpu_offload")


class NodeConfig:
    """Tier-1 static node config; unknown keys fail fast at boot."""

    KNOWN = {
        "node.id": str,
        "node.environment": str,
        "coordinator": bool,
        "http-server.port": int,
        "discovery.uri": str,
        "query.max-memory-per-node": str,
        # cluster memory governance (server/memory_arbiter.py): the
        # master gate (false = bit-exact pre-governance behavior), the
        # cluster-wide per-query cap, the admission high/low water
        # marks (fractions of the cluster's query-attributed capacity;
        # QUEUED queries are HELD, never failed, while over high
        # water), the blocked-reservation age that triggers the
        # low-memory killer, the longest a worker reservation may
        # block before failing, the victim policy
        # (total-reservation | last-admitted), and the host-RAM spill
        # budget for the degrade-before-kill lane
        "memory.governance-enabled": bool,
        "query.max-memory": str,
        "memory.admission-high-water": float,
        "memory.admission-low-water": float,
        "memory.blocked-timeout-s": float,
        "memory.reserve-block-max-s": float,
        "memory.kill-policy": str,
        "memory.host-spill-bytes": str,
        "exchange.max-buffer-size": str,
        "task.concurrency": int,
        # query-completed JSONL sink (reference: event-listener.properties)
        "event-listener.path": str,
        # unified RPC plane (server.rpc): per-call timeout + bounded
        # retries with exponential backoff + full jitter
        "rpc.request-timeout-s": float,
        "rpc.retries": int,
        "rpc.backoff-base-s": float,
        "rpc.backoff-max-s": float,
        # exchange pull pipelining: token-acked page-pull requests kept
        # in flight per pull loop (1 = strict request->ack->request)
        "rpc.pull-depth": int,
        # device-resident split cache: LRU byte budget for staged
        # columns kept across queries (0 keeps nothing)
        "staging.cache-bytes": str,
        # worker->coordinator announce cadence (healthy interval; the
        # failure backoff grows from it) and per-announce timeout
        "announcement.interval-s": float,
        "announcement.timeout-s": float,
        # per-worker circuit breaker: consecutive connection failures
        # to OPEN, and the OPEN cool-off before the half-open probe
        "failure-detector.threshold": int,
        "failure-detector.open-s": float,
        # distributed dynamic filtering: bounded wait for the build
        # summary before probe scheduling proceeds unfiltered, and the
        # NDV cap for IN-list summaries (exec/dynfilter.py)
        "dynamic-filtering.wait-ms": float,
        "dynamic-filtering.ndv-limit": int,
        # durable-exchange spool (server.spool): shared directory the
        # workers tee partitioned exchange pages into under
        # retry_policy=TASK/QUERY, its byte budget, and the TTL after
        # which committed attempts are garbage-collected
        "exchange.spool-path": str,
        "exchange.spool-bytes": str,
        "exchange.spool-ttl-s": float,
        # ICI-native collective shuffle (server/exchange_spi.py): the
        # master gate (false = bit-exact legacy HTTP shuffle; seeds the
        # exchange_ici_enabled session default) and an explicit slice
        # identity override — by default a worker derives its slice
        # from platform + host process, the co-location the in-slice
        # exchange segment actually requires
        "exchange.ici-enabled": bool,
        "exchange.slice-id": str,
        # single-program collective stages (PR 18): when every producer
        # of a partitioned stage shares the mesh, compile ONE
        # shard_map/all_to_all program per stage instead of per-source
        # gather passes, and publish single-partition (gather) root
        # output on the ICI lane too (true by default; the collective
        # path fails open to the per-source gather). The drain depth
        # bounds the background spool-tee queue (retry_policy=TASK)
        # before producers feel backpressure.
        "exchange.single-program": bool,
        "exchange.spool-drain-depth": int,
        # parameterized plan cache (plan/canonical.py): LRU entry bound
        # of the statement-level cache, and the enable_plan_cache
        # session default seed
        "plan.cache-entries": int,
        "plan.cache-enabled": bool,
        # micro-batched point-lookup serving (server/coordinator.py
        # batch queue + the vmapped compile entries in
        # plan/canonical.py): the hold window concurrent same-
        # fingerprint statements may wait to share ONE device
        # dispatch (0 = off, bit-exact pre-batching) and the largest
        # group size. Seed the microbatch_wait_ms / microbatch_max
        # session defaults
        "serving.microbatch-wait-ms": float,
        "serving.microbatch-max": int,
        # history-based statistics (plan/history.py): directory of the
        # crash-safe JSONL history store and its entry bound; the
        # optimizer consults observed per-operator actuals keyed by
        # canonical plan fingerprints before connector stats
        "history.path": str,
        "history.max-entries": int,
        # adaptive execution (epoch-versioned plan cache + runtime
        # join-strategy switching at the dynamic-filter build-summary
        # barrier): the master gate (false = bit-exact pre-adaptive;
        # seeds the adaptive_enabled session default) and the shared
        # divergence factor — relative change beyond which a learned /
        # observed cardinality contradicts the estimate a plan was
        # built on (bumps history epochs, triggers replans and
        # broadcast<->partitioned switches)
        "adaptive.enabled": bool,
        "adaptive.divergence-factor": float,
        # per-operator observability (exec/stats.OperatorStats): seeds
        # the enable_operator_stats session default
        "operator-stats.enabled": bool,
        # slow-query log: queries over the threshold append their
        # EXPLAIN ANALYZE text + plan fingerprint to the JSONL sidecar
        # (threshold absent/<=0 = off)
        "slow-query.threshold-ms": float,
        "slow-query.path": str,
        # seeds the session retry_policy default (NONE | TASK | QUERY)
        "retry-policy": str,
        # worker drain: how long a draining worker waits for running
        # tasks to finish and buffered output to be pulled/spooled
        # before exiting
        "drain.grace-s": float,
        # durable coordinator state (server.journal): directory of the
        # crash-safe admission journal; a restarted coordinator replays
        # it and re-admits every non-terminal query
        "coordinator.journal-path": str,
        # multi-coordinator control plane (server/lease.py): comma-
        # separated peer coordinator URIs. Set, the journal path
        # becomes a SHARED control directory — this coordinator
        # journals under <path>/<node.id>/, publishes a TTL'd lease
        # file carrying its admission/memory/QoS occupancy and open
        # statement ids, announces itself to every peer
        # (role=coordinator), and claims + resumes a dead peer's open
        # queries when that peer's lease expires (fencing epoch
        # prevents split-brain double-claims). Unset (the default) the
        # lease plane never constructs — single-coordinator deploys
        # are bit-exact pre-HA.
        "coordinator.peers": str,
        # lease TTL: a coordinator lease not renewed for this long is
        # expired and its journal claimable (renewal runs at TTL/3)
        "lease.ttl-s": float,
        # worker orphan-task reaper: tasks whose minting coordinator
        # incarnation (the qid boot nonce) has not heartbeated for
        # this long are DELETEd through the normal teardown path,
        # releasing their buffer-pool reservations. <=0 (the default)
        # disables the reaper — bit-exact pre-reaper behavior.
        "task.orphan-ttl-s": float,
        # elastic worker pool (server.pool): autoscaler bounds, control
        # cadence, and hysteresis (consecutive idle ticks before a
        # scale-down, cooldown after any scaling action)
        "pool.min-workers": int,
        "pool.max-workers": int,
        "pool.scale-interval-s": float,
        "pool.scale-down-ticks": int,
        "pool.cooldown-s": float,
        # preemptible capacity: marks this worker preemptible (announced
        # to discovery; gather/merge stages prefer stable nodes) and the
        # short drain grace a preemption notice gets
        "node.preemptible": bool,
        "pool.preempt-grace-s": float,
        # streaming ingest lane (server/ingest.py): directory of the
        # per-table crc32-framed WALs (unset = the lane never
        # constructs; legacy INSERT/CTAS bit-exact) and the commit-loop
        # cadence folding pending micro-batches into snapshots
        "ingest.wal-path": str,
        "ingest.commit-interval-ms": float,
        # durable lakehouse (server/manifests.py): root of the
        # manifest-committed table format (unset = no manifests, no
        # compaction thread; ingest commits stay WAL-only bit-exact),
        # the data-file size compaction targets, background-compaction
        # cadence and trigger threshold, and the orphan GC TTL (also
        # the time-travel retention window)
        "lakehouse.path": str,
        "lakehouse.target-file-bytes": str,
        "lakehouse.compaction.interval-s": float,
        "lakehouse.compaction.min-files": int,
        "lakehouse.orphan-ttl-s": float,
        # materialized views (exec/mview.py): the staleness bound the
        # read gate enforces over views of legacy-written bases, and
        # the master switch for incremental (delta-merge) maintenance
        # (false = every maintenance event is a full refresh)
        "mview.max-staleness-s": float,
        "mview.incremental-enabled": bool,
        # serving-plane result reuse (server/result_cache.py): the
        # master gate (false = bit-exact pre-cache), the LRU byte
        # budget charged to the MemoryPool's result-cache owner, the
        # bounded-stale serving window for invalidated entries, and
        # the MV-aware scan-rewrite gate
        "result-cache.enabled": bool,
        "result-cache.bytes": str,
        "result-cache.max-staleness-s": float,
        "mview.auto-rewrite": bool,
        # tail-latency QoS plane (server/qos.py): the master gate
        # (false = bit-exact legacy admission), the post-resume grace
        # during which a resumed query is immune to re-suspension, and
        # the lifetime suspension cap per query. Per-group keys
        # (qos.<group>.priority / qos.<group>.target-p99-ms) are
        # accepted dynamically — see _QOS_GROUP_KEY below
        "qos.enabled": bool,
        "qos.resume-grace-s": float,
        "qos.max-suspensions-per-query": int,
        # deterministic chaos: JSON FaultPlane spec (utils.faults)
        "fault-injection.spec": str,
        # device-plane telemetry (utils/telemetry.py): the master gate
        # for the dispatch/transfer/compile counters (false = zero
        # counter delta, bit-exact results either way), the cluster
        # sampler cadence (<=0 = sampler off — the default; when on,
        # the coordinator scrapes itself + every announced worker each
        # interval into the metrics_history ring), the ring-buffer row
        # bound, and the optional JSONL persistence path (journal
        # segment idiom, newest two segments kept)
        "telemetry.enabled": bool,
        "telemetry.sample-interval-s": float,
        "telemetry.retention": int,
        "telemetry.path": str,
    }

    #: dynamic per-group QoS keys: qos.<group>.priority (int) and
    #: qos.<group>.target-p99-ms (float) — group names are config
    #: data, so they cannot enumerate in KNOWN
    _QOS_GROUP_KEY = re.compile(
        r"^qos\.([A-Za-z0-9_\-]+)\.(priority|target-p99-ms)$"
    )

    def __init__(self, props: Optional[Dict[str, str]] = None):
        self.props: Dict[str, Any] = {}
        for k, v in (props or {}).items():
            t = self.KNOWN.get(k)
            if t is None:
                m = self._QOS_GROUP_KEY.match(k)
                if m is None:
                    raise KeyError(f"unknown config key: {k}")
                t = int if m.group(2) == "priority" else float
            self.props[k] = (
                v.lower() == "true" if t is bool and isinstance(v, str) else t(v)
            )

    def get(self, key: str, default=None):
        return self.props.get(key, default)
