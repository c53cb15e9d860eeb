"""Driver benchmark: TPC-H Q1 @ SF1 rows/sec on one chip.

Default mode prints ONE JSON line:
    {"metric", "value", "unit", "vs_baseline"}
``--all`` additionally benchmarks the other BASELINE.json configs
(Q3/Q5 @ SF10, window functions over orders) and prints one JSON line
per config — used to fill BASELINE.md's measured table; the driver
contract stays the single-line default.

Q1 (lineitem scan + filter + projection arithmetic + hash aggregate +
sort) is the `BASELINE.json` headline config. The timed region is
steady-state end-to-end plan execution — device program + host root
stage + result gather — with data generation, host→HBM staging, and
compilation amortized out by warmup, mirroring how the reference
separates scan setup from operator runtime in its benchmarks
(SURVEY.md §4.6).

``vs_baseline`` divides by ``CPU_BASELINE_ROWS_PER_SEC`` below (no
published reference numbers exist — SURVEY.md §6).

A run that cannot measure fails: a dead device probe raises, and a
skipped line makes the exit code non-zero. There is no CPU fallback —
every line names the backend it ran on.
"""

import json
import os
import sys
import time


def _analysis_clean():
    """True when the static-analysis gate (tools/analyze.py) is clean
    on this tree at measurement time — recorded on the report header
    line so a record carries the lint state of what was measured.
    None (json null) when the framework cannot run; never an error."""
    try:
        here = os.path.dirname(os.path.abspath(__file__))
        tools = os.path.join(here, "tools")
        if tools not in sys.path:
            sys.path.insert(0, tools)
        import analysis

        findings = analysis.run_passes(os.path.join(here, "presto_tpu"))
        return not any(f.active for f in findings)
    except Exception:
        return None

# Denominator of ``vs_baseline``: this engine, Q1@SF1, same protocol
# (warmup 1 + best of 5), on the XLA CPU backend of a 1-vCPU Intel Xeon
# @ 2.10GHz at commit d7c7ee0 (steady best 2.33 s). A CPU number kept
# only so the ratio stays comparable across old lines; it is not a
# device metric and no record of that run is kept in the tree. NOT
# comparable to BASELINE.json's 32-vCPU Presto-Java north star. ROADMAP
# S0 replaces this file and the constant with it.
CPU_BASELINE_ROWS_PER_SEC = 2_575_542

WARMUP = 1
ITERS = 5


#: previous device-plane snapshot — every emitted line carries the
#: delta spent since the line before it (measurements run sequentially
#: between emits, so the delta IS the measurement's device cost plus
#: its setup)
_DEV_SNAP = None

#: lines emitted as skips so far (the exit code reports them)
_SKIPPED = 0


def _device_delta() -> dict:
    """Device counters spent since the previous emitted line
    (utils/telemetry): dispatch count, compile time, and total
    host<->device transfer bytes."""
    global _DEV_SNAP
    from presto_tpu.utils.telemetry import device_snapshot

    snap = device_snapshot()
    prev = _DEV_SNAP or {}
    _DEV_SNAP = snap
    return {
        "dispatches": int(
            snap["dispatches"] - prev.get("dispatches", 0)
        ),
        "compile_ms": round(
            snap["compile_ms"] - prev.get("compile_ms", 0.0), 1
        ),
        "transfer_bytes": int(
            (snap["h2d_bytes"] + snap["d2h_bytes"])
            - (prev.get("h2d_bytes", 0) + prev.get("d2h_bytes", 0))
        ),
    }


def _emit(line: dict) -> None:
    """Print ONE result line, enforcing the skip contract at the last
    possible moment: a line carrying an
    ``error`` key must be a skip — no ``value`` at all — because a
    failed measurement printed as ``value: 0`` reads as a measured
    zero and poisons the metric trajectory. Every print site routes
    through here, so no future failure path can reintroduce the bug
    by hand-building its dict.

    Every line (skips included) is also stamped with the device-plane
    delta since the previous line and the boot probe's structured
    ``backend_diag`` — the backend that ran is on every metric, not
    just the headline. Skips are counted: :func:`main` exits non-zero
    when any line was skipped."""
    global _SKIPPED
    if "error" in line and not line.get("skipped"):
        line = {
            "metric": line.get("metric", "unknown"),
            "skipped": True,
            "unit": line.get("unit", "rows/s"),
            "error": str(line["error"])[:300],
        }
    if line.get("skipped"):
        _SKIPPED += 1
    if "device" not in line:
        line["device"] = _device_delta()
    if "backend_diag" not in line:
        from presto_tpu.utils.devicediag import last_diag_dict

        diag = last_diag_dict()
        if diag:
            line["backend_diag"] = {
                k: diag[k]
                for k in ("backend", "phase", "ok", "error_class")
                if k in diag
            }
    print(json.dumps(line), flush=True)


def skip_line(metric: str, exc: BaseException, unit: str = "rows/s") -> dict:
    """Result line for a config that could NOT be measured (backend
    init failure, config crash). A failed run once emitted ``value: 0``
    with the error beside it, and the zero poisoned the metric
    trajectory as if the engine measured 0 rows/s.
    A skipped config must carry NO value at all — just the skip flag
    and the error."""
    return {
        "metric": metric,
        "skipped": True,
        "unit": unit,
        "error": f"{type(exc).__name__}: {exc}"[:300],
    }


def _table_rows(runner, schema: str, table: str) -> int:
    """Driving-table cardinality from connector stats (the closed-form
    generator's counts differ slightly from upstream dbgen's, so rows/s
    must use the rows this engine actually scans)."""
    return _table_rows_cat(runner, "tpch", schema, table)


def _table_rows_cat(runner, catalog: str, schema: str, table: str) -> int:
    from presto_tpu.connectors.spi import TableHandle

    conn = runner.catalogs.get(catalog)
    st = conn.metadata().get_table_stats(
        TableHandle(catalog, schema, table)
    )
    return int(st.row_count)

_Q3 = """
select l_orderkey, sum(l_extendedprice * (1 - l_discount)) as revenue,
  o_orderdate, o_shippriority
from tpch.SCHEMA.customer, tpch.SCHEMA.orders, tpch.SCHEMA.lineitem
where c_mktsegment = 'BUILDING' and c_custkey = o_custkey
  and l_orderkey = o_orderkey and o_orderdate < date '1995-03-15'
  and l_shipdate > date '1995-03-15'
group by l_orderkey, o_orderdate, o_shippriority
order by revenue desc, o_orderdate
limit 10
"""

_Q5 = """
select n_name, sum(l_extendedprice * (1 - l_discount)) as revenue
from tpch.SCHEMA.customer, tpch.SCHEMA.orders, tpch.SCHEMA.lineitem,
  tpch.SCHEMA.supplier, tpch.SCHEMA.nation, tpch.SCHEMA.region
where c_custkey = o_custkey and l_orderkey = o_orderkey
  and l_suppkey = s_suppkey and c_nationkey = s_nationkey
  and s_nationkey = n_nationkey and n_regionkey = r_regionkey
  and r_name = 'ASIA' and o_orderdate >= date '1994-01-01'
  and o_orderdate < date '1995-01-01'
group by n_name
order by revenue desc
"""

_WINDOW = """
select o_orderkey, o_custkey,
  row_number() over (partition by o_custkey order by o_orderdate) as rn,
  rank() over (partition by o_orderpriority order by o_totalprice) as rk
from tpch.SCHEMA.orders
"""

# TPC-H Q17-style SELECTIVE star join (the dynamic-filtering headline
# shape): the tiny filtered part build prunes the lineitem probe before
# the join. Run with a small fragment budget so the stage-at-a-time
# executor builds the runtime filter; the emitted line reports
# dynamic_filter_rows_pruned alongside rows/s.
_Q17SEL = """
select sum(l_extendedprice) as total
from tpch.SCHEMA.lineitem, tpch.SCHEMA.part
where l_partkey = p_partkey
  and p_brand = 'Brand#23' and p_container = 'MED BOX'
"""

_Q18 = """
select c_name, c_custkey, o_orderkey, o_orderdate, o_totalprice,
  sum(l_quantity) as total_qty
from tpch.SCHEMA.customer, tpch.SCHEMA.orders, tpch.SCHEMA.lineitem
where o_orderkey in (
    select l_orderkey from tpch.SCHEMA.lineitem
    group by l_orderkey having sum(l_quantity) > 300)
  and c_custkey = o_custkey and o_orderkey = l_orderkey
group by c_name, c_custkey, o_orderkey, o_orderdate, o_totalprice
order by o_totalprice desc, o_orderdate
limit 100
"""


def _bench_query(
    runner, sql: str, driving_rows: int, expect_rows=None, iters=None
):
    from presto_tpu.plan.planner import plan_statement
    from presto_tpu.sql import parse_statement

    stmt = parse_statement(sql)
    plan = plan_statement(stmt, runner.catalogs, runner.session)
    result = None
    for _ in range(WARMUP + 1):
        result = runner.execute_plan(plan)
    if expect_rows is not None:
        n_out = len(result.rows())
        assert n_out == expect_rows, f"expected {expect_rows}, got {n_out}"
    times = []
    for _ in range(iters if iters is not None else ITERS):
        t0 = time.perf_counter()
        runner.execute_plan(plan)
        times.append(time.perf_counter() - t0)
    best = min(times)
    # n_runs = every plan execution above (warmup + verify + timed):
    # the source of truth for per-iteration counter-delta metrics
    return driving_rows / best, best, WARMUP + 1 + len(times)


def _serving_line(backend: str) -> dict:
    """Serving-latency line, extended for micro-batched serving
    (ROADMAP item 1): 100+ concurrent clients replay ONE point-lookup
    shape with fresh literals through PREPARE/EXECUTE against an
    in-process coordinator (the batch queue fronts coordinator
    dispatch), measured TWICE on the same backend — first with
    serving.microbatch-wait-ms=0 (unbatched: the PR 6 plan-cache
    path), then with the batch queue on — reporting batched vs
    unbatched warm QPS/p50/p99, the device-dispatch count
    (serving.batches), and mean batch occupancy. The contract of the
    batched round is dispatches STRICTLY fewer than statements served
    (mean occupancy > 1)."""
    import threading

    from presto_tpu.server.coordinator import CoordinatorServer
    from presto_tpu.utils.metrics import REGISTRY

    clients, per_client = 100, 5
    prepared = {
        "bench_serve": (
            "select c_name, c_acctbal, c_mktsegment "
            "from tpch.sf1.customer where c_custkey = ?"
        )
    }
    coord = CoordinatorServer(max_concurrent_queries=clients + 8)

    def run_round(wait_ms: float, seed: int) -> dict:
        coord.local.session.set("microbatch_wait_ms", wait_ms)
        lat: list = []
        errors: list = []
        lock = threading.Lock()
        barrier = threading.Barrier(clients)

        def one_client(ci: int) -> None:
            try:
                barrier.wait(60)
                for i in range(per_client):
                    # fresh literals, always within the key range
                    v = 1 + ((seed + ci * per_client + i) * 37) % (
                        nkeys - 1
                    )
                    t = time.perf_counter()
                    q = coord.submit(
                        f"execute bench_serve using {v}",
                        prepared=prepared,
                    )
                    q.done.wait(120)
                    dt = time.perf_counter() - t
                    with lock:
                        if q.state != "FINISHED":
                            errors.append(
                                RuntimeError(q.error or q.state)
                            )
                        else:
                            lat.append(dt)
            except Exception as e:  # report, don't hang
                with lock:
                    errors.append(e)

        threads = [
            threading.Thread(target=one_client, args=(ci,))
            for ci in range(clients)
        ]
        t0 = time.perf_counter()
        for t in threads:
            t.start()
        for t in threads:
            t.join()
        wall = time.perf_counter() - t0
        if errors:
            raise errors[0]
        lat.sort()
        return {
            "qps": len(lat) / wall,
            "p50": lat[len(lat) // 2],
            "p99": lat[min(len(lat) - 1, int(len(lat) * 0.99))],
            "queries": len(lat),
        }

    try:
        nkeys = _table_rows(coord.local, "sf1", "customer")
        # cold: plan + XLA compile + staging, once
        t0 = time.perf_counter()
        q = coord.submit("execute bench_serve using 7", prepared=prepared)
        q.done.wait(600)
        if q.state != "FINISHED":
            raise RuntimeError(q.error or q.state)
        cold_s = time.perf_counter() - t0
        unbatched = run_round(0.0, seed=0)
        # batched warmup round: pay the per-lane-bucket vmap compiles
        # outside the timed window (a warm batch compiles nothing)
        coord.local.session.set("microbatch_max", 32)
        run_round(10.0, seed=1 << 16)
        b0 = int(REGISTRY.counter("serving.batches").total)
        s0 = int(REGISTRY.counter("serving.batched_statements").total)
        occ0 = REGISTRY.distribution("serving.batch_occupancy").values()
        hits0 = int(REGISTRY.counter("plan.cache_hit").total)
        batched = run_round(10.0, seed=1 << 17)
        batches = int(REGISTRY.counter("serving.batches").total) - b0
        stmts = (
            int(REGISTRY.counter("serving.batched_statements").total)
            - s0
        )
        occ1 = REGISTRY.distribution("serving.batch_occupancy").values()
        d_count = occ1["count"] - occ0["count"]
        occupancy = (
            (occ1["sum"] - occ0["sum"]) / d_count if d_count else 0.0
        )
        plan_hits = (
            int(REGISTRY.counter("plan.cache_hit").total) - hits0
        )
    finally:
        coord.shutdown()
    return {
        "metric": "serving_point_lookup_sf1_qps",
        "value": round(batched["qps"], 2),
        "unit": "queries/s",
        "clients": clients,
        "queries": batched["queries"],
        "p50_ms": round(batched["p50"] * 1000.0, 2),
        "p99_ms": round(batched["p99"] * 1000.0, 2),
        "unbatched_qps": round(unbatched["qps"], 2),
        "unbatched_p50_ms": round(unbatched["p50"] * 1000.0, 2),
        "unbatched_p99_ms": round(unbatched["p99"] * 1000.0, 2),
        "cold_ms": round(cold_s * 1000.0, 1),
        # the micro-batch contract: one device dispatch answers many
        # statements — dispatches strictly fewer than statements
        "batches": batches,
        "batched_statements": stmts,
        "mean_batch_occupancy": round(occupancy, 2),
        "batched_beats_unbatched": bool(
            batched["qps"] > unbatched["qps"]
        ),
        "plan_cache_hits": plan_hits,
        "backend": backend,
    }


def _serving_repeat_line(backend: str) -> list:
    """Repeated-query serving mix (the result-reuse tier, ROADMAP
    item 3): the ``serving_point_lookup_sf1_qps`` harness replayed
    with a HOT fingerprint set — repeated statements repeat their
    literal VALUES too, because the result-cache key is the canonical
    fingerprint × the literal vector. Three rounds on one backend:

    - uncached: result cache OFF, pure hot set, sequential client
      (plan cache warm, micro-batch lane on — the honest pre-reuse
      per-statement serving cost);
    - cached: result cache ON, same hot set, same sequential client,
      after one populating pass — the contract round (≥10× the
      uncached qps, hits > 0, ZERO device dispatches: asserted via
      telemetry deltas). The tier rounds run SEQUENTIALLY because
      the contract is the per-statement serving cost: a hit is pure
      Python, so a 100-thread GIL scrum measures context switching,
      not the cache — while concurrency actively HELPS the uncached
      round (the microbatch lane amortizes its dispatches), which
      would understate the tier honestly measured per statement;
    - mixed: the dashboard-shaped 80/20 mix (80% hot fingerprints
      over a stable snapshot, 20% fresh literals) under 16
      concurrent clients, reported beside the tiers (Amdahl + the
      GIL cap the mixed speedup; the tier contract is measured on
      the pure repeated set).

    Returns TWO metric lines: the cached-tier qps and the hit count
    (its own line so the regress gate flags a cache that silently
    stopped hitting)."""
    import threading

    from presto_tpu.server.coordinator import CoordinatorServer
    from presto_tpu.utils.metrics import REGISTRY
    from presto_tpu.utils.telemetry import device_snapshot

    n_hot, mixed_clients = 8, 16
    prepared = {
        "bench_serve_rc": (
            "select c_name, c_acctbal, c_mktsegment "
            "from tpch.sf1.customer where c_custkey = ?"
        )
    }
    coord = CoordinatorServer(max_concurrent_queries=mixed_clients + 8)

    def run_round(seed: int, hot_frac: float, clients: int,
                  per_client: int) -> dict:
        lat: list = []
        errors: list = []
        lock = threading.Lock()
        barrier = threading.Barrier(clients)

        def one_client(ci: int) -> None:
            try:
                barrier.wait(60)
                for i in range(per_client):
                    n = ci * per_client + i
                    if (n % 100) < hot_frac * 100:
                        # hot set: same fingerprint, same literal
                        v = 1 + (n % n_hot)
                    else:
                        v = 1 + ((seed + n) * 37) % (nkeys - 1)
                    t = time.perf_counter()
                    q = coord.submit(
                        f"execute bench_serve_rc using {v}",
                        prepared=prepared,
                    )
                    q.done.wait(120)
                    dt = time.perf_counter() - t
                    with lock:
                        if q.state != "FINISHED":
                            errors.append(
                                RuntimeError(q.error or q.state)
                            )
                        else:
                            lat.append(dt)
            except Exception as e:  # report, don't hang
                with lock:
                    errors.append(e)

        threads = [
            threading.Thread(target=one_client, args=(ci,))
            for ci in range(clients)
        ]
        t0 = time.perf_counter()
        for t in threads:
            t.start()
        for t in threads:
            t.join()
        wall = time.perf_counter() - t0
        if errors:
            raise errors[0]
        lat.sort()
        return {
            "qps": len(lat) / wall,
            "p50": lat[len(lat) // 2],
            "queries": len(lat),
        }

    try:
        nkeys = _table_rows(coord.local, "sf1", "customer")
        coord.local.session.set("microbatch_wait_ms", 4.0)
        coord.local.session.set("microbatch_max", 32)
        # cold: plan + XLA compile + staging + the vmap lane buckets
        q = coord.submit(
            "execute bench_serve_rc using 7", prepared=prepared
        )
        q.done.wait(600)
        if q.state != "FINISHED":
            raise RuntimeError(q.error or q.state)
        run_round(1 << 16, 1.0, 1, 40)  # warm every lane bucket
        coord.local.session.set("enable_result_cache", False)
        uncached = run_round(0, 1.0, 1, 120)
        coord.local.session.set("enable_result_cache", True)
        run_round(1, 1.0, 1, 40)  # populate: misses + stores
        h0 = int(REGISTRY.counter("result_cache.hits").total)
        d0 = device_snapshot()["dispatches"]
        cached = run_round(2, 1.0, 1, 200)
        hits = int(REGISTRY.counter("result_cache.hits").total) - h0
        hit_dispatches = int(
            device_snapshot()["dispatches"] - d0
        )
        mixed = run_round(3, 0.8, mixed_clients, 25)
    finally:
        coord.shutdown()
    speedup = (
        cached["qps"] / uncached["qps"] if uncached["qps"] else 0.0
    )
    line = {
        "metric": "serving_repeated_cached_qps",
        "value": round(cached["qps"], 2),
        "unit": "queries/s",
        "queries": cached["queries"],
        "p50_ms": round(cached["p50"] * 1000.0, 2),
        "uncached_qps": round(uncached["qps"], 2),
        "uncached_p50_ms": round(uncached["p50"] * 1000.0, 2),
        "cached_speedup_x": round(speedup, 2),
        "mixed_80_20_qps": round(mixed["qps"], 2),
        "mixed_clients": mixed_clients,
        "hot_fingerprints": n_hot,
        # the reuse-tier contract: ≥10× the uncached tier, hits > 0,
        # and ZERO device dispatches across the all-hit round
        "result_cache_hits": hits,
        "hit_round_dispatches": hit_dispatches,
        "cached_10x_ok": bool(speedup >= 10.0),
        "backend": backend,
    }
    hits_line = {
        "metric": "serving_repeated_result_cache_hits",
        "value": hits,
        "unit": "hits",
        "backend": backend,
    }
    return [line, hits_line]


def _elasticity_line(backend: str) -> dict:
    """Elasticity measurement (ROADMAP item 3 / the elastic-pool PR):
    queries completed during a scripted POOL-HALVING window. An
    in-process 4-worker cluster under retry_policy=TASK serves
    concurrent clients while half the pool drains mid-window and fresh
    capacity replaces it — the line reports throughput across the
    disruption and the failure count, whose contract is ZERO (the drain
    protocol + spool recovery make shrink lossless). Backend-tagged
    like every other line; failures to even run the cluster emit a
    ``skipped`` line, never a fake zero."""
    import tempfile
    import threading

    from presto_tpu.server import (
        CoordinatorServer,
        PrestoTpuClient,
        WorkerServer,
    )
    from presto_tpu.session import NodeConfig

    window_s = 4.0
    sql = "select count(*) as c from tpch.tiny.orders"
    with tempfile.TemporaryDirectory() as td:
        cfg = NodeConfig(
            {
                "exchange.spool-path": td + "/spool",
                "retry-policy": "TASK",
            }
        )
        coord = CoordinatorServer(config=cfg).start()
        workers = [
            WorkerServer(coordinator_uri=coord.uri, config=cfg).start()
            for _ in range(4)
        ]
        try:
            deadline = time.monotonic() + 15
            while (
                time.monotonic() < deadline
                and len(coord.active_workers()) < 4
            ):
                time.sleep(0.05)
            expected = [tuple(r) for r in coord.local.execute(sql).rows()]
            done = {"completed": 0, "failed": 0}
            lock = threading.Lock()
            stop = time.monotonic() + window_s

            def client_loop():
                client = PrestoTpuClient(coord.uri, timeout_s=60)
                while time.monotonic() < stop:
                    try:
                        rows = [tuple(r) for r in client.execute(sql).rows()]
                        ok = rows == expected
                    except Exception:
                        ok = False
                    with lock:
                        done["completed" if ok else "failed"] += 1

            threads = [
                threading.Thread(target=client_loop) for _ in range(4)
            ]
            t0 = time.monotonic()
            for t in threads:
                t.start()
            # the scripted halving: drain 2 of 4 mid-window, restore
            time.sleep(window_s * 0.25)
            from presto_tpu.server import rpc as _rpc

            for w in workers[:2]:
                _rpc.call_json("PUT", w.uri + "/v1/state/drain")
            time.sleep(window_s * 0.35)
            workers += [
                WorkerServer(
                    coordinator_uri=coord.uri, config=cfg
                ).start()
                for _ in range(2)
            ]
            for t in threads:
                t.join(120)
            wall = time.monotonic() - t0
        finally:
            for w in workers:
                w.shutdown(graceful=False)
            coord.shutdown()
    return {
        "metric": "elastic_pool_halving_queries_completed",
        "value": done["completed"],
        "unit": "queries",
        "window_s": round(wall, 2),
        "qps": round(done["completed"] / max(wall, 1e-9), 2),
        "failed": done["failed"],
        "clients": 4,
        "workers": "4 -> 2 -> 4 (drain protocol)",
        "backend": backend,
    }


def _memory_pressure_line(backend: str) -> dict:
    """Memory-governance measurement (the cluster-memory PR): a
    concurrent over-budget query mix on a deliberately capped per-node
    budget, under the arbiter + low-memory killer + host-spill lane.
    The line reports completed/killed/spilled_bytes with the contract
    ``completed + killed == submitted`` and ZERO wedged queries — over-
    capacity work either finishes (spill/degrade) or dies loudly with
    MEMORY_PRESSURE; nothing hangs. Backend-tagged; a cluster that
    cannot boot emits a ``skipped`` line, never a fake zero."""
    import threading

    from presto_tpu.server import (
        CoordinatorServer,
        PrestoTpuClient,
        WorkerServer,
    )
    from presto_tpu.server.client import QueryFailed
    from presto_tpu.session import NodeConfig
    from presto_tpu.utils.metrics import REGISTRY

    spilled0 = int(REGISTRY.counter("spill.bytes_spilled").total)
    cfg = NodeConfig(
        {
            "memory.governance-enabled": "true",
            "memory.blocked-timeout-s": "0.3",
            "memory.reserve-block-max-s": "15",
            "memory.host-spill-bytes": "64MB",
            "announcement.interval-s": "0.1",
            "staging.cache-bytes": "49152",
            "query.max-memory-per-node": "49152",
        }
    )
    hungry = "select sum(l_quantity) s from tpch.tiny.lineitem"
    small = "select count(*) c from tpch.tiny.region"
    coord = CoordinatorServer(config=cfg).start()
    workers = [
        WorkerServer(coordinator_uri=coord.uri, config=cfg).start()
        for _ in range(2)
    ]
    try:
        deadline = time.monotonic() + 15
        while (
            time.monotonic() < deadline
            and len(coord.active_workers()) < 2
        ):
            time.sleep(0.05)
        expected = [
            tuple(r) for r in coord.local.execute(small).rows()
        ]
        mix = [hungry, small, small, hungry, small, small] * 2
        out = {"completed": 0, "killed": 0, "wedged": 0}
        lock = threading.Lock()

        def one(sql):
            client = PrestoTpuClient(coord.uri, timeout_s=60)
            try:
                rows = [tuple(r) for r in client.execute(sql).rows()]
                ok = sql == hungry or rows == expected
                key = "completed" if ok else "wedged"
            except QueryFailed as e:
                key = (
                    "killed"
                    if "MEMORY_PRESSURE" in str(e)
                    else "wedged"
                )
            except Exception:
                key = "wedged"
            with lock:
                out[key] += 1

        threads = [
            threading.Thread(target=one, args=(sql,)) for sql in mix
        ]
        t0 = time.monotonic()
        for t in threads:
            t.start()
        for t in threads:
            t.join(90)
        wall = time.monotonic() - t0
    finally:
        for w in workers:
            w.shutdown(graceful=False)
        coord.shutdown()
    return {
        "metric": "memory_pressure_survivors",
        "value": out["completed"],
        "unit": "queries",
        "submitted": len(mix),
        "killed": out["killed"],
        "wedged": out["wedged"],
        "contract_ok": (
            out["completed"] + out["killed"] == len(mix)
            and out["wedged"] == 0
        ),
        "spilled_bytes": int(
            REGISTRY.counter("spill.bytes_spilled").total
        )
        - spilled0,
        "window_s": round(wall, 2),
        "backend": backend,
    }


def _streaming_ingest_line(backend: str) -> dict:
    """Streaming ingest + incremental materialized views (ROADMAP
    item 4 / the ingest-lane PR): a writer thread streams row
    micro-batches through ``POST /v1/ingest/{table}`` while 8
    concurrent clients point-read an incrementally-maintained SUM/COUNT
    view through the coordinator (plan cache + micro-batch queue in
    front). Reports sustained ingest rows/s, read p50/p99, and the
    maintenance counters, with the contract ``full_recomputes == 0``
    after warmup — every measured-window refresh is a delta merge,
    never a recompute. Backend-tagged; boot failures emit a skipped
    line, never a fake zero."""
    import json as _json
    import tempfile
    import threading
    import urllib.request

    from presto_tpu.connectors import create_connector
    from presto_tpu.server.coordinator import CoordinatorServer
    from presto_tpu.session import NodeConfig
    from presto_tpu.utils.metrics import REGISTRY

    clients, window_s, batch_rows, n_keys = 8, 4.0, 200, 64
    with tempfile.TemporaryDirectory() as td:
        cfg = NodeConfig(
            {
                "ingest.wal-path": td,
                "ingest.commit-interval-ms": "25",
                "mview.incremental-enabled": "true",
                "serving.microbatch-wait-ms": "4",
            }
        )
        coord = CoordinatorServer(
            config=cfg, max_concurrent_queries=clients + 8
        ).start()
        try:
            coord.local.catalogs.register(
                "mem", create_connector("memory")
            )
            coord.local.execute(
                "create table mem.default.events "
                "(k bigint, v bigint)"
            )
            coord.local.execute(
                "create materialized view mem.default.dash as "
                "select k, sum(v) as sv, count(*) as c "
                "from mem.default.events group by k"
            )
            uri = coord.uri + "/v1/ingest/mem.default.events"

            def post_batch(i: int, commit=False):
                body = {
                    "columns": {
                        "k": [
                            (i * batch_rows + j) % n_keys
                            for j in range(batch_rows)
                        ],
                        "v": [1] * batch_rows,
                    }
                }
                if commit:
                    body["commit"] = True
                req = urllib.request.Request(
                    uri, data=_json.dumps(body).encode()
                )
                urllib.request.urlopen(req, timeout=60).read()

            prepared = {
                "dash_read": (
                    "select sv, c from mem.default.dash where k = ?"
                )
            }
            # warmup: seed every group, pay the XLA compiles of the
            # ingest delta plane AND the read path outside the window
            post_batch(0, commit=True)
            q = coord.submit(
                "execute dash_read using 7", prepared=prepared
            )
            q.done.wait(600)
            if q.state != "FINISHED":
                raise RuntimeError(q.error or q.state)
            inc0 = int(
                REGISTRY.counter("mview.incremental_refreshes").total
            )
            ref0 = int(REGISTRY.counter("mview.refreshes").total)
            rows0 = int(REGISTRY.counter("ingest.rows").total)
            stop = time.monotonic() + window_s
            ingested = {"batches": 0}
            lat: list = []
            errors: list = []
            lock = threading.Lock()

            def writer():
                i = 1
                try:
                    while time.monotonic() < stop:
                        post_batch(i)
                        i += 1
                        with lock:
                            ingested["batches"] += 1
                except Exception as e:
                    with lock:
                        errors.append(e)

            def reader(ci: int):
                j = 0
                try:
                    while time.monotonic() < stop:
                        j += 1
                        key = (ci * 131 + j * 17) % n_keys
                        t0 = time.perf_counter()
                        qq = coord.submit(
                            f"execute dash_read using {key}",
                            prepared=prepared,
                        )
                        qq.done.wait(120)
                        dt = time.perf_counter() - t0
                        with lock:
                            if qq.state != "FINISHED":
                                errors.append(
                                    RuntimeError(qq.error or qq.state)
                                )
                            else:
                                lat.append(dt)
                except Exception as e:
                    with lock:
                        errors.append(e)

            threads = [threading.Thread(target=writer)] + [
                threading.Thread(target=reader, args=(ci,))
                for ci in range(clients)
            ]
            t0 = time.monotonic()
            for t in threads:
                t.start()
            for t in threads:
                t.join(120)
            wall = time.monotonic() - t0
            if errors:
                raise errors[0]
            # drain the tail so the counters settle
            coord.ingest.flush()
            inc = (
                int(
                    REGISTRY.counter(
                        "mview.incremental_refreshes"
                    ).total
                )
                - inc0
            )
            ref = int(REGISTRY.counter("mview.refreshes").total) - ref0
            ing_rows = (
                int(REGISTRY.counter("ingest.rows").total) - rows0
            )
            lat.sort()
        finally:
            coord.shutdown()
    return {
        "metric": "streaming_ingest_mview_qps",
        "value": round(ing_rows / wall, 1),
        "unit": "rows/s",
        "window_s": round(wall, 2),
        "ingest_batches": ingested["batches"],
        "read_clients": clients,
        "reads": len(lat),
        "read_qps": round(len(lat) / wall, 2),
        "read_p50_ms": round(
            lat[len(lat) // 2] * 1000.0, 2
        ) if lat else None,
        "read_p99_ms": round(
            lat[min(len(lat) - 1, int(len(lat) * 0.99))] * 1000.0, 2
        ) if lat else None,
        "incremental_refreshes": inc,
        # the contract: after warmup, maintenance is ALL delta merges
        "full_recomputes": ref - inc,
        "contract_ok": (ref - inc) == 0 and inc > 0,
        "backend": backend,
    }


def _lakehouse_restart_recovery_line(backend: str) -> dict:
    """Durable lakehouse ingest with a scripted bounce mid-commit
    (the crash-safe manifest PR): a producer streams acked
    micro-batches through the ingest lane with the lakehouse tee on;
    mid-window the publish is killed at the ``_current`` pointer swap
    (the worst of the three pipeline points — data files and manifest
    already landed) and the coordinator-side manager is abandoned,
    then a FRESH incarnation over the same WAL + lakehouse dirs
    restores from the manifest tip and replays the acked tail.
    Reports sustained ingest rows/s, the recovery wall, and the
    contract ``acked_batches_lost == 0`` — every batch acked before
    the kill is readable after it. Backend-tagged; boot failures emit
    a skipped line, never a fake zero."""
    import tempfile

    from presto_tpu import types as T
    from presto_tpu.connectors import create_connector
    from presto_tpu.connectors.spi import TableHandle
    from presto_tpu.exec.local_runner import LocalQueryRunner
    from presto_tpu.exec.staging import CatalogManager
    from presto_tpu.server.ingest import IngestManager
    from presto_tpu.utils import faults

    batch_rows, window_s = 200, 2.0

    def boot(wal: str, lake: str):
        catalogs = CatalogManager()
        mem = create_connector("memory")
        catalogs.register("mem", mem)
        runner = LocalQueryRunner(catalogs=catalogs)
        ing = IngestManager(
            runner, wal, start_thread=False, lakehouse_path=lake
        )
        return runner, mem, ing

    def table_rows(runner) -> int:
        return runner.execute(
            "select count(*) from mem.default.events"
        ).rows()[0][0]

    with tempfile.TemporaryDirectory() as td:
        wal, lake = td + "/wal", td + "/lake"
        runner, mem, ing = boot(wal, lake)
        mem.create_table(
            TableHandle("mem", "default", "events"),
            {"k": T.BIGINT, "v": T.BIGINT},
        )
        acked = 0  # rows whose append() returned before the kill
        i = 0
        t0 = time.monotonic()
        stop = t0 + window_s
        while time.monotonic() < stop:
            ing.append(
                "mem.default.events",
                columns={
                    "k": [
                        (i * batch_rows + j) % 64
                        for j in range(batch_rows)
                    ],
                    "v": [1] * batch_rows,
                },
            )
            acked += batch_rows
            i += 1
            if i % 4 == 0:
                ing.flush()
        wall = time.monotonic() - t0
        # the scripted bounce: kill the publish at the pointer swap,
        # then abandon this incarnation without another flush
        faults.configure(
            {"rules": [
                {"action": "io_error", "path": "_current", "count": 1}
            ]}
        )
        try:
            ing.append(
                "mem.default.events",
                columns={"k": [0], "v": [1]},
            )
            acked += 1
            ing.flush()
        finally:
            faults.configure(None)
        ing.close(final_flush=False)

        t1 = time.monotonic()
        runner2, _mem2, ing2 = boot(wal, lake)  # restore + replay
        recovery_s = time.monotonic() - t1
        ing2.flush()  # commit the replayed acked tail
        recovered = table_rows(runner2)
        stats = ing2.stats()
        ing2.close(final_flush=False)
    return {
        "metric": "lakehouse_restart_recovery",
        "value": round(acked / wall, 1),
        "unit": "rows/s",
        "window_s": round(wall, 2),
        "acked_rows": acked,
        "recovered_rows": recovered,
        # THE contract: every row acked before the kill — including
        # the batch whose publish died at the pointer swap — is
        # readable after recovery
        "acked_batches_lost": max(acked - recovered, 0),
        "recovery_ms": round(recovery_s * 1000.0, 1),
        "replayed_batches": stats.get("replayed", 0),
        "contract_ok": recovered == acked,
        "backend": backend,
    }


def _qos_line(backend: str) -> dict:
    """Tail-latency QoS measurement (the QoS-plane PR): interactive
    point-lookup p99 WITH a concurrent analytic scan load in the same
    cluster, qos-on vs qos-off, against the idle (no-load) p99. The
    QoS plane's promise is that priority lanes + preempt-and-resume
    hold interactive latency while batch work shares the cluster:
    contract ``qos-on p99 <= 2x idle p99`` (the qos-off number is
    reported beside it to show the degradation the plane removes).
    Backend-tagged; a cluster that cannot boot emits a ``skipped``
    line, never a fake zero."""
    import tempfile
    import threading

    from presto_tpu.server import CoordinatorServer, WorkerServer
    from presto_tpu.session import NodeConfig

    lookups = 24
    lookup_sql = (
        "select c_name from tpch.tiny.customer where c_custkey = 7"
    )
    scan_sql = (
        "select l_returnflag, sum(l_quantity) as q, "
        "sum(l_extendedprice) as p from tpch.tiny.lineitem "
        "group by l_returnflag"
    )
    groups = {
        "rootGroups": [
            {
                "name": "interactive",
                "weight": 1,
                "hardConcurrencyLimit": 4,
                "priority": 10,
            },
            {
                "name": "batch",
                "weight": 1,
                "hardConcurrencyLimit": 4,
                "priority": 0,
            },
        ],
        "selectors": [{"user": "inter-.*", "group": "interactive"}],
        "defaultGroup": "batch",
    }

    def boot(td: str, qos_on: bool):
        cfg = {"exchange.spool-path": td + "/spool", "retry-policy": "TASK"}
        if qos_on:
            cfg.update(
                {
                    "qos.enabled": "true",
                    "qos.resume-grace-s": "0.1",
                    "qos.interactive.target-p99-ms": "500",
                }
            )
        node = NodeConfig(cfg)
        coord = CoordinatorServer(
            config=node,
            max_concurrent_queries=2,
            resource_groups=groups,
        ).start()
        workers = []
        try:
            for _ in range(2):
                workers.append(
                    WorkerServer(
                        coordinator_uri=coord.uri, config=node
                    ).start()
                )
            deadline = time.monotonic() + 15
            while (
                time.monotonic() < deadline
                and len(coord.active_workers()) < 2
            ):
                time.sleep(0.05)
        except BaseException:
            # a half-booted cluster must not outlive the skip line
            for w in workers:
                w.shutdown(graceful=False)
            coord.shutdown()
            raise
        return coord, workers

    def measure(coord, with_load: bool):
        stop = threading.Event()

        def load_loop():
            while not stop.is_set():
                q = coord.submit(scan_sql, user="batch-1")
                q.done.wait(60)

        loaders = (
            [threading.Thread(target=load_loop) for _ in range(2)]
            if with_load
            else []
        )
        for t in loaders:
            t.start()
        if with_load:
            time.sleep(0.5)  # let the scan load occupy the cluster
        lat = []
        try:
            for _ in range(lookups):
                t0 = time.monotonic()
                q = coord.submit(lookup_sql, user="inter-1")
                q.done.wait(60)
                if q.state == "FINISHED":
                    lat.append((time.monotonic() - t0) * 1000.0)
        finally:
            stop.set()
            for t in loaders:
                t.join(120)
        lat.sort()
        if not lat:
            raise RuntimeError("no interactive lookups completed")
        return (
            lat[len(lat) // 2],
            lat[min(len(lat) - 1, int(0.99 * len(lat)))],
        )

    def run_cluster(qos_on: bool, with_idle: bool):
        with tempfile.TemporaryDirectory() as td:
            coord, workers = boot(td, qos_on)
            try:
                idle = measure(coord, with_load=False) if with_idle else None
                loaded = measure(coord, with_load=True)
                susp = (
                    int(
                        sum(
                            r["suspensions"]
                            for r in coord.qos.view_rows()
                        )
                    )
                    if coord.qos is not None
                    else 0
                )
                return idle, loaded, susp
            finally:
                for w in workers:
                    w.shutdown(graceful=False)
                coord.shutdown()

    idle, on_loaded, suspensions = run_cluster(qos_on=True, with_idle=True)
    _, off_loaded, _ = run_cluster(qos_on=False, with_idle=False)
    return {
        "metric": "qos_interactive_p99_under_scan",
        "value": round(on_loaded[1], 1),
        "unit": "ms",
        "idle_p50_ms": round(idle[0], 1),
        "idle_p99_ms": round(idle[1], 1),
        "qos_on_p50_ms": round(on_loaded[0], 1),
        "qos_on_p99_ms": round(on_loaded[1], 1),
        "qos_off_p99_ms": round(off_loaded[1], 1),
        "suspensions": suspensions,
        "lookups": lookups,
        "contract_ok": on_loaded[1] <= 2.0 * idle[1],
        "backend": backend,
    }


def _partitioned_join_line(backend: str) -> dict:
    """ICI-native collective shuffle (the exchange-plane PR): wall-
    clock of a hash-partitioned TPC-H join + aggregation across
    in-process workers, ICI shuffle vs HTTP shuffle on the SAME
    backend. The ICI window must move ZERO bytes through the
    pages_wire shuffle (``exchange.http_shuffle_bytes`` flat) while
    ``exchange.ici_bytes_elided`` grows — the win is asserted from
    counters, not claimed. The single-program PR adds a third window
    (``exchange.single-program=false`` = the per-source-gather ICI
    path) and the device-plane contract ``fewer_dispatches_ok``:
    one collective program per stage must cost strictly fewer
    ``device.dispatches`` than a gather pass per source. Reuses the
    PR 11 backend discipline: the caller probed the backend
    (``_probe_backend``) and a cluster that cannot boot emits
    ``skip_line`` — never value 0."""
    import time as _time

    import jax

    from presto_tpu.server import (
        CoordinatorServer,
        PrestoTpuClient,
        WorkerServer,
    )
    from presto_tpu.session import NodeConfig
    from presto_tpu.utils.metrics import REGISTRY

    sql = (
        "select o_orderpriority, count(*) as n, "
        "sum(l_extendedprice) as v "
        "from tpch.tiny.orders, tpch.tiny.lineitem "
        "where o_orderkey = l_orderkey "
        "group by o_orderpriority order by o_orderpriority"
    )
    iters = 3
    n_workers = 4

    def run_cluster(ici_on: bool, single_program: bool = True):
        cfg = {
            "exchange.ici-enabled": "true" if ici_on else "false",
            "exchange.single-program": (
                "true" if single_program else "false"
            ),
        }
        coord = CoordinatorServer(config=NodeConfig(dict(cfg))).start()
        workers = []
        try:
            for _ in range(n_workers):
                workers.append(
                    WorkerServer(
                        coordinator_uri=coord.uri,
                        config=NodeConfig(dict(cfg)),
                    ).start()
                )
            deadline = _time.monotonic() + 15
            while (
                _time.monotonic() < deadline
                and len(coord.active_workers()) < n_workers
            ):
                _time.sleep(0.05)
            if len(coord.active_workers()) < n_workers:
                raise RuntimeError("workers not discovered")
            client = PrestoTpuClient(coord.uri, timeout_s=600)
            client.execute(
                "set session join_distribution_type = PARTITIONED"
            )
            rows = [tuple(r) for r in client.execute(sql).rows()]
            times = []
            for _ in range(iters):
                t0 = _time.perf_counter()
                client.execute(sql).rows()
                times.append(_time.perf_counter() - t0)
            return rows, min(times)
        finally:
            for w in workers:
                w.shutdown(graceful=False)
            coord.shutdown()

    from presto_tpu.utils.telemetry import device_snapshot

    http0 = REGISTRY.counter("exchange.http_shuffle_bytes").total
    dev0 = device_snapshot()
    rows_http, http_s = run_cluster(False)
    dev1 = device_snapshot()
    http_during_off = (
        REGISTRY.counter("exchange.http_shuffle_bytes").total - http0
    )
    # per-source-gather ICI window (exchange.single-program=false =
    # the pre-single-program per-source ici_fetch path) — the
    # dispatch baseline the collective program must beat
    psrc0 = device_snapshot()
    rows_psrc, psrc_s = run_cluster(True, single_program=False)
    psrc1 = device_snapshot()
    http1 = REGISTRY.counter("exchange.http_shuffle_bytes").total
    elided0 = REGISTRY.counter("exchange.ici_bytes_elided").total
    edges0 = REGISTRY.counter("exchange.ici_edges").total
    collective0 = REGISTRY.counter("exchange.collective_stages").total
    rows_ici, ici_s = run_cluster(True)
    dev2 = device_snapshot()
    http_during_ici = (
        REGISTRY.counter("exchange.http_shuffle_bytes").total - http1
    )
    elided = (
        REGISTRY.counter("exchange.ici_bytes_elided").total - elided0
    )
    edges = REGISTRY.counter("exchange.ici_edges").total - edges0
    collective = (
        REGISTRY.counter("exchange.collective_stages").total
        - collective0
    )
    # per-mode device-plane deltas (utils/telemetry.py): the single-
    # program contract is FEWER dispatches per query than the
    # per-source-gather ICI path it replaces — one collective program
    # per stage instead of a gather pass per source. The HTTP window's
    # dispatch delta is reported for visibility but is NOT the bar:
    # HTTP exchanges host-side (serialize/wire/deserialize), so its
    # device-dispatch count is low by construction; the device plane
    # only competes against itself.
    http_disp = int(dev1["dispatches"] - dev0["dispatches"])
    psrc_disp = int(psrc1["dispatches"] - psrc0["dispatches"])
    ici_disp = int(dev2["dispatches"] - psrc1["dispatches"])
    http_h2d = int(dev1["h2d_bytes"] - dev0["h2d_bytes"])
    psrc_h2d = int(psrc1["h2d_bytes"] - psrc0["h2d_bytes"])
    ici_h2d = int(dev2["h2d_bytes"] - psrc1["h2d_bytes"])
    return {
        "metric": "partitioned_join_shuffle_8dev",
        "value": round(ici_s, 4),
        "unit": "s",
        "ici_wall_s": round(ici_s, 4),
        "per_source_wall_s": round(psrc_s, 4),
        "http_wall_s": round(http_s, 4),
        "speedup": round(http_s / ici_s, 3) if ici_s > 0 else None,
        "ici_beats_http": ici_s < http_s,
        "ici_bytes_elided": int(elided),
        "ici_edges": int(edges),
        "collective_stages": int(collective),
        "ici_dispatches": ici_disp,
        "per_source_dispatches": psrc_disp,
        "http_dispatches": http_disp,
        "ici_h2d_bytes": ici_h2d,
        "per_source_h2d_bytes": psrc_h2d,
        "http_h2d_bytes": http_h2d,
        "fewer_dispatches_ok": ici_disp < psrc_disp,
        "http_shuffle_bytes_during_ici": int(http_during_ici),
        "http_shuffle_bytes_during_http": int(http_during_off),
        "zero_wire_bytes_ok": elided > 0 and http_during_ici == 0,
        "results_equal": rows_http == rows_ici == rows_psrc,
        "workers": n_workers,
        "n_devices": len(jax.devices()),
        "backend": backend,
    }


def _adaptive_line(backend: str) -> dict:
    """Adaptive execution (the epoch-versioned-replanning PR): a
    skewed sf1 join whose COLD estimate is wrong by >=10x — every row
    of a memory-connector build table (derived from sf1 customer)
    shares one key, so the classic ``k = 7 and v > -1e6`` selectivity
    math (0.1 x 0.33 without column stats) under-estimates the build
    by ~30x and the cold plan sizes its join for a build that is 30x
    bigger than planned (capacity-overflow retries). The first run
    records the truth into the history store, the epoch plane marks
    the consulted estimates diverged, and the WARM statement-cache hit
    REPLANS against learned cardinalities — the contract is
    ``warm_plan_changed`` (replan or strategy switch asserted from
    counters) and warm <= cold end-to-end. Backend-tagged like every
    line; boot failure emits a skipped line, never value 0."""
    import tempfile

    from presto_tpu.connectors import create_connector
    from presto_tpu.exec.local_runner import LocalQueryRunner
    from presto_tpu.utils.metrics import REGISTRY

    sql = (
        "select count(*) as n, sum(s.v) as sv "
        "from mem.default.adaptive_skew s "
        "join tpch.sf1.customer c on s.k = c.c_custkey "
        "where s.k = 7 and s.v > -1000000"
    )
    with tempfile.TemporaryDirectory() as td:
        runner = LocalQueryRunner(history_path=td)
        runner.session.set("adaptive_enabled", "true")
        runner.catalogs.register("mem", create_connector("memory"))
        # the skew: EVERY row carries build key 7 (sf1 customer is the
        # row source only), so the equality estimate misses by ~10x
        # and the extra conjunct pushes the cold error past 30x
        runner.execute(
            "create table mem.default.adaptive_skew as "
            "select 7 as k, c_acctbal as v from tpch.sf1.customer"
        )
        replans0 = int(REGISTRY.counter("plan.replans").total)
        switches0 = int(
            REGISTRY.counter("adaptive.strategy_switches").total
        )
        t0 = time.perf_counter()
        cold = runner.execute(sql).rows()
        cold_s = time.perf_counter() - t0
        # warm 1: statement-cache hit -> epoch divergence -> REPLAN
        # against learned cardinalities; warm 2 serves the replanned
        # entry (zero planning) — report the better of the two, the
        # steady warm state
        warm_rows = None
        warm_times = []
        for _ in range(2):
            t0 = time.perf_counter()
            warm_rows = runner.execute(sql).rows()
            warm_times.append(time.perf_counter() - t0)
        warm_s = min(warm_times)
        replans = int(REGISTRY.counter("plan.replans").total) - replans0
        switches = (
            int(REGISTRY.counter("adaptive.strategy_switches").total)
            - switches0
        )
    if warm_rows != cold:
        raise RuntimeError(
            f"adaptive replan changed results: {cold} != {warm_rows}"
        )
    warm_plan_changed = (replans + switches) > 0
    return {
        "metric": "adaptive_skewed_join_warm_vs_cold",
        "value": round(cold_s / warm_s, 3) if warm_s > 0 else None,
        "unit": "x",
        "cold_s": round(cold_s, 3),
        "warm_s": round(warm_s, 3),
        "replans": replans,
        "strategy_switches": switches,
        "warm_plan_changed": warm_plan_changed,
        # the acceptance contract: the warm run demonstrably changed
        # plan shape AND beat its cold run end-to-end
        "contract_ok": warm_plan_changed and warm_s <= cold_s,
        "backend": backend,
    }


def _probe_backend() -> str:
    """Run a real tiny computation — trace + compile + execute + fetch,
    the full dispatch path a query exercises — via the shared
    structured probe (utils/devicediag), so every bench line's
    ``backend_diag`` records WHICH phase died (enumerate / compile /
    execute). A dead probe raises: there is no fallback backend."""
    from presto_tpu.utils.devicediag import probe_backend

    diag = probe_backend()
    if not diag.ok:
        raise RuntimeError(
            f"backend probe failed at {diag.phase}: "
            f"{diag.error_class}: {diag.error}"
        )
    return diag.backend


def _multi_coordinator_failover_line(backend: str) -> dict:
    """Multi-coordinator HA (ISSUE 17): statement throughput with 1
    coordinator vs 3 lease-federated coordinators under sprayed client
    load, with a SCRIPTED kill of one coordinator mid-window in the
    3-coordinator phase. The contract is ``failed == 0``: every open
    query on the killed coordinator resumes on a lease-fenced peer and
    its statement URI keeps resolving through the alias chain, so
    clients never observe a failure — and the line records the
    1 -> 3 statement-qps scaling. A cluster that cannot even boot
    emits ``skipped``, never a fake zero."""
    import tempfile
    import threading

    from presto_tpu.server import CoordinatorServer, PrestoTpuClient
    from presto_tpu.session import NodeConfig
    from presto_tpu.utils import faults

    window_s = 4.0
    sql = "select count(*) as c from tpch.tiny.orders"

    def load_window(uris, expected, n_clients, kill=None):
        done = {"completed": 0, "failed": 0}
        lock = threading.Lock()
        stop = time.monotonic() + window_s

        def client_loop():
            client = PrestoTpuClient(
                uris, timeout_s=60, reconnect_attempts=16
            )
            while time.monotonic() < stop:
                try:
                    rows = [
                        tuple(r) for r in client.execute(sql).rows()
                    ]
                    ok = rows == expected
                except Exception:
                    ok = False
                with lock:
                    done["completed" if ok else "failed"] += 1

        threads = [
            threading.Thread(target=client_loop)
            for _ in range(n_clients)
        ]
        t0 = time.monotonic()
        for t in threads:
            t.start()
        if kill is not None:
            # the scripted kill: a quarter into the window, arm a
            # one-shot kill_coordinator rule against coord-0 — the
            # next statement it admits crashes it (lease goes silent,
            # socket closes, journal strands open queries)
            time.sleep(window_s * 0.25)
            kill()
        for t in threads:
            t.join(120)
        return done, time.monotonic() - t0

    def mk_coords(ctl, n):
        ports, socks = [], []
        import socket as _socket

        for _ in range(n):
            s = _socket.socket()
            s.bind(("127.0.0.1", 0))
            socks.append(s)
            ports.append(s.getsockname()[1])
        for s in socks:
            s.close()
        uris = [f"http://127.0.0.1:{p}" for p in ports]
        coords = []
        for i in range(n):
            cfg = {"node.id": f"coord-{i}"}
            if n > 1:
                cfg["coordinator.journal-path"] = ctl
                cfg["coordinator.peers"] = ",".join(
                    u for j, u in enumerate(uris) if j != i
                )
                cfg["lease.ttl-s"] = "0.75"
            coords.append(
                CoordinatorServer(
                    port=ports[i], config=NodeConfig(cfg)
                ).start()
            )
        return coords

    with tempfile.TemporaryDirectory() as td:
        # phase 1: the single-coordinator baseline
        coords = mk_coords(td + "/ctl1", 1)
        try:
            expected = [
                tuple(r) for r in coords[0].local.execute(sql).rows()
            ]
            solo, solo_wall = load_window(
                [coords[0].uri], expected, n_clients=8
            )
        finally:
            for c in coords:
                c.shutdown()
        # phase 2: 3 lease-federated coordinators + the scripted kill
        coords = mk_coords(td + "/ctl3", 3)
        try:
            spray = [c.uri for c in coords]
            fleet, fleet_wall = load_window(
                spray,
                expected,
                n_clients=8,
                kill=lambda: faults.configure({
                    "rules": [
                        {
                            "action": "kill_coordinator",
                            "node": "coord-0",
                            "count": 1,
                        },
                    ],
                }),
            )
            claims = sum(c.failover_claims for c in coords[1:])
        finally:
            faults.configure(None)
            for c in coords:
                c.shutdown()
    solo_qps = solo["completed"] / max(solo_wall, 1e-9)
    fleet_qps = fleet["completed"] / max(fleet_wall, 1e-9)
    return {
        "metric": "multi_coordinator_failover_qps",
        "value": round(fleet_qps, 2),
        "unit": "queries/s",
        "qps_1coord": round(solo_qps, 2),
        "scaling_x": round(fleet_qps / max(solo_qps, 1e-9), 2),
        "failed": solo["failed"] + fleet["failed"],
        "failover_claims": claims,
        "clients": 8,
        "coordinators": "1, then 3 with coord-0 killed mid-window",
        "backend": backend,
    }


def _q1_line(runner, backend: str) -> dict:
    """The headline TPC-H Q1 @ SF1 measurement (cold + steady-state
    rows/s). Raises on backend death mid-measurement — the caller
    emits the skip line."""
    import __graft_entry__ as G
    from presto_tpu.plan.planner import plan_statement
    from presto_tpu.sql import parse_statement
    from presto_tpu.utils.metrics import REGISTRY

    sql = G._Q1.replace("tiny", "sf1")
    nrows = _table_rows(runner, "sf1", "lineitem")
    # delta, not the process total
    hits0 = int(REGISTRY.counter("staging.cache_hit").total)
    plan = plan_statement(
        parse_statement(sql), runner.catalogs, runner.session
    )
    # cold: first end-to-end execution in this process — connector
    # read + host->device staging + XLA compile + execute
    t0 = time.perf_counter()
    runner.execute_plan(plan)
    cold_s = time.perf_counter() - t0
    # warm: steady state on the same process — split cache serves
    # the staged pages device-resident, compile cache hits
    rps, warm_s, _ = _bench_query(runner, sql, nrows, expect_rows=4)
    vs = (
        rps / CPU_BASELINE_ROWS_PER_SEC
        if CPU_BASELINE_ROWS_PER_SEC
        else 1.0
    )
    return {
        "metric": "tpch_q1_sf1_rows_per_sec",
        "value": round(rps),
        "unit": "rows/s",
        "vs_baseline": round(vs, 3),
        "backend": backend,
        "analysis_clean": _analysis_clean(),
        "cold_s": round(cold_s, 3),
        "warm_s": round(warm_s, 3),
        "staging_cache_hits": int(
            REGISTRY.counter("staging.cache_hit").total
        ) - hits0,
    }


def main() -> None:
    from presto_tpu.exec.local_runner import LocalQueryRunner

    run_all = "--all" in sys.argv
    # --only SUBSTR: run matching extra configs in isolation (one
    # process per heavy config — a backend crash on one config must not
    # poison the rest of the matrix)
    only = None
    if "--only" in sys.argv:
        only = sys.argv[sys.argv.index("--only") + 1]
        run_all = True

    backend = _probe_backend()
    runner = LocalQueryRunner()
    if only is None:
        try:
            _emit(_q1_line(runner, backend))
        except Exception as e:
            # the probe passed but the real measurement died: a skipped
            # line (no value key), and a non-zero exit at the end
            _emit(skip_line("tpch_q1_sf1_rows_per_sec", e))
        # serving plane: 100+ concurrent literal-variant EXECUTEs over
        # one prepared shape through the coordinator's micro-batch
        # queue — batched vs unbatched QPS/p50/p99 (a failed serving
        # measurement must not poison the Q1 line above)
        try:
            _emit(_serving_line(backend))
        except Exception as e:
            _emit(skip_line("serving_point_lookup_sf1_qps", e, "queries/s"))
        # result-reuse tier: the repeated-query mix (80% hot
        # fingerprints over a stable snapshot) — cached-tier qps vs
        # uncached on the same backend, hit count as its own line
        try:
            for rc_line in _serving_repeat_line(backend):
                _emit(rc_line)
        except Exception as e:
            _emit(
                skip_line("serving_repeated_cached_qps", e, "queries/s")
            )
            _emit(
                skip_line(
                    "serving_repeated_result_cache_hits", e, "hits"
                )
            )
        # elasticity: queries completed while the worker pool halves
        # and recovers mid-window (zero failures is the contract; a
        # cluster that cannot even boot emits skipped, not value 0)
        try:
            _emit(_elasticity_line(backend))
        except Exception as e:
            _emit(
                skip_line(
                    "elastic_pool_halving_queries_completed", e, "queries"
                )
            )
        # memory governance: concurrent over-budget mix on a capped
        # budget — completed + killed == submitted, zero wedged
        try:
            _emit(_memory_pressure_line(backend))
        except Exception as e:
            _emit(skip_line("memory_pressure_survivors", e, "queries"))
        # streaming ingest + incremental materialized views: sustained
        # WAL'd micro-batch ingest with 8 concurrent point-read
        # clients over an incrementally-maintained view — zero full
        # recomputes after warmup is the contract
        try:
            _emit(_streaming_ingest_line(backend))
        except Exception as e:
            _emit(skip_line("streaming_ingest_mview_qps", e))
        # tail-latency QoS: interactive point-lookup p99 with a
        # concurrent analytic scan load, qos-on vs qos-off — the
        # contract is qos-on p99 <= 2x idle p99
        try:
            _emit(_qos_line(backend))
        except Exception as e:
            _emit(skip_line("qos_interactive_p99_under_scan", e, "ms"))
        # exchange plane: partitioned join + aggregation wall-clock,
        # ICI (in-slice device collectives) vs HTTP shuffle on the
        # same backend — zero pages_wire bytes on in-slice edges is
        # the contract, asserted from counters
        try:
            _emit(_partitioned_join_line(backend))
        except Exception as e:
            _emit(skip_line("partitioned_join_shuffle_8dev", e, "s"))
        # adaptive execution: a skewed sf1 join run cold then warm —
        # the warm statement-cache hit must replan (or strategy-switch)
        # on history divergence and beat the cold run end-to-end
        try:
            _emit(_adaptive_line(backend))
        except Exception as e:
            _emit(skip_line("adaptive_skewed_join_warm_vs_cold", e, "x"))
        # multi-coordinator HA: 1 -> 3 coordinator statement qps with
        # a scripted kill mid-window — failed == 0 is the contract
        # (open queries fail over through the lease + alias chain)
        try:
            _emit(_multi_coordinator_failover_line(backend))
        except Exception as e:
            _emit(
                skip_line(
                    "multi_coordinator_failover_qps", e, "queries/s"
                )
            )
        # durable lakehouse: sustained acked ingest with a scripted
        # bounce killed at the _current pointer swap — the contract is
        # acked_batches_lost == 0 after restore + tail replay
        try:
            _emit(_lakehouse_restart_recovery_line(backend))
        except Exception as e:
            _emit(skip_line("lakehouse_restart_recovery", e))
    if not run_all:
        if _SKIPPED:
            sys.exit(1)  # a skipped line is a missing measurement
        return

    from presto_tpu import queries_tpcds

    # SF10 runs RESIDENT: ~2.4 GB of columns fit v5e HBM (16 GB) with
    # room to spare, and the staged-table cache amortizes the one-time
    # host->device transfer across iterations, where re-staging per
    # pass (what the default 1<<24 budget's streamed path does) would
    # count staging into every iteration (cost not measured on the
    # chip). iters=2 keeps heavy configs' wall sane.
    # The *_streamed config then exercises exec/streaming.py explicitly
    # with a forced 1M-row budget at SF1 (6 split batches + bucketed
    # merge per pass) — the larger-than-HBM discipline, measured.
    extra = [
        # SF1 join configs: ~240 MB working sets stage once (resident
        # thereafter), so the join rows of the matrix run at a scale
        # one chip holds
        ("tpch_q3_sf1_rows_per_sec", _Q3, "sf1", "lineitem", 10,
         None, None),
        ("tpch_q5_sf1_rows_per_sec", _Q5, "sf1", "lineitem", 5,
         None, None),
        # selective star join under a small fragment budget: the
        # stage-at-a-time executor builds the dynamic filter from the
        # part build side and prunes lineitem probe rows pre-join; the
        # line reports dynamic_filter_rows_pruned
        ("tpch_q17_selective_sf1_rows_per_sec", _Q17SEL, "sf1",
         "lineitem", 1, {"max_fragment_weight": "6"}, None),
        ("tpch_q3_sf10_rows_per_sec", _Q3, "sf10", "lineitem", 10,
         {"max_device_rows": str(1 << 27)}, 2),
        ("tpch_q5_sf10_rows_per_sec", _Q5, "sf10", "lineitem", 5,
         {"max_device_rows": str(1 << 27)}, 2),
        ("tpch_q18_sf1_rows_per_sec", _Q18, "sf1", "lineitem", 100,
         None, None),
        ("tpch_q18_sf10_rows_per_sec", _Q18, "sf10", "lineitem", 100,
         {"max_device_rows": str(1 << 27)}, 2),
        # budget 2M: lineitem (6M) streams while orders (1.5M) still
        # fits as the replicated build side of the semi-join
        ("tpch_q18_sf1_streamed_rows_per_sec", _Q18, "sf1", "lineitem",
         100, {"max_device_rows": str(1 << 21)}, 2),
        ("tpch_window_orders_sf1_rows_per_sec", _WINDOW, "sf1",
         "orders", None, None, None),
        ("tpcds_q95_tiny_rows_per_sec", queries_tpcds.Q95, None,
         ("tpcds", "tiny", "web_sales"), None, None, None),
        ("tpcds_q64_tiny_rows_per_sec", queries_tpcds.Q64, None,
         ("tpcds", "tiny", "store_sales"), None, None, None),
        # SF1-scale TPC-DS: star join over 2.88M store_sales rows
        ("tpcds_q3_sf1_rows_per_sec",
         queries_tpcds.official_for("sf1")["q3"], None,
         ("tpcds", "sf1", "store_sales"), None, None, 2),
        # the join-order stress query (bushy rescue: composite
        # (item, week) plan) at SF1 — 23.5M inventory x 14.4M
        # catalog_sales
        ("tpcds_q72_sf1_rows_per_sec",
         queries_tpcds.official_for("sf1")["q72"], None,
         ("tpcds", "sf1", "catalog_sales"),
         None, {"max_device_rows": str(1 << 27)}, 2),
    ]
    failed = 0
    for metric, sql, schema, driving, expect, props, iters in extra:
        if only is not None:
            # substring match, but never across a digit boundary:
            # --only tpch_q3_sf1 must NOT drag tpch_q3_sf10 along (an
            # unintended heavy config can crash the process and poison
            # the rest of the matrix)
            i = metric.find(only)
            if i < 0 or (
                i + len(only) < len(metric)
                and metric[i + len(only)].isdigit()
            ):
                continue
        try:
            from presto_tpu.utils.metrics import REGISTRY as _REG

            saved = {
                k: str(runner.session.get(k)) for k in (props or {})
            }
            pruned0 = _REG.counter("dynamic_filter.rows_pruned").total
            try:
                for k, v in (props or {}).items():
                    runner.session.set(k, v)
                if isinstance(driving, tuple):
                    cat, sch, tbl = driving
                    nrows = _table_rows_cat(runner, cat, sch, tbl)
                    q = sql
                else:
                    nrows = _table_rows(runner, schema, driving)
                    q = sql.replace("SCHEMA", schema)
                rps, best, n_runs = _bench_query(
                    runner,
                    q,
                    nrows,
                    expect_rows=expect,
                    iters=iters,
                )
            finally:
                for k, v in saved.items():
                    runner.session.set(k, v)
            line = {
                "metric": metric,
                "value": round(rps),
                "unit": "rows/s",
                "seconds": round(best, 3),
                "backend": backend,
            }
            if "q17_selective" in metric:
                # per-iteration pruning (the counter accumulates over
                # every plan execution of this config; n_runs is the
                # count _bench_query actually performed)
                total = (
                    _REG.counter("dynamic_filter.rows_pruned").total
                    - pruned0
                )
                line["dynamic_filter_rows_pruned"] = total // max(
                    n_runs, 1
                )
            _emit(line)
        except Exception as e:
            failed += 1
            _emit(skip_line(metric, e))
    if failed or _SKIPPED:
        # honest exit status: a crashed/errored config must not read
        # as rc=0 to the matrix wrapper
        sys.exit(1)


if __name__ == "__main__":
    try:
        main()
    except Exception as e:  # never leave the driver without a JSON line
        # skipped, NOT value: 0 — a backend-init failure is a missing
        # measurement, not a measured zero — and never exit code 0
        _emit(skip_line("tpch_q1_sf1_rows_per_sec", e))
        sys.exit(1)
