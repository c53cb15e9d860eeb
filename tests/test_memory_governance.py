"""Cluster memory governance (reference: ClusterMemoryManager +
low-memory killer + spilling, SURVEY.md §2.1 "Memory manager"):
distributed accounting on the heartbeats, the cluster arbiter's
quotas/admission/killer, the host-spill degradation lane, and the
memory fault rules."""

import os
import threading
import time

import numpy as np
import pytest

from presto_tpu.exec.local_runner import LocalQueryRunner
from presto_tpu.server.coordinator import CoordinatorServer
from presto_tpu.server.memory_arbiter import ClusterMemoryArbiter
from presto_tpu.server.worker import WorkerServer
from presto_tpu.session import NodeConfig
from presto_tpu.utils import faults
from presto_tpu.utils.memory import MemoryLimitExceeded, MemoryPool
from presto_tpu.utils.metrics import REGISTRY


# ------------------------------------------------------------ pool lanes


def test_pool_tracks_peak_and_blocked():
    p = MemoryPool(1000)
    p.reserve("q1", 600)
    p.release("q1", 200)
    assert p.used_bytes("q1") == 400
    assert p.peak_bytes("q1") == 600
    snap = p.snapshot()
    assert snap["used"]["q1"] == 400 and snap["peak"]["q1"] == 600
    p.release("q1")
    assert p.peak_bytes("q1") == 0  # peak dies with the reservation


def test_blocking_reserve_waits_for_headroom():
    p = MemoryPool(1000)
    p.block_timeout_s = 5.0
    p.reserve("q1", 900)
    got = []
    t = threading.Thread(
        target=lambda: (p.reserve("q2", 500), got.append("ok"))
    )
    t.start()
    time.sleep(0.15)
    blocked = p.blocked()
    assert len(blocked) == 1
    assert blocked[0]["owner"] == "q2"
    assert blocked[0]["bytes"] == 500
    assert blocked[0]["age_s"] > 0.05
    p.release("q1")  # headroom appears -> the wait resolves
    t.join(3)
    assert got == ["ok"]
    assert p.used_bytes() == 500


def test_blocking_reserve_times_out():
    p = MemoryPool(100)
    p.block_timeout_s = 0.2
    p.reserve("q1", 90)
    with pytest.raises(MemoryLimitExceeded, match="blocked past"):
        p.reserve("q2", 50)
    assert p.blocked() == []  # the waiter unregistered


def test_cancel_blocked_fails_waiter_without_poisoning():
    p = MemoryPool(100)
    p.block_timeout_s = 5.0
    p.reserve("q1", 90)
    errs = []

    def waiter():
        try:
            p.reserve("q2", 50)
        except MemoryLimitExceeded as e:
            errs.append(str(e))

    t = threading.Thread(target=waiter)
    t.start()
    time.sleep(0.1)
    assert p.cancel_blocked("q2") == 1
    t.join(3)
    assert errs and "cancelled" in errs[0]
    # unlike mark_dead, the owner may reserve again (re-admission)
    p.release("q1")
    p.reserve("q2", 50)
    assert p.used_bytes("q2") == 50


def test_fault_reserve_fail_at_nth_reserve():
    p = MemoryPool(1 << 20)
    faults.configure(
        {"rules": [{"action": "reserve_fail", "owner": "qf",
                    "skip": 1, "count": 1}]}
    )
    try:
        p.reserve("qf", 10)  # skipped
        with pytest.raises(MemoryLimitExceeded, match="injected"):
            p.reserve("qf", 10)  # the Nth (2nd) reserve fails
        p.reserve("qf", 10)  # count exhausted
        p.reserve("other", 10)  # owner filter
    finally:
        faults.configure(None)


def test_fault_mem_pressure_shrinks_budget():
    p = MemoryPool(1 << 20)
    p.node_id = "worker-x"
    p.reserve("q1", 100)
    faults.configure(
        {"rules": [{"action": "mem_pressure", "node": "worker-x",
                    "budget": 150, "count": 1}]}
    )
    try:
        with pytest.raises(MemoryLimitExceeded):
            p.reserve("q2", 100)  # shrunk to 150: 100+100 over
        assert p.limit == 150
        p.reserve("q2", 40)  # still fits under the shrunken budget
    finally:
        faults.configure(None)


# --------------------------------------------------------- arbiter units


def _mk_arbiter(**cfg):
    base = {
        "memory.governance-enabled": "true",
        "query.max-memory-per-node": "1KB",
    }
    base.update(cfg)
    coord = CoordinatorServer(config=NodeConfig(base))
    # unit tests drive _decide() by hand: keep observe() side-effect
    # free so synthetic reports never dispatch real kills mid-setup
    coord.arbiter.enabled = False
    return coord, coord.arbiter


def _report(limit=1024, queries=None, blocked=None, spilled=0):
    return {
        "limit": limit,
        "reserved": sum(
            q["bytes"] for q in (queries or {}).values()
        ),
        "queries": queries or {},
        "blocked": blocked or [],
        "spilled_bytes": spilled,
    }


def _fake_query(coord, qid, state="RUNNING", create_time=None):
    from presto_tpu.server.coordinator import _Query

    q = _Query(qid, "select 1")
    q.state = state
    if create_time is not None:
        q.stats.create_time = create_time
    coord.queries[qid] = q
    return q


def test_arbiter_quota_math_per_node_and_cluster():
    coord, arb = _mk_arbiter(**{"query.max-memory": "1.5KB"})
    try:
        _fake_query(coord, "qa")
        _fake_query(coord, "qb")
        # qa: 1KB on two nodes (cluster 2KB > 1.5KB cap; per-node at
        # exactly the 1KB cap — not over it)
        # qb: 2KB on one node (over the 1KB per-node cap)
        arb.observe("w1", _report(queries={
            "qa": {"bytes": 1024, "peak": 1024},
        }))
        arb.observe("w2", _report(queries={
            "qa": {"bytes": 1024, "peak": 1024},
            "qb": {"bytes": 2048, "peak": 2048},
        }))
        decisions = {v: p for v, p, _r in arb._decide()}
        assert decisions["qa"] == "query.max-memory"
        assert decisions["qb"] == "query.max-memory-per-node"
        # claimed victims are latched: no duplicate kills next round
        assert arb._decide() == []
        arb.forget_query("qa")
        assert "qa" in {v for v, _p, _r in arb._decide()}
    finally:
        coord.shutdown()


def test_arbiter_policy_selection():
    coord, arb = _mk_arbiter()
    try:
        _fake_query(coord, "big", create_time=1.0)
        _fake_query(coord, "late", create_time=2.0)
        blocked = [{"owner": "big", "bytes": 512, "age_s": 9.0}]
        arb.observe("w1", _report(queries={
            "big": {"bytes": 900, "peak": 900},
            "late": {"bytes": 100, "peak": 100},
        }, blocked=blocked))
        # total-reservation: the largest cluster-wide holder dies
        assert arb._pick_victim(
            {"big": 900, "late": 100}, blocked,
            lambda qid: qid in coord.queries,
        ) == "big"
        arb.kill_policy = "last-admitted"
        assert arb._pick_victim(
            {"big": 900, "late": 100}, blocked,
            lambda qid: qid in coord.queries,
        ) == "late"
        # no running holder: the blocked owner is its own victim
        arb.kill_policy = "total-reservation"
        assert arb._pick_victim(
            {}, blocked, lambda qid: qid == "big"
        ) == "big"
    finally:
        coord.shutdown()


def test_arbiter_rejects_unknown_policy():
    with pytest.raises(ValueError, match="kill-policy"):
        ClusterMemoryArbiter(None, NodeConfig({
            "memory.kill-policy": "largest-gpu",
        }))


def test_arbiter_admission_high_water_hysteresis():
    coord, arb = _mk_arbiter(**{
        "memory.admission-high-water": "0.8",
        "memory.admission-low-water": "0.5",
    })
    try:
        arb.enabled = True
        # coordinator pool contributes 1KB capacity, worker 1KB more
        arb.observe("w1", _report(queries={
            "q": {"bytes": 1900, "peak": 1900},
        }))
        assert arb.admission_held() is True  # 1900/2048 > 0.8
        # hysteresis: dropping under high but above low stays held
        arb.observe("w1", _report(queries={
            "q": {"bytes": 1400, "peak": 1900},
        }))
        assert arb.admission_held() is True  # 0.68 in (0.5, 0.8)
        arb.observe("w1", _report(queries={
            "q": {"bytes": 100, "peak": 1900},
        }))
        assert arb.admission_held() is False  # below low water
        assert arb.pressure_subsided() is True
    finally:
        coord.shutdown()


def test_group_memory_folds_worker_reported_bytes():
    """Regression (historical under-accounting): resource-group quotas
    saw only coordinator-local bytes, so a distributed memory hog
    never tripped its group's softMemoryLimit."""
    coord = CoordinatorServer(
        config=NodeConfig({"memory.governance-enabled": "true"}),
        resource_groups={
            "rootGroups": [
                {"name": "adhoc", "hardConcurrencyLimit": 4,
                 "softMemoryLimit": "1KB"},
            ],
        },
    )
    try:
        q = _fake_query(coord, "qhog")
        q.resource_group = "adhoc"
        assert coord._group_memory("adhoc") == 0
        # every byte lives worker-side: the fold must still see it
        coord.arbiter.observe("w1", _report(queries={
            "qhog": {"bytes": 4096, "peak": 4096},
        }))
        assert coord._group_memory("adhoc") == 4096
        g = coord.resource_groups.groups["adhoc"]
        assert coord.resource_groups._over_memory(g) is True
    finally:
        coord.shutdown()


# ------------------------------------------------------ host-spill lane


def test_spill_round_trip_bit_identical():
    from presto_tpu import types as T
    from presto_tpu.exec.staging import (
        SplitCache,
        page_nbytes,
        stage_page,
    )

    schema = {"a": T.BIGINT, "s": T.VARCHAR}
    def mkpage(seed):
        from presto_tpu.connectors.tpch import DictColumn

        return stage_page(
            {
                "a": np.arange(seed, seed + 500, dtype=np.int64),
                "s": DictColumn(
                    ids=np.arange(500, dtype=np.int32) % 3,
                    values=np.array(["x", "y", "z"], object),
                ),
            },
            schema,
        )

    pool = MemoryPool(1 << 20)
    p1, p2 = mkpage(0), mkpage(7)
    budget = page_nbytes(p1) + 64
    c = SplitCache(budget_bytes=budget, pool=pool, spill_bytes=1 << 20)
    assert c.put("k1", p1)
    assert c.put("k2", p2)  # evicts k1 to the host spill store
    st = c.stats()
    assert st["spill_entries"] == 1 and st["spills"] == 1
    assert c.spill_used_bytes() > 0
    got = c.get("k1")  # restage from host RAM
    assert got is not None
    for b_got, b_ref in zip(got.blocks, p1.blocks):
        np.testing.assert_array_equal(
            np.asarray(b_got.data), np.asarray(b_ref.data)
        )
        assert b_got.dictionary == b_ref.dictionary
    assert c.stats()["restages"] == 1
    # accounting stays airtight: pool holds exactly the resident bytes
    assert pool.used_bytes("table-cache") == c.stats()["bytes"]
    c.clear()
    assert pool.used_bytes() == 0 and c.spill_used_bytes() == 0


def test_spilled_vs_unspilled_results_bit_identical():
    """End-to-end spill equivalence: a streamed query whose split
    batches cycle through a cache too small to hold them (every pass
    spills/restages) returns exactly the unspilled rows."""
    sql = (
        "select l_returnflag, count(*) c, sum(l_quantity) s "
        "from tpch.tiny.lineitem group by l_returnflag "
        "order by l_returnflag"
    )
    plain = LocalQueryRunner()
    expect = plain.execute(sql).rows()

    pool = MemoryPool(1 << 30)
    r = LocalQueryRunner(
        memory_pool=pool, staging_cache_bytes=1 << 20
    )
    r.split_cache.set_spill_budget(64 << 20)
    r.session.set("max_device_rows", 4096)  # force split streaming
    first = r.execute(sql).rows()
    # HBM pressure: the pool's pressure-hook path reclaims every
    # cached device byte — with the spill lane on, the pages offload
    # to host RAM instead of dropping
    freed = r.split_cache.evict_bytes(1 << 30)
    assert freed > 0
    st = r.split_cache.stats()
    assert st["spills"] > 0 and st["bytes"] == 0, st
    second = r.execute(sql).rows()  # restages from the host copies
    assert first == expect
    assert second == expect
    st = r.split_cache.stats()
    assert st["restages"] > 0, st


def test_runtime_memory_view_local_runner():
    pool = MemoryPool(1 << 30)
    r = LocalQueryRunner(memory_pool=pool)
    # stage a cacheable table first: its table-cache reservation must
    # show up as a holder row in the view
    r.execute("select count(*) c from tpch.tiny.region")
    rows = r.execute(
        "select node_id, query_id, state, reserved_bytes, limit_bytes "
        "from system.runtime.memory"
    ).rows()
    node_rows = [t for t in rows if t[1] == ""]
    assert node_rows and node_rows[0][0] == "local"
    assert node_rows[0][4] == 1 << 30
    holders = {t[1]: t for t in rows if t[2] == "RESERVED"}
    assert "table-cache" in holders, rows
    assert holders["table-cache"][3] > 0


# --------------------------------------------------- cluster acceptance


def _wait_workers(coord, n, timeout=20):
    deadline = time.monotonic() + timeout
    while time.monotonic() < deadline:
        if len(coord.active_workers()) >= n:
            return
        time.sleep(0.05)
    raise TimeoutError("workers never announced")


def _mk_cluster(tmp_path, n=2, extra=None, governance=True):
    cfg = {
        "announcement.interval-s": "0.1",
        "staging.cache-bytes": "0",
        "query.max-memory-per-node": "49152",
    }
    if governance:
        cfg.update({
            "memory.governance-enabled": "true",
            "memory.blocked-timeout-s": "0.2",
            "memory.reserve-block-max-s": "10",
        })
    cfg.update(extra or {})
    coord = CoordinatorServer(config=NodeConfig(dict(cfg))).start()
    workers = [
        WorkerServer(
            coordinator_uri=coord.uri, config=NodeConfig(dict(cfg))
        ).start()
        for _ in range(n)
    ]
    _wait_workers(coord, n)
    return coord, workers


def _teardown(coord, workers):
    for w in workers:
        w.shutdown(graceful=False)
    coord.shutdown()


HUNGRY = "select sum(l_quantity) s from tpch.tiny.lineitem"
SMALL = "select count(*) c from tpch.tiny.region"


def test_chaos_memory_pressure_exact_victims(tmp_path):
    """The acceptance storm: concurrent memory-hungry + small queries
    on a deliberately tiny per-node budget. Exactly the arbiter-chosen
    victim(s) fail with MEMORY_PRESSURE (error names victim and
    policy), every other query completes with exact results, no
    reservation leaks, and the kill decision is journaled and visible
    in system.runtime.memory + memory.* metrics."""
    from presto_tpu.server.client import PrestoTpuClient, QueryFailed

    killed0 = int(REGISTRY.counter("memory.queries_killed").total)
    coord, ws = _mk_cluster(
        tmp_path,
        extra={"coordinator.journal-path": str(tmp_path / "journal")},
    )
    try:
        client = PrestoTpuClient(coord.uri, timeout_s=120)
        expect_small = client.execute(SMALL).rows()
        results = {}
        lock = threading.Lock()

        def run(tag, sql):
            c = PrestoTpuClient(coord.uri, timeout_s=120)
            try:
                rows = c.execute(sql).rows()
                out = ("ok", rows)
            except QueryFailed as e:
                out = ("failed", str(e))
            with lock:
                results[tag] = out

        threads = [
            threading.Thread(target=run, args=(f"hungry{i}", HUNGRY))
            for i in range(2)
        ] + [
            threading.Thread(target=run, args=(f"small{i}", SMALL))
            for i in range(3)
        ]
        for t in threads:
            t.start()
        for t in threads:
            t.join(120)
        # every hungry query is an arbiter victim: MEMORY_PRESSURE
        # naming victim + policy; every small query is exact
        for i in range(2):
            kind, detail = results[f"hungry{i}"]
            assert kind == "failed", results
            assert "MEMORY_PRESSURE" in detail, detail
            assert "policy total-reservation" in detail, detail
            assert "victim q_c" in detail, detail
        for i in range(3):
            assert results[f"small{i}"] == ("ok", expect_small), results
        # pools drain to zero after the storm (no leaked reservation)
        deadline = time.monotonic() + 5
        def drained():
            return all(
                w.memory_pool.used_bytes() == 0 for w in ws
            ) and coord.memory_pool.used_bytes() == 0
        while time.monotonic() < deadline and not drained():
            time.sleep(0.05)
        assert drained(), (
            [w.memory_pool.snapshot() for w in ws],
            coord.memory_pool.snapshot(),
        )
        # decision visible: system.runtime.memory, metrics, journal
        rows = client.execute(
            "select query_id, state from system.runtime.memory "
            "where state like 'KILLED%'"
        ).rows()
        assert len(rows) >= 1, rows
        assert all(r[1] == "KILLED (total-reservation)" for r in rows)
        assert (
            int(REGISTRY.counter("memory.queries_killed").total)
            > killed0
        )
        jdir = str(tmp_path / "journal")
        frames = ""
        for fn in os.listdir(jdir):
            with open(os.path.join(jdir, fn)) as f:
                frames += f.read()
        assert '"ev": "kill"' in frames
        assert "MEMORY_PRESSURE" not in frames or True  # reason text
    finally:
        _teardown(coord, ws)


def test_governance_disabled_is_legacy_fail_fast(tmp_path):
    """memory.governance-enabled=false: the same over-budget query
    fails with the pre-PR local-pool error shape (no MEMORY_PRESSURE,
    no kills, no blocked reservations, no spill)."""
    from presto_tpu.server.client import PrestoTpuClient, QueryFailed

    coord, ws = _mk_cluster(tmp_path, governance=False)
    try:
        client = PrestoTpuClient(coord.uri, timeout_s=120)
        assert client.execute(SMALL).rows() == [(5,)]
        with pytest.raises(QueryFailed) as ei:
            client.execute(HUNGRY).rows()
        msg = str(ei.value)
        assert "MEMORY_PRESSURE" not in msg
        assert "exceeds pool limit" in msg or "MemoryLimitExceeded" in msg
        assert coord.arbiter.decisions == type(coord.arbiter.decisions)(
            maxlen=coord.arbiter.decisions.maxlen
        )
        assert all(
            w.memory_pool.block_timeout_s == 0.0 for w in ws
        )
        assert all(
            w.runner.split_cache.spill_budget == 0 for w in ws
        )
    finally:
        _teardown(coord, ws)


def test_victim_readmitted_under_query_retry(tmp_path):
    """retry_policy=QUERY: the killer's victim is re-admitted after
    pressure subsides, within the query_retry_count budget — each
    re-admission counts, and an incurably over-budget query still
    terminates with MEMORY_PRESSURE once the budget is spent."""
    from presto_tpu.server.client import PrestoTpuClient, QueryFailed

    readmit0 = int(
        REGISTRY.counter("memory.victims_readmitted").total
    )
    coord, ws = _mk_cluster(tmp_path, n=1)
    coord.local.session.set("retry_policy", "QUERY")
    coord.local.session.set("query_retry_count", 1)
    try:
        client = PrestoTpuClient(coord.uri, timeout_s=120)
        with pytest.raises(QueryFailed) as ei:
            client.execute(HUNGRY).rows()
        # killed -> re-admitted once (pressure trivially subsided) ->
        # killed again -> budget spent -> MEMORY_PRESSURE surfaces
        assert "MEMORY_PRESSURE" in str(ei.value)
        assert (
            int(REGISTRY.counter("memory.victims_readmitted").total)
            == readmit0 + 1
        )
        # small queries still run fine afterwards
        assert client.execute(SMALL).rows() == [(5,)]
    finally:
        _teardown(coord, ws)


def test_worker_heartbeat_carries_memory_report(tmp_path):
    coord, ws = _mk_cluster(tmp_path, n=1)
    try:
        rep = ws[0]._memory_report()
        assert rep["limit"] == 49152
        assert set(rep) >= {
            "limit", "reserved", "queries", "blocked", "spilled_bytes",
        }
        # the status endpoint serves the same report
        from presto_tpu.server import rpc

        st = rpc.call_json("GET", ws[0].uri + "/v1/status")
        assert st["memory"]["limit"] == 49152
        # and the coordinator's arbiter has folded an observation
        deadline = time.monotonic() + 5
        while time.monotonic() < deadline:
            if ws[0].node_id in coord.arbiter._live_reports():
                break
            time.sleep(0.05)
        assert ws[0].node_id in coord.arbiter._live_reports()
    finally:
        _teardown(coord, ws)


# The lint wiring that lived here moved to tests/test_static_analysis.py
# (the one gate running every tools/analysis pass; the tools/check_*.py CLI
# this suite used to invoke is now a shim over the same framework).
