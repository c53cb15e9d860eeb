"""Server-path features landed in round 3 (VERDICT r2 items 6/7 + the
ordered MERGE exchange): always-on memory accounting with the
kill-largest policy, concurrent worker pulls, and merge-exchange
ordered gathers."""

import threading
import time

import pytest

from presto_tpu.server import CoordinatorServer, PrestoTpuClient, WorkerServer
from presto_tpu.server.client import QueryFailed
from presto_tpu.session import NodeConfig
from presto_tpu.verifier import SqliteOracle, verify_query


def _wait_workers(coord, n, timeout=10.0):
    deadline = time.time() + timeout
    while time.time() < deadline:
        if len(coord.active_workers()) >= n:
            return
        time.sleep(0.05)
    raise TimeoutError("workers not discovered")


# ------------------------------------------------------ memory accounting


def test_memory_accounting_always_on_server_path():
    """A too-big query fails on ACCOUNTING (MemoryLimitExceeded), not
    OOM — the pool is constructed by default from tier-1 config."""
    coord = CoordinatorServer(
        config=NodeConfig({"query.max-memory-per-node": "64kB"})
    ).start()
    try:
        assert coord.memory_pool.limit == 64 * 1024
        client = PrestoTpuClient(coord.uri, timeout_s=60)
        with pytest.raises(QueryFailed, match="[Mm]emory"):
            client.execute("select count(*) as c from tpch.tiny.lineitem")
    finally:
        coord.shutdown()


def test_memory_pool_default_on():
    coord = CoordinatorServer()
    try:
        assert coord.memory_pool is not None
        assert coord.local.memory_pool is coord.memory_pool
    finally:
        coord.shutdown()
    w = WorkerServer()
    try:
        assert w.memory_pool is not None
        assert w.runner.memory_pool is w.memory_pool
    finally:
        w.shutdown(graceful=False)


def test_kill_largest_policy():
    """Pool exhaustion kills the largest RUNNING query, never the
    requester or the shared table cache."""
    from presto_tpu.server.coordinator import _Query

    coord = CoordinatorServer(
        config=NodeConfig({"query.max-memory-per-node": "1000B"})
    )
    try:
        pool = coord.memory_pool
        big = _Query("q_big", "select 1")
        small = _Query("q_small", "select 2")
        coord.queries["q_big"] = big
        coord.queries["q_small"] = small
        pool.reserve("table-cache", 200)
        pool.reserve("q_big", 500)
        pool.reserve("q_small", 100)
        # q_new needs 400B: pool exhausted -> q_big is evicted
        pool.reserve("q_new", 400)
        assert big.state == "FAILED"
        assert "memory" in big.error.lower()
        assert small.state != "FAILED"
        assert pool.used_bytes("q_big") == 0
        assert pool.used_bytes("q_new") == 400
        assert pool.used_bytes("table-cache") == 200  # never evicted
    finally:
        coord.shutdown()


# --------------------------------------------------- concurrent pulls


class _CountingWorker(WorkerServer):
    """Counts created tasks (DELETE pops worker.tasks, so live counts
    don't survive the pull acks)."""

    def __init__(self, *a, **k):
        super().__init__(*a, **k)
        self.created = 0

    def create_task(self, spec):
        self.created += 1
        return super().create_task(spec)


class _SlowWorker(_CountingWorker):
    """Worker whose scan staging sleeps; records each staging interval
    so concurrency is assertable from event ORDER, not wall-clock
    ratios (load-insensitive — VERDICT r3 weak 3)."""

    DELAY_S = 0.6

    def __init__(self, *a, **kw):
        super().__init__(*a, **kw)
        self.spans = []

    def _load_range(self, scan, lo, hi, columns):
        t0 = time.time()
        time.sleep(self.DELAY_S)
        out = super()._load_range(scan, lo, hi, columns)
        self.spans.append((t0, time.time()))
        return out


def test_dynamic_splits_favor_fast_worker():
    """Work stealing: with one slow and one fast worker, the fast one
    drains most of the over-partitioned split queue (reference:
    dynamic split placement, SURVEY.md §2.4)."""
    from presto_tpu.session import Session

    coord = CoordinatorServer(
        session=Session(
            properties={"page_capacity": 4096, "split_queue_factor": 8}
        )
    ).start()
    slow = _SlowWorker(coordinator_uri=coord.uri)
    slow.DELAY_S = 0.4
    slow.start()
    fast = _CountingWorker(coordinator_uri=coord.uri).start()
    try:
        _wait_workers(coord, 2)
        client = PrestoTpuClient(coord.uri, timeout_s=120)
        res = client.execute(
            "select count(*) as c from tpch.tiny.lineitem"
        )
        assert res.rows() == [(59997,)]
        # the fast worker must have claimed more ranges than the slow
        assert fast.created > slow.created, (slow.created, fast.created)
    finally:
        slow.shutdown(graceful=False)
        fast.shutdown(graceful=False)
        coord.shutdown()


def test_stage_time_is_slowest_worker_not_sum():
    """3 slow workers, one batch each: tasks dispatch CONCURRENTLY, so
    the stage costs ~max(worker), not ~sum(worker) (VERDICT r2 item 7).

    Asserted from event ORDER — the three staging intervals must
    overlap (serial dispatch would make them disjoint no matter how
    loaded the box is) — not from wall-clock ratios, which flaked under
    load on the 1-vCPU CI host (VERDICT r3 weak 3)."""
    coord = CoordinatorServer()
    coord.local.session.set("page_capacity", 1 << 20)  # one batch/worker
    coord.local.session.set("split_queue_factor", 1)  # one range/worker
    workers = [
        _SlowWorker(coordinator_uri=coord.uri).start() for _ in range(3)
    ]
    for w in workers:
        w.DELAY_S = 1.5  # overlap margin >> scheduler jitter under load
    coord.start()
    try:
        _wait_workers(coord, 3)
        client = PrestoTpuClient(coord.uri, timeout_s=60)
        client.execute("select count(*) as c from tpch.tiny.region")
        for w in workers:
            w.spans.clear()  # warmup staging is not part of the stage
        res = client.execute(
            "select count(*) as c from tpch.tiny.lineitem"
        )
        assert res.rows() == [(59997,)]
        spans = [s for w in workers for s in w.spans]
        assert len(spans) == 3, spans  # one range per worker
        latest_start = max(s for s, _ in spans)
        earliest_end = min(e for _, e in spans)
        assert latest_start < earliest_end, (
            f"staging intervals did not overlap (serial dispatch?): "
            f"{spans}"
        )
    finally:
        for w in workers:
            w.shutdown(graceful=False)
        coord.shutdown()


# --------------------------------------------------- ordered MERGE


@pytest.fixture(scope="module")
def merge_cluster():
    coord = CoordinatorServer().start()
    workers = [
        WorkerServer(coordinator_uri=coord.uri).start() for _ in range(2)
    ]
    _wait_workers(coord, 2)
    yield coord, workers
    for w in workers:
        w.shutdown(graceful=False)
    coord.shutdown()


@pytest.fixture(scope="module")
def oracle():
    return SqliteOracle("tiny")


def test_ordered_merge_exchange(merge_cluster, oracle, monkeypatch):
    """ORDER BY over a no-cut fragment takes the merge-exchange path
    (workers emit sorted runs; the coordinator k-way merges instead of
    re-sorting) and stays oracle-exact."""
    from presto_tpu.server import coordinator as coord_mod

    coord, _ = merge_cluster
    calls = []
    orig = coord_mod._merge_sorted_runs

    def spy(payloads, schema, sort_node):
        calls.append(len(payloads))
        return orig(payloads, schema, sort_node)

    monkeypatch.setattr(coord_mod, "_merge_sorted_runs", spy)
    client = PrestoTpuClient(coord.uri, timeout_s=120)
    sql = (
        "select o_orderkey, o_totalprice from tpch.tiny.orders "
        "where o_custkey <= 200 "
        "order by o_totalprice desc, o_orderkey"
    )
    diff = verify_query(client, oracle, sql)
    assert diff is None, diff
    assert calls and calls[0] >= 2, "merge path did not engage"


def test_ordered_merge_topn(merge_cluster, oracle, monkeypatch):
    from presto_tpu.server import coordinator as coord_mod

    coord, _ = merge_cluster
    calls = []
    orig = coord_mod._merge_sorted_runs

    def spy(payloads, schema, sort_node):
        calls.append(sort_node.limit)
        return orig(payloads, schema, sort_node)

    monkeypatch.setattr(coord_mod, "_merge_sorted_runs", spy)
    client = PrestoTpuClient(coord.uri, timeout_s=120)
    sql = (
        "select l_orderkey, l_extendedprice from tpch.tiny.lineitem "
        "order by l_extendedprice desc, l_orderkey, l_linenumber "
        "limit 25"
    )
    diff = verify_query(client, oracle, sql)
    assert diff is None, diff
    assert calls == [25]


def test_bucketed_gather_merge(oracle, monkeypatch):
    """Partial states beyond the device budget hash-bucket at the
    gather and merge one bucket at a time (grouped execution at the
    coordinator; VERDICT r2 weak 5) — oracle-exact.

    Pins ``distributed_final=false``: with the worker<->worker shuffle
    on (the default), keyed FINAL merges run on workers and the
    coordinator's bucketed gather is the fallback discipline under
    test here."""
    from presto_tpu.exec import streaming as S
    from presto_tpu.session import Session

    coord = CoordinatorServer(
        session=Session(
            properties={
                "max_device_rows": 4096,
                "distributed_final": "false",
            }
        )
    ).start()
    workers = [
        WorkerServer(coordinator_uri=coord.uri).start() for _ in range(2)
    ]
    calls = []
    orig = S.bucketize_payloads

    def spy(payloads, schema, keys, n_buckets):
        calls.append(n_buckets)
        return orig(payloads, schema, keys, n_buckets)

    monkeypatch.setattr(S, "bucketize_payloads", spy)
    try:
        _wait_workers(coord, 2)
        client = PrestoTpuClient(coord.uri, timeout_s=300)
        sql = (
            "select l_orderkey, count(*) as c, sum(l_quantity) as s "
            "from tpch.tiny.lineitem group by l_orderkey"
        )
        diff = verify_query(client, oracle, sql)
        assert diff is None, diff
        assert calls and calls[0] > 1, "bucketed gather did not engage"
    finally:
        for w in workers:
            w.shutdown(graceful=False)
        coord.shutdown()


def test_agg_query_skips_merge_path(merge_cluster, monkeypatch):
    """A stage with an aggregation cut must NOT take the merge path
    (sorted runs of partial states would be wrong)."""
    from presto_tpu.server import coordinator as coord_mod

    coord, _ = merge_cluster
    calls = []
    orig = coord_mod._merge_sorted_runs

    def spy(*a):
        calls.append(1)
        return orig(*a)

    monkeypatch.setattr(coord_mod, "_merge_sorted_runs", spy)
    client = PrestoTpuClient(coord.uri, timeout_s=120)
    res = client.execute(
        "select l_returnflag, count(*) as n from tpch.tiny.lineitem "
        "group by l_returnflag order by l_returnflag"
    )
    assert len(res.rows()) == 3
    assert not calls


def test_statement_surface_over_http():
    """The round-5 statement surface — DDL, DML, DESCRIBE, prepared
    statements — works over the client protocol (result pages incl.
    the two-varchar DESCRIBE page serialize on the wire)."""
    from presto_tpu.connectors import create_connector
    from presto_tpu.exec.staging import CatalogManager

    catalogs = CatalogManager()
    catalogs.register("tpch", create_connector("tpch"))
    catalogs.register("mem", create_connector("memory"))
    coord = CoordinatorServer(catalogs=catalogs)
    coord.start()
    try:
        client = PrestoTpuClient(coord.uri, timeout_s=120)
        client.execute(
            "create table mem.default.wire (k bigint, v varchar)"
        )
        assert client.execute(
            "show columns from mem.default.wire"
        ).data == [["k", "bigint"], ["v", "varchar"]]
        client.execute(
            "insert into mem.default.wire values (1, 'a'), (2, 'b')"
        )
        assert client.execute(
            "update mem.default.wire set v = 'z' where k = 2"
        ).data == [[1]]
        assert client.execute(
            "delete from mem.default.wire where k = 1"
        ).data == [[1]]
        assert client.execute(
            "select k, v from mem.default.wire"
        ).data == [[2, "z"]]
        client.execute(
            "prepare wp from select v from mem.default.wire "
            "where k = ?"
        )
        assert client.execute("execute wp using 2").data == [["z"]]
        client.execute("drop table mem.default.wire")
    finally:
        coord.shutdown()
