"""Device-resident split cache + pipelined prefetch staging.

Covers the worker hot-path optimization end to end: LRU/byte-budget
semantics of :class:`presto_tpu.exec.staging.SplitCache` (enforced
through the memory accountant), cache-hit correctness vs fresh
staging, invalidation on writable-connector writes, prefetch-depth=0
equivalence plus the ``stage:prefetch``/``execute`` span overlap,
pipelined exchange pulls (``rpc.pull-depth``), and adaptive
exchange compression.
"""

import gc
import os
import time

import numpy as np
import pytest

from presto_tpu import types as T
from presto_tpu.connectors.spi import TableHandle
from presto_tpu.exec.local_runner import LocalQueryRunner
from presto_tpu.exec.staging import (
    SplitCache,
    page_nbytes,
    prefetch_iter,
    stage_page,
)
from presto_tpu.session import NodeConfig, Session
from presto_tpu.utils.memory import MemoryPool
from presto_tpu.utils.telemetry import device_snapshot


def _page(n=1024, fill=1):
    return stage_page(
        {"x": np.full(n, fill, np.int64)}, {"x": T.BIGINT}
    )


def _h(table):
    return TableHandle("tpch", "tiny", table)


# ------------------------------------------------- SplitCache semantics


def test_lru_eviction_respects_budget_and_pool():
    pool = MemoryPool(1 << 20)
    page = _page()
    nbytes = page_nbytes(page)
    cache = SplitCache(budget_bytes=int(nbytes * 2.5), pool=pool)
    k1, k2, k3, k4 = [(_h("a"), i) for i in range(4)]
    assert cache.put(k1, _page(fill=1))
    assert cache.put(k2, _page(fill=2))
    # both fit; the pool's shared owner carries exactly the cache bytes
    assert pool.used_bytes(SplitCache.OWNER) == cache.used_bytes()
    assert cache.put(k3, _page(fill=3))  # evicts k1 (LRU)
    assert cache.evictions == 1
    assert cache.used_bytes() <= cache.budget
    assert pool.used_bytes(SplitCache.OWNER) == cache.used_bytes()
    assert cache.get(k1) is None
    assert cache.get(k2) is not None  # refreshes k2
    assert cache.put(k4, _page(fill=4))  # now evicts k3, not k2
    assert cache.get(k3) is None
    assert cache.get(k2) is not None
    stats = cache.stats()
    assert stats["evictions"] == 2
    assert stats["bytes"] == pool.used_bytes(SplitCache.OWNER)


def test_oversized_entry_never_cached():
    cache = SplitCache(budget_bytes=100)
    assert not cache.put((_h("a"), 0), _page())
    assert cache.used_bytes() == 0


def test_cache_fill_never_kills_a_query():
    """try_reserve discipline: a full pool means "not cached", not a
    kill-largest eviction of a running query's reservation."""
    pool = MemoryPool(10_000)
    pool.reserve("q_running", 9_000)
    cache = SplitCache(budget_bytes=1 << 20, pool=pool)
    assert not cache.put((_h("a"), 0), _page())  # 8KB won't fit
    assert pool.used_bytes("q_running") == 9_000
    assert cache.used_bytes() == 0


def test_query_reservation_reclaims_cache_under_pressure():
    """A query's raising reserve evicts droppable cache bytes (the
    MemoryPool pressure hook) instead of failing or killing a query
    while gigabytes of cache sit idle."""
    page = _page()
    nbytes = page_nbytes(page)
    pool = MemoryPool(int(nbytes * 3.5))
    cache = SplitCache(budget_bytes=1 << 20, pool=pool)
    for i in range(3):
        assert cache.put((_h("a"), i), _page(fill=i))
    # pool nearly full of cache; a query needs 2 pages' worth
    pool.reserve("q_live", int(nbytes * 2))
    assert pool.used_bytes("q_live") == int(nbytes * 2)
    assert cache.stats()["entries"] <= 1  # LRU entries yielded
    assert (
        pool.used_bytes(SplitCache.OWNER) == cache.used_bytes()
    )


def test_pinned_entries_survive_pressure_eviction():
    """An entry serving an EXECUTING batch is pinned: eviction must
    not release its pool accounting while the page is live on device
    (over-commit). Unpinning makes it evictable again."""
    page = _page()
    nbytes = page_nbytes(page)
    pool = MemoryPool(int(nbytes * 4.5))
    cache = SplitCache(budget_bytes=1 << 20, pool=pool)
    keys = [(_h("a"), i) for i in range(3)]
    for i, k in enumerate(keys):
        assert cache.put(k, _page(fill=i))
    assert cache.get(keys[0], pin=True) is not None
    # pressure for ~2 pages: LRU order would take k0 first, but it is
    # pinned — k1/k2 go instead
    pool.reserve("q_live", int(nbytes * 3))
    assert cache.get(keys[0]) is not None  # pinned entry survived
    assert cache.get(keys[1]) is None and cache.get(keys[2]) is None
    assert pool.used_bytes(SplitCache.OWNER) == cache.used_bytes()
    # fully pinned cache cannot satisfy further pressure: reserve fails
    with pytest.raises(Exception):
        pool.reserve("q_more", int(nbytes * 2))
    cache.unpin(keys[0])
    pool.reserve("q_more", int(nbytes * 1.2))  # now evictable
    assert cache.stats()["entries"] == 0


def test_put_does_not_evict_when_pool_reservation_fails():
    """try_reserve failure must not have emptied the cache first."""
    page = _page()
    nbytes = page_nbytes(page)
    pool = MemoryPool(int(nbytes * 2.5))
    cache = SplitCache(budget_bytes=int(nbytes * 1.5), pool=pool)
    assert cache.put((_h("a"), 0), _page())
    pool.reserve("q_live", int(nbytes * 1.2))  # pool now tight
    assert not cache.put((_h("a"), 1), _page())
    assert cache.stats()["entries"] == 1  # existing entry survived


def test_invalidate_releases_reservations():
    pool = MemoryPool(1 << 20)
    cache = SplitCache(budget_bytes=1 << 20, pool=pool)
    cache.put((_h("a"), 0), _page())
    cache.put((_h("a"), 1), _page())
    cache.put((_h("b"), 0), _page())
    assert cache.invalidate(_h("a")) == 2
    assert cache.stats()["entries"] == 1
    assert pool.used_bytes(SplitCache.OWNER) == cache.used_bytes()


# ------------------------------------------- runner integration (hits)


def test_repeated_query_hits_cache_and_skips_connector():
    r = LocalQueryRunner()
    conn = r.catalogs.get("tpch")
    calls = []
    orig = conn.create_page_source

    def spy(split, columns):
        calls.append(split)
        return orig(split, columns)

    q = "select count(*) as c, sum(r_regionkey) as s from tpch.tiny.region"
    conn.create_page_source = spy
    try:
        first = r.execute(q)
        assert len(calls) > 0
        calls.clear()
        second = r.execute(q)
        assert calls == [], "warm run must not touch the connector"
    finally:
        conn.create_page_source = orig
    assert first.rows() == second.rows()
    assert r.split_cache.hits >= 1
    # per-query stats carry the hit count
    warm_qs = r.history.snapshot()[-1]
    assert warm_qs.staging_cache_hits >= 1


def test_cache_budget_enforced_through_accountant_under_load():
    """With a budget far below the working set, the cache never
    exceeds staging.cache-bytes (asserted via the memory pool) and
    eviction keeps queries correct."""
    pool = MemoryPool(1 << 30)
    budget = 200_000  # region+nation fit; lineitem columns do not
    r = LocalQueryRunner(memory_pool=pool, staging_cache_bytes=budget)
    queries = [
        "select count(*) as c from tpch.tiny.region",
        "select count(*) as c from tpch.tiny.nation",
        "select count(*) as c from tpch.tiny.supplier",
        "select sum(l_quantity) as s from tpch.tiny.lineitem",
        "select count(*) as c from tpch.tiny.region",
    ]
    expect = [(5,)], [(25,)], [(100,)], None, [(5,)]
    for q, exp in zip(queries * 2, list(expect) * 2):
        res = r.execute(q)
        if exp is not None:
            assert res.rows() == exp
        assert r.split_cache.used_bytes() <= budget
        assert pool.used_bytes(SplitCache.OWNER) <= budget
        assert (
            pool.used_bytes(SplitCache.OWNER)
            == r.split_cache.used_bytes()
        )


def test_memory_connector_write_invalidates_cache():
    from presto_tpu.connectors import create_connector

    r = LocalQueryRunner()
    r.catalogs.register("mem", create_connector("memory"))
    r.execute("create table mem.default.t (x bigint)")
    r.execute("insert into mem.default.t values (1), (2)")
    q = "select x from mem.default.t order by x"
    assert r.execute(q).rows() == [(1,), (2,)]
    handle = TableHandle("mem", "default", "t")
    assert any(
        k[0] == handle for k in r.split_cache._entries
    ), "memory-connector page should be cached after a scan"
    r.execute("insert into mem.default.t values (3)")
    assert not any(
        k[0] == handle for k in r.split_cache._entries
    ), "a write must invalidate the table's cached pages"
    assert r.execute(q).rows() == [(1,), (2,), (3,)]
    r.execute("delete from mem.default.t where x = 2")
    assert r.execute(q).rows() == [(1,), (3,)]


# ------------------------------------------- residency by column


Q6_COLS = ("l_extendedprice", "l_discount", "l_quantity", "l_shipdate")
Q1_COLS = Q6_COLS + ("l_returnflag", "l_linestatus", "l_tax")
CAP = 4096


def _lineitem_scan(runner, columns):
    from presto_tpu.plan import nodes as N

    handle = _h("lineitem")
    types = runner.catalogs.get("tpch").metadata().get_table_schema(handle)
    return N.TableScanNode(
        handle, tuple(columns), tuple((c, types[c]) for c in columns)
    )


class _ColumnSpy:
    """Records the column lists asked of the connector's page source."""

    def __init__(self, runner):
        self.conn = runner.catalogs.get("tpch")
        self.orig = self.conn.create_page_source
        self.reads = []

    def __enter__(self):
        def spy(split, columns):
            self.reads.append(tuple(columns))
            return self.orig(split, columns)

        self.conn.create_page_source = spy
        return self

    def __exit__(self, *exc):
        self.conn.create_page_source = self.orig


def _column_runner(budget=1 << 30, pool=None):
    return LocalQueryRunner(
        memory_pool=pool, staging_cache_bytes=budget
    )


def _col_bytes(page, names):
    from presto_tpu.exec.staging import block_nbytes

    return sum(block_nbytes(page.block(c)) for c in names)


@pytest.mark.parametrize(
    "first,second",
    [(Q6_COLS, Q1_COLS), (Q1_COLS, Q6_COLS), (Q1_COLS, Q1_COLS)],
    ids=["q6-then-q1", "q1-then-q6", "q1-twice"],
)
def test_overlapping_scans_stage_each_shared_column_once(first, second):
    """Two scans of one split range whose column sets overlap: the
    second reads and stages only what the first did not, the cache
    holds the union once, and the assembled page equals a fresh
    staging of the same columns."""
    r = _column_runner()
    gc.collect()  # caches of earlier tests die now, not mid-test
    snap0 = device_snapshot()
    with _ColumnSpy(r) as spy:
        a, _ = r.stage_split(_lineitem_scan(r, first), 0, CAP, CAP)
        b, _ = r.stage_split(_lineitem_scan(r, second), 0, CAP, CAP)
    new = tuple(c for c in second if c not in first)
    assert spy.reads == [tuple(first)] + ([new] if new else [])
    union = dict.fromkeys(first + second)
    st = r.split_cache.stats()
    assert st["entries"] == len(union)
    assert st["misses"] == len(union)
    assert st["hits"] == len(second) - len(new)
    assert st["bytes"] == _col_bytes(a, first) + _col_bytes(b, new)
    d = {k: v - snap0[k] for k, v in device_snapshot().items()}
    assert d["stage_col_misses"] == len(union)
    assert d["stage_col_hits"] == len(second) - len(new)
    assert d["h2d_bytes"] == st["bytes"]
    assert d["stage_resident_bytes"] == st["bytes"]
    assert d["stage_evictions"] == 0
    # the shared columns are the SAME device arrays, not copies
    for c in set(first) & set(second):
        assert b.block(c).data is a.block(c).data
    fresh = LocalQueryRunner().stage_split(
        _lineitem_scan(r, second), 0, CAP, CAP
    )[0]
    assert b.names == fresh.names == tuple(second)
    assert b.to_pylist() == fresh.to_pylist()


def test_eviction_and_pins_are_per_column():
    """The LRU budget drops single columns, oldest first, and a pinned
    column stays while its neighbours of the same split range go."""
    probe = _column_runner()
    page, _ = probe.stage_split(_lineitem_scan(probe, Q6_COLS), 0, CAP, CAP)
    one = _col_bytes(page, ("l_quantity",))  # three int64 columns
    budget = _col_bytes(page, Q6_COLS)
    pool = MemoryPool(1 << 30)
    r = _column_runner(budget=budget, pool=pool)
    scan = _lineitem_scan(r, Q6_COLS)
    _, release = r.stage_split(scan, 0, CAP, CAP, owner="q")
    assert r.split_cache.stats()["entries"] == 4
    assert pool.used_bytes(SplitCache.OWNER) == budget
    release()
    # a second range of one column: evicts exactly the oldest column
    # of the first range, not the whole page
    _, release = r.stage_split(
        _lineitem_scan(r, ("l_quantity",)), CAP, 2 * CAP, CAP, owner="q"
    )
    release()
    st = r.split_cache.stats()
    assert (st["evictions"], st["entries"]) == (1, 4)
    assert st["bytes"] <= budget
    assert pool.used_bytes(SplitCache.OWNER) == st["bytes"]
    with _ColumnSpy(r) as spy:
        _, release = r.stage_split(scan, 0, CAP, CAP, owner="q")
    assert spy.reads == [("l_extendedprice",)]  # only the evicted one
    # all four columns of range 0 are pinned now: a further column
    # cannot be admitted over them and accounts to the query instead
    before = r.split_cache.stats()
    _, release2 = r.stage_split(
        _lineitem_scan(r, ("l_tax",)), 2 * CAP, 3 * CAP, CAP, owner="q"
    )
    pinned = {k[1] for k in r.split_cache._entries if k[2] == 0}
    assert pinned == set(Q6_COLS)
    assert pool.used_bytes("q") == one
    release2()
    assert pool.used_bytes("q") == 0
    release()
    assert not r.split_cache._pins
    assert r.split_cache.stats()["evictions"] >= before["evictions"]


def test_invalidate_drops_every_column_of_the_table():
    r = _column_runner()
    r.stage_split(_lineitem_scan(r, Q1_COLS), 0, CAP, CAP)
    r.stage_split(_lineitem_scan(r, Q6_COLS), CAP, 2 * CAP, CAP)
    r.execute("select count(*) from tpch.tiny.region")  # another table
    before = r.split_cache.stats()["entries"]
    assert r.split_cache.invalidate(_h("lineitem")) == 11
    st = r.split_cache.stats()
    assert st["entries"] == before - 11
    assert not any(k[0] == _h("lineitem") for k in r.split_cache._entries)
    with _ColumnSpy(r) as spy:
        r.stage_split(_lineitem_scan(r, Q6_COLS), 0, CAP, CAP)
    assert spy.reads == [Q6_COLS]


def test_runtime_caches_row_counts_columns():
    """``system.runtime.caches`` follows the entries: one a column and
    split range, with the cache's own hit and miss counts."""
    r = _column_runner()
    r.stage_split(_lineitem_scan(r, Q6_COLS), 0, CAP, CAP)
    r.stage_split(_lineitem_scan(r, Q1_COLS), 0, CAP, CAP)
    st = r.split_cache.stats()
    row = r.execute(
        "select entries, bytes, hits, misses, evictions "
        "from system.runtime.caches where cache = 'staging.split_cache'"
    ).rows()
    assert row == [(7, st["bytes"], 4, 7, 0)]


def test_partial_miss_is_a_staging_span_and_a_hit_is_none(monkeypatch):
    """The read + stage of the missing columns runs under
    ``phase("staging", site="stage_column")``, so it lands in
    ``span_ms.staging``; a batch served from resident columns opens no
    span at all."""
    from presto_tpu.utils import tracing

    sites = []
    phase = tracing.phase

    def spy(name, site=""):
        sites.append((name, site))
        return phase(name, site)

    monkeypatch.setattr(tracing, "phase", spy)
    r = _column_runner()
    r.stage_split(_lineitem_scan(r, Q6_COLS), 0, CAP, CAP)
    del sites[:]
    s0 = device_snapshot()["span_ms.staging"]
    r.stage_split(_lineitem_scan(r, Q6_COLS), 0, CAP, CAP)
    assert sites == [] and device_snapshot()["span_ms.staging"] == s0
    r.stage_split(_lineitem_scan(r, Q1_COLS), 0, CAP, CAP)
    assert sites[0] == ("staging", "stage_column")
    assert device_snapshot()["span_ms.staging"] > s0


def test_concurrent_overlapping_scans_keep_the_accounts_straight():
    """Eight drivers stage overlapping column sets of four split ranges
    through a cache half the size of what they touch: whatever the
    interleaving of hits, fills, evictions and duplicate stagings,
    every page holds its range's rows, no pin and no query reservation
    survives, and the pool's cache owner equals the cache's bytes."""
    import sys
    import threading

    probe = _column_runner()
    sums = {}
    for i in range(4):
        page = probe.stage_split(
            _lineitem_scan(probe, Q1_COLS), i * CAP, (i + 1) * CAP, CAP
        )[0]
        sums[i] = {c: int(page.block(c).data.sum()) for c in Q1_COLS}
    budget = probe.split_cache.used_bytes() // 2
    pool = MemoryPool(1 << 30)
    r = _column_runner(budget=budget, pool=pool)
    errors = []

    def driver(t):
        try:
            for k in range(12):
                cols = Q6_COLS if (t + k) % 2 else Q1_COLS
                i = (t + k) % 4
                page, release = r.stage_split(
                    _lineitem_scan(r, cols), i * CAP, (i + 1) * CAP, CAP,
                    owner=f"q{t}",
                )
                try:
                    got = {c: int(page.block(c).data.sum()) for c in cols}
                    assert got == {c: sums[i][c] for c in cols}, (t, k)
                    assert r.split_cache.used_bytes() <= budget
                finally:
                    release()
        except BaseException as e:
            errors.append(e)

    interval = sys.getswitchinterval()
    sys.setswitchinterval(1e-5)
    try:
        threads = [threading.Thread(target=driver, args=(t,)) for t in range(8)]
        for th in threads:
            th.start()
        for th in threads:
            th.join(60)
    finally:
        sys.setswitchinterval(interval)
    assert not any(th.is_alive() for th in threads) and not errors, errors
    assert not r.split_cache._pins
    assert pool.used_bytes() == pool.used_bytes(SplitCache.OWNER)
    assert pool.used_bytes(SplitCache.OWNER) == r.split_cache.used_bytes()
    assert r.split_cache.stats()["evictions"] > 0


def test_failed_read_leaves_no_pin_behind():
    """A page source that raises on a partial miss must not leave the
    columns that did hit pinned for ever."""
    r = _column_runner()
    r.stage_split(_lineitem_scan(r, Q6_COLS), 0, CAP, CAP)

    def boom(columns):
        raise OSError("disk on fire")

    with pytest.raises(OSError):
        r.stage_split(
            _lineitem_scan(r, Q1_COLS), 0, CAP, CAP, owner="q",
            page_source=boom,
        )
    assert not r.split_cache._pins


def test_spilled_columns_restage_one_by_one():
    """The host-spill lane moves single columns: after pool pressure
    every column is in host RAM, and a narrower scan restages only its
    own."""
    r = _column_runner(pool=MemoryPool(1 << 30))
    r.split_cache.set_spill_budget(64 << 20)
    scan = _lineitem_scan(r, Q1_COLS)
    want = r.stage_split(scan, 0, CAP, CAP)[0].to_pylist()
    assert r.split_cache.evict_bytes(1 << 30) > 0
    st = r.split_cache.stats()
    assert (st["bytes"], st["spill_entries"]) == (0, 7)
    with _ColumnSpy(r) as spy:
        r.stage_split(_lineitem_scan(r, Q6_COLS), 0, CAP, CAP)
        st = r.split_cache.stats()
        assert (st["restages"], st["spill_entries"]) == (4, 3)
        got = r.stage_split(scan, 0, CAP, CAP)[0].to_pylist()
    assert spy.reads == []
    assert got == want


# -------------------------------------------------- prefetch pipeline


def test_prefetch_iter_orders_and_depth_zero_equivalence():
    items = list(range(7))
    serial = list(prefetch_iter(items, lambda x: x * x, 0))
    piped = list(prefetch_iter(items, lambda x: x * x, 2))
    assert serial == piped == [x * x for x in items]


def test_prefetch_iter_propagates_errors():
    def load(x):
        if x == 3:
            raise ValueError("boom")
        return x

    got = []
    with pytest.raises(ValueError, match="boom"):
        for v in prefetch_iter(range(6), load, 2):
            got.append(v)
    assert got == [0, 1, 2]


def _streamed_runner():
    return LocalQueryRunner(
        session=Session(
            properties={
                "max_device_rows": 16_384,
                "page_capacity": 4_096,
            }
        )
    )


STREAMED_Q = (
    "select l_returnflag, sum(l_quantity) as s, count(*) as c "
    "from tpch.tiny.lineitem group by l_returnflag order by l_returnflag"
)


def test_prefetch_depth_zero_bit_identical():
    """Staging ahead changes when a batch is staged, not what: the
    serial loop (depth 0) and depth 2 yield the same staged batches of
    the same split ranges in the same order."""
    # budget 0: both passes really read and stage every batch
    r = LocalQueryRunner(staging_cache_bytes=0)
    scan = _lineitem_scan(r, Q1_COLS)
    ranges = [(lo, lo + CAP) for lo in range(0, 5 * CAP, CAP)]

    def load(rng):
        return r.stage_split(scan, rng[0], rng[1], CAP)[0].to_pylist()

    serial = list(prefetch_iter(ranges, load, 0))
    ahead = list(prefetch_iter(ranges, load, 2))
    assert len(serial) == len(ranges) and serial == ahead
    assert r.split_cache.stats()["entries"] == 0


def test_prefetch_spans_overlap_execute():
    """The trace of a multi-split scan shows stage:prefetch spans
    overlapping the open execute span (the compute/transfer overlap
    EXPLAIN ANALYZE is supposed to make visible)."""
    r = _streamed_runner()
    r.execute(STREAMED_Q)
    qs = r.history.snapshot()[-1]
    spans = qs.trace.spans()
    execute = next(s for s in spans if s.name == "execute")
    prefetch = [s for s in spans if s.name == "stage:prefetch"]
    assert prefetch, "prefetch staging must be traced"
    overlapping = [
        s
        for s in prefetch
        if s.start < execute.end and execute.start < s.end
    ]
    assert overlapping, "prefetch spans must overlap execution"


# ------------------------------------- worker hot path (distributed)


def _wait_workers(coord, n, timeout=10.0):
    deadline = time.time() + timeout
    while time.time() < deadline:
        if len(coord.active_workers()) >= n:
            return
        time.sleep(0.05)
    raise TimeoutError("workers not discovered")


def test_worker_warm_task_reports_cache_hits():
    from presto_tpu.server import CoordinatorServer, WorkerServer
    from presto_tpu.server.client import PrestoTpuClient

    coord = CoordinatorServer().start()
    w = WorkerServer(coordinator_uri=coord.uri).start()
    try:
        assert w.runner.split_cache.budget > 0
        _wait_workers(coord, 1)
        client = PrestoTpuClient(coord.uri, timeout_s=60)
        q = "select count(*) as c from tpch.tiny.orders"
        cold = client.execute(q)
        assert cold.rows() == [(15000,)]
        warm = client.execute(q)
        assert warm.rows() == [(15000,)]
        info = client.query_info(warm.query_id)
        hits = sum(
            t.get("staging_cache_hits", 0)
            for st in info["stages"]
            for t in st["tasks"]
        )
        assert hits > 0, "warm task must serve splits from the cache"
        assert info.get("staging_cache_hits", 0) > 0  # query rollup
    finally:
        w.shutdown(graceful=False)
        coord.shutdown()


def test_served_mix_stages_only_columns_not_yet_resident():
    """Q6, Q1, Q6 through client -> coordinator -> worker with lineitem
    streamed in batches: every result equals the benchmark's numpy
    reference, Q1 adds host->device bytes only for the three columns
    Q6 did not scan, and the third statement adds none."""
    import zlib

    from benchmark import discovery
    from benchmark.data import HostData
    from presto_tpu.connectors.tpch import TpchConnector
    from presto_tpu.server import CoordinatorServer, WorkerServer
    from presto_tpu.server.client import PrestoTpuClient

    here = os.path.dirname(os.path.abspath(discovery.__file__))
    data = HostData(TpchConnector(), "tpch", "tiny")
    coord = CoordinatorServer(
        session=Session(
            properties={"max_device_rows": 16_384, "page_capacity": 4_096}
        )
    ).start()
    w = WorkerServer(coordinator_uri=coord.uri).start()
    reads = []
    load_range = w._load_range

    def spy(scan, lo, hi, columns):
        reads.append((scan.handle.table, lo, tuple(columns)))
        return load_range(scan, lo, hi, columns)

    w._load_range = spy
    try:
        _wait_workers(coord, 1)
        client = PrestoTpuClient(coord.uri, timeout_s=120)
        cache = w.runner.split_cache
        seen = []
        for name in ("q6", "q1", "q6"):
            mod = discovery.load_module(
                os.path.join(here, "statements", name + ".py")
            )
            rng = np.random.default_rng([5, zlib.crc32(name.encode())])
            p = mod.params(rng, data)
            before = cache.stats()
            del reads[:]
            rows = client.execute(mod.sql("tpch.tiny", p, "t")).rows()
            assert mod.compare(
                [tuple(r) for r in rows], mod.reference(data, p)
            ) is None, (name, p)
            after = cache.stats()
            seen.append({
                "batches": len({lo for _t, lo, _c in reads}),
                "columns": {c for _t, _lo, cols in reads for c in cols},
                "bytes": after["bytes"] - before["bytes"],
                "misses": after["misses"] - before["misses"],
                "hits": after["hits"] - before["hits"],
            })
        q6, q1, again = seen
        assert q6["batches"] >= 10  # lineitem really streams
        assert q6["columns"] == set(Q6_COLS)
        assert q6["misses"] == 4 * q6["batches"] and q6["hits"] == 0
        assert q1["columns"] == set(Q1_COLS) - set(Q6_COLS)
        assert q1["misses"] == 3 * q6["batches"]
        assert q1["hits"] == 4 * q6["batches"]
        # two int32 dictionary columns and one int64 against three
        # int64 and one int32: Q1 adds 16 bytes a row to Q6's 28
        assert q1["bytes"] * 28 == q6["bytes"] * 16
        assert again["columns"] == set() and again["bytes"] == 0
        assert (again["misses"], again["hits"]) == (0, 4 * q6["batches"])
        assert cache.stats()["evictions"] == 0 and not cache._pins
    finally:
        w.shutdown(graceful=False)
        coord.shutdown()


def test_served_q1_partial_pages_sized_by_key_domain(monkeypatch):
    """Q1 through client -> coordinator -> worker with batches smaller
    than the plan's ``max_groups``: every partial program returns a
    1,024-slot page (the bucket of the proved key domain, not the
    planner's row bucket), the rows equal the numpy reference, and
    ``program_out_bytes`` grows by the static size of each dispatched
    program's output page."""
    import jax

    from benchmark import discovery
    from benchmark.data import HostData
    from presto_tpu.connectors.tpch import TpchConnector
    from presto_tpu.exec import local_runner
    from presto_tpu.plan import nodes as N
    from presto_tpu.server import CoordinatorServer, WorkerServer
    from presto_tpu.server.client import PrestoTpuClient

    here = os.path.dirname(os.path.abspath(discovery.__file__))
    mod = discovery.load_module(os.path.join(here, "statements", "q1.py"))
    data = HostData(TpchConnector(), "tpch", "tiny")
    coord = CoordinatorServer(
        session=Session(
            properties={"max_device_rows": 16_384, "page_capacity": 4_096}
        )
    ).start()
    w = WorkerServer(coordinator_uri=coord.uri).start()

    outputs = []  # (capacity, static bytes) of every dispatched program
    static_nbytes = local_runner._static_page_nbytes

    def spy_nbytes(page):
        n = static_nbytes(page)
        if not isinstance(page.blocks[0].data, jax.core.Tracer):
            outputs.append((page.capacity, n))
        return n

    monkeypatch.setattr(local_runner, "_static_page_nbytes", spy_nbytes)
    partials = []  # (plan's max_groups, batch capacity) per worker batch
    dispatch = w.runner._dispatch

    def spy_dispatch(resolved, pages):
        aggs = [
            n for n in N.walk(resolved.root)
            if isinstance(n, N.AggregationNode)
        ]
        partials.append((aggs[0].max_groups, pages[0].capacity))
        return dispatch(resolved, pages)

    w.runner._dispatch = spy_dispatch
    try:
        _wait_workers(coord, 1)
        client = PrestoTpuClient(coord.uri, timeout_s=120)
        p = mod.params(np.random.default_rng(30), data)
        before = device_snapshot()
        rows = client.execute(mod.sql("tpch.tiny", p, "t")).rows()
        after = device_snapshot()
        assert mod.compare(
            [tuple(r) for r in rows], mod.reference(data, p)
        ) is None
        assert len(partials) >= 10  # lineitem really streams
        assert all(cap == 4_096 < mg for mg, cap in partials), partials
        assert after["dispatches"] - before["dispatches"] == len(outputs)
        assert len(outputs) >= len(partials)
        assert {cap for cap, _ in outputs} == {1_024}, outputs
        grew = after["program_out_bytes"] - before["program_out_bytes"]
        assert grew == sum(n for _, n in outputs) > 0
        # thirteen blocks a row, none of them max_groups long
        assert max(n for _, n in outputs) < 1_024 * 13 * 9
    finally:
        w.shutdown(graceful=False)
        coord.shutdown()


def test_worker_cache_disabled_by_zero_budget():
    """A zero budget is the whole off switch: the worker looks its
    columns up, is told "not resident", and reads the connector for
    the same statement again."""
    from presto_tpu.server import CoordinatorServer, WorkerServer
    from presto_tpu.server.client import PrestoTpuClient

    coord = CoordinatorServer().start()
    w = WorkerServer(
        coordinator_uri=coord.uri,
        config=NodeConfig({"staging.cache-bytes": "0"}),
    ).start()
    reads = []
    load_range = w._load_range

    def spy(scan, lo, hi, columns):
        reads.append((lo, hi, tuple(columns)))
        return load_range(scan, lo, hi, columns)

    w._load_range = spy
    try:
        assert w.runner.split_cache.budget == 0
        _wait_workers(coord, 1)
        client = PrestoTpuClient(coord.uri, timeout_s=60)
        q = "select sum(o_totalprice) as s from tpch.tiny.orders"
        first = client.execute(q).rows()
        cold = list(reads)
        assert cold and all(cols for _, _, cols in cold)
        assert client.execute(q).rows() == first
        assert sorted(reads[len(cold):]) == sorted(cold)
        assert w.runner.split_cache.stats()["entries"] == 0
    finally:
        w.shutdown(graceful=False)
        coord.shutdown()


# ----------------------------------------- pipelined exchange pulls


@pytest.mark.parametrize("pull_depth", [1, 2, 3])
def test_pull_depth_results_identical(monkeypatch, pull_depth):
    """Multi-page pulls return every page exactly once at any depth
    (the X-Ack floor keeps speculative requests from freeing
    unconsumed pages)."""
    from presto_tpu.server import CoordinatorServer, WorkerServer
    from presto_tpu.server import worker as worker_mod
    from presto_tpu.server.client import PrestoTpuClient

    monkeypatch.setattr(worker_mod, "PAGE_ROWS", 512)
    coord = CoordinatorServer(
        config=NodeConfig({"rpc.pull-depth": str(pull_depth)})
    ).start()
    w = WorkerServer(coordinator_uri=coord.uri).start()
    try:
        _wait_workers(coord, 1)
        client = PrestoTpuClient(coord.uri, timeout_s=60)
        res = client.execute(
            "select c_custkey from tpch.tiny.customer"
        )
        got = sorted(r[0] for r in res.rows())
        assert len(got) == 1500
        assert got == list(range(1, 1501))
    finally:
        w.shutdown(graceful=False)
        coord.shutdown()


# ------------------------------------------- adaptive compression


def test_wire_small_buffer_ships_raw():
    from presto_tpu.server import pages_wire

    data = np.arange(4, dtype=np.int64)
    buf = pages_wire.serialize_page([("x", data, None, T.BIGINT, None)], 4)
    import json as _json
    import struct

    (hlen,) = struct.unpack_from("<I", buf, 4)
    header = _json.loads(buf[8 : 8 + hlen].decode())
    col = header["columns"][0]
    assert col["enc"] == "raw"
    assert col["comp_size"] == col["raw_size"]
    payload, schema, n = pages_wire.deserialize_page(buf)
    assert n == 4
    np.testing.assert_array_equal(payload["x"], data)


def test_wire_compressible_buffer_still_zlib():
    from presto_tpu.server import pages_wire

    data = np.zeros(100_000, dtype=np.int64)
    buf = pages_wire.serialize_page(
        [("x", data, None, T.BIGINT, None)], len(data)
    )
    import json as _json
    import struct

    (hlen,) = struct.unpack_from("<I", buf, 4)
    col = _json.loads(buf[8 : 8 + hlen].decode())["columns"][0]
    assert col["enc"] == "zlib"
    assert col["comp_size"] < col["raw_size"]
    payload, _schema, _n = pages_wire.deserialize_page(buf)
    np.testing.assert_array_equal(payload["x"], data)


def test_wire_incompressible_buffer_skips_zlib():
    from presto_tpu.server import pages_wire

    rng = np.random.default_rng(7)
    data = rng.integers(0, 2**62, size=100_000, dtype=np.int64)
    buf = pages_wire.serialize_page(
        [("x", data, None, T.BIGINT, None)], len(data)
    )
    import json as _json
    import struct

    (hlen,) = struct.unpack_from("<I", buf, 4)
    col = _json.loads(buf[8 : 8 + hlen].decode())["columns"][0]
    assert col["enc"] == "raw"
    payload, _schema, _n = pages_wire.deserialize_page(buf)
    np.testing.assert_array_equal(payload["x"], data)


def test_wire_legacy_frame_without_enc_decodes():
    """Backward compat: a header with no enc fields reads as zlib."""
    import json as _json
    import struct
    import zlib

    from presto_tpu.server import pages_wire

    data = np.arange(1000, dtype=np.int64)
    raw = data.tobytes()
    comp = zlib.compress(raw, 1)
    header = {
        "nrows": 1000,
        "columns": [
            {
                "name": "x",
                "type": "bigint",
                "np_dtype": data.dtype.str,
                "comp_size": len(comp),
                "raw_size": len(raw),
                "crc32": zlib.crc32(raw),
            }
        ],
    }
    hj = _json.dumps(header).encode()
    buf = b"".join([b"PTP1", struct.pack("<I", len(hj)), hj, comp])
    payload, schema, n = pages_wire.deserialize_page(buf)
    assert n == 1000
    np.testing.assert_array_equal(payload["x"], data)


# The lint wiring that lived here moved to tests/test_static_analysis.py
# (the one gate running every tools/analysis pass; the tools/check_*.py CLI
# this suite used to invoke is now a shim over the same framework).
