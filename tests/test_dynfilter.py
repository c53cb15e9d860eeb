"""Dynamic filtering (exec/dynfilter.py): build-side runtime filters
pushed into probe-side scans and split pruning.

Covers the PR-4 acceptance surface: oracle/dual-path equality with
filtering on vs off across join types (inner/semi, dictionary string
keys, empty build side), distributed connector-level pruning
(``dynamic_filter.splits_pruned > 0`` on a hive-partitioned probe
scan), the bounded wait (slow/killed build degrades to the unfiltered
plan), native-dtype bound conservativeness (the float32/int64
truncation regression), parquet row-group / ORC stripe min-max
pruning, the distributed fuzz toggle, and the summary-site lint.
"""

import os
import time

import numpy as np
import pytest

pa = pytest.importorskip("pyarrow")
import pyarrow.parquet as pq  # noqa: E402

from presto_tpu import types as T  # noqa: E402
from presto_tpu.connectors import create_connector  # noqa: E402
from presto_tpu.connectors.spi import (  # noqa: E402
    RangeSet,
    TableHandle,
)
from presto_tpu.exec import dynfilter  # noqa: E402
from presto_tpu.exec.local_runner import LocalQueryRunner  # noqa: E402
from presto_tpu.exec.staging import CatalogManager  # noqa: E402
from presto_tpu.utils import faults  # noqa: E402
from presto_tpu.utils.metrics import REGISTRY  # noqa: E402
from presto_tpu.utils.telemetry import device_snapshot  # noqa: E402


@pytest.fixture(autouse=True)
def clear_fault_plane():
    yield
    faults.configure(None)


def _counter(name: str) -> int:
    return REGISTRY.counter(name).total


def _on_off(runner, sql):
    """Execute with dynamic filtering ON then OFF; return both row
    lists (session state restored)."""
    saved = str(runner.session.get("enable_dynamic_filtering"))
    try:
        runner.session.set("enable_dynamic_filtering", "true")
        on = runner.execute(sql).rows()
        runner.session.set("enable_dynamic_filtering", "false")
        off = runner.execute(sql).rows()
    finally:
        runner.session.set("enable_dynamic_filtering", saved)
    return on, off


# ------------------------------------------------------- local runner


@pytest.fixture(scope="module")
def runner():
    r = LocalQueryRunner()
    r.catalogs.register("memory", create_connector("memory"))
    # small fragment budget: every join plan runs stage-at-a-time, so
    # the build side executes first and the dynamic filter engages
    r.session.set("max_fragment_weight", "6")
    r.execute(
        "create table memory.default.fact_str "
        "(id bigint, tag varchar)"
    )
    r.execute(
        "insert into memory.default.fact_str values "
        "(1, 'a'), (2, 'b'), (3, 'c'), (4, 'd'), (5, 'b')"
    )
    r.execute("create table memory.default.fact_n (id bigint, k bigint)")
    r.execute(
        "insert into memory.default.fact_n values "
        "(1, 10), (2, 20), (3, 30), (4, 40), (5, 20)"
    )
    r.execute("create table memory.default.dim_n (k bigint)")
    r.execute("insert into memory.default.dim_n values (20), (30)")
    return r


def test_inner_join_on_off_equal(runner):
    sql = (
        "select count(*) as n, sum(l_extendedprice) as s "
        "from tpch.tiny.lineitem l join tpch.tiny.part p "
        "on l.l_partkey = p.p_partkey "
        "where p.p_container = 'MED BOX'"
    )
    pruned0 = _counter("dynamic_filter.rows_pruned")
    on, off = _on_off(runner, sql)
    assert on == off
    assert _counter("dynamic_filter.rows_pruned") > pruned0, (
        "the selective build side should prune probe rows"
    )


def test_semi_join_on_off_equal(runner):
    sql = (
        "select count(*) as n from tpch.tiny.lineitem "
        "where l_orderkey in (select o_orderkey from tpch.tiny.orders "
        "where o_totalprice > 400000)"
    )
    on, off = _on_off(runner, sql)
    assert on == off


def test_dict_string_key_on_off_equal(runner):
    """Dictionary-encoded string join keys summarize as a present-id
    LUT resolved through the dictionary into an IN-list of VALUES
    (same-dictionary self-join: the fragmented executor's supported
    string-join shape)."""
    sql = (
        "select count(*) as n from memory.default.fact_str a join "
        "(select tag from memory.default.fact_str where id >= 4) b "
        "on a.tag = b.tag"
    )
    pruned0 = _counter("dynamic_filter.rows_pruned")
    on, off = _on_off(runner, sql)
    assert on == off == [(3,)]
    assert _counter("dynamic_filter.rows_pruned") > pruned0, (
        "tags outside the build's dictionary subset should be pruned"
    )


def test_empty_build_on_off_equal(runner):
    sql = (
        "select count(*) as n from tpch.tiny.lineitem l "
        "join tpch.tiny.part p on l.l_partkey = p.p_partkey "
        "where p.p_name = 'zzz_no_such_part'"
    )
    on, off = _on_off(runner, sql)
    assert on == off == [(0,)]


def test_left_outer_join_not_filtered(runner):
    """Outer joins preserve unmatched probe rows: the dynamic filter
    must NOT engage (and results must match either way)."""
    sql = (
        "select count(*) as n from memory.default.fact_n f "
        "left join memory.default.dim_n d on f.k = d.k"
    )
    on, off = _on_off(runner, sql)
    assert on == off == [(5,)]


# --------------------------------------- native-dtype bound regression


def test_float32_bounds_native_dtype():
    """Bounds of a REAL (float32) build key must be the EXACT float32
    values — not decimal/widened roundings that can exclude matching
    probe rows (the old astype-to-float64 path narrowed to float32
    under x64-off and filled with wrapped iinfo values)."""
    import jax.numpy as jnp

    from presto_tpu.page import Block, Page

    # 0.1 and 16777217 are NOT exactly representable in float32: the
    # stored values differ from the decimal spelling, so a bound
    # computed anywhere but the native dtype risks excluding them
    vals = np.asarray([0.1, 16777217.0, 2.5], dtype=np.float32)
    page = Page(
        blocks=(
            Block(
                data=jnp.asarray(vals), valid=None, dtype=T.REAL
            ),
        ),
        num_valid=jnp.asarray(3, jnp.int32),
        names=("k",),
    )
    conjuncts, n = dynfilter.device_conjuncts(
        page, [("k", "k")], {"k": T.REAL}
    )
    assert n == 1
    between = conjuncts[0]
    lo, hi = between.low.value, between.high.value
    assert lo == float(vals.min()) and hi == float(vals.max())
    # round-tripping the bound back to float32 must be exact
    assert np.float32(lo) == vals.min()
    assert np.float32(hi) == vals.max()


def test_int64_bounds_beyond_int32():
    """int64 keys past 2^31 must not wrap (the old path's
    astype(jnp.int64) + iinfo(int64) fills narrowed under x64-off)."""
    import jax.numpy as jnp

    from presto_tpu.page import Block, Page

    vals = np.asarray(
        [2**31 + 5, 2**31 + 11, 2**33], dtype=np.int64
    )
    page = Page(
        blocks=(
            Block(
                data=jnp.asarray(vals), valid=None, dtype=T.BIGINT
            ),
        ),
        num_valid=jnp.asarray(3, jnp.int32),
        names=("k",),
    )
    conjuncts, n = dynfilter.device_conjuncts(
        page, [("k", "k")], {"k": T.BIGINT}
    )
    assert n == 1
    assert conjuncts[0].low.value == 2**31 + 5
    assert conjuncts[0].high.value == 2**33


def test_real_key_join_on_off_equal():
    """End-to-end: REAL join keys straddling float32 rounding stay
    matched under dynamic filtering."""
    r = LocalQueryRunner()
    r.catalogs.register("memory", create_connector("memory"))
    r.session.set("max_fragment_weight", "6")
    r.execute("create table memory.default.dimf (x real)")
    r.execute(
        "insert into memory.default.dimf values (0.1), (16777217.0)"
    )
    r.execute("create table memory.default.factf (x real, v bigint)")
    r.execute(
        "insert into memory.default.factf values "
        "(0.1, 1), (16777217.0, 2), (99.5, 3)"
    )
    sql = (
        "select count(*) as n, sum(f.v) as s "
        "from memory.default.factf f "
        "join memory.default.dimf d on f.x = d.x"
    )
    on, off = _on_off(r, sql)
    assert on == off == [(2, 3)]


def test_nan_build_keys_do_not_poison_bounds():
    """NaN float build keys match nothing but must NOT read as an
    empty build (NaN min/max would emit constant-false and drop REAL
    matches)."""
    import jax.numpy as jnp

    from presto_tpu.page import Block, Page

    vals = np.asarray([1.0, np.nan, 5.0], dtype=np.float64)
    page = Page(
        blocks=(
            Block(
                data=jnp.asarray(vals), valid=None, dtype=T.DOUBLE
            ),
        ),
        num_valid=jnp.asarray(3, jnp.int32),
        names=("k",),
    )
    conjuncts, n = dynfilter.device_conjuncts(
        page, [("k", "k")], {"k": T.DOUBLE}
    )
    assert n == 1
    assert (conjuncts[0].low.value, conjuncts[0].high.value) == (1.0, 5.0)


# ------------------------------------------------- summary unit tests


def test_summary_merge_and_json_roundtrip():
    a = dynfilter.ColumnFilter(
        column="k", lo=5, hi=9, values=(5, 7, 9), empty=False
    )
    b = dynfilter.ColumnFilter(
        column="k", lo=1, hi=6, values=(1, 6), empty=False
    )
    m = a.merge(b, ndv_limit=10)
    assert (m.lo, m.hi) == (1, 9)
    assert m.values == (1, 5, 6, 7, 9)
    # NDV overflow drops the value set, keeps bounds
    m2 = a.merge(b, ndv_limit=3)
    assert m2.values is None and (m2.lo, m2.hi) == (1, 9)
    # empty merges are identity
    e = dynfilter.ColumnFilter(column="k")
    assert e.merge(a, 10) == a and a.merge(e, 10) == a
    s = dynfilter.FilterSummary(columns=(a,))
    assert dynfilter.FilterSummary.from_json(s.to_json()) == s


def test_to_constraint_forms():
    s = dynfilter.subset_summary([
        dynfilter.ColumnFilter(
            column="a", lo=1, hi=4, values=(1, 4), empty=False
        ),
        dynfilter.ColumnFilter(column="b", lo=2.5, hi=9.5, empty=False),
        dynfilter.ColumnFilter(column="c"),
    ])
    con = dynfilter.to_constraint(
        s, [("a", T.BIGINT), ("b", T.DOUBLE), ("c", T.BIGINT)]
    )
    d = dict(con)
    assert d["a"] == (1, 4)
    assert d["b"] == RangeSet(lo=2.5, hi=9.5)
    assert d["c"] == ()  # empty build: nothing matches


# --------------------------------------- connector-level split pruning


def test_parquet_rowgroup_pruning(tmp_path):
    (tmp_path / "s").mkdir()
    n = 1000
    pq.write_table(
        pa.table({"k": pa.array(np.arange(n, dtype=np.int64))}),
        tmp_path / "s" / "t.parquet",
        row_group_size=100,
    )
    conn = create_connector("parquet", root=str(tmp_path))
    h = TableHandle("pq", "s", "t")
    base = conn.get_splits(h, target_split_rows=100)._splits
    kept = conn.get_splits(
        h,
        target_split_rows=100,
        constraint=(("k", RangeSet(lo=250, hi=349)),),
    )._splits
    assert len(kept) < len(base)
    covered = sum(s.row_end - s.row_start for s in kept)
    assert covered <= 200  # at most two row groups survive
    # surviving splits still contain every matching row
    rows = []
    for s in kept:
        rows.extend(conn.create_page_source(s, ["k"])["k"].tolist())
    assert set(range(250, 350)) <= set(rows)
    # empty value set (empty build): nothing is read
    none = conn.get_splits(
        h, target_split_rows=100, constraint=(("k", ()),)
    )._splits
    assert sum(s.row_end - s.row_start for s in none) == 0


def test_orc_stripe_pruning(tmp_path):
    orc = pytest.importorskip("pyarrow.orc")
    (tmp_path / "s").mkdir()
    n = 200_000  # several stripes even at the 64 KiB stripe floor
    orc.write_table(
        pa.table({"k": pa.array(np.arange(n, dtype=np.int64))}),
        tmp_path / "s" / "t.orc",
        stripe_size=65536,
    )
    conn = create_connector("orc", root=str(tmp_path))
    h = TableHandle("orc", "s", "t")
    base = conn.get_splits(h, target_split_rows=1)._splits
    if len(base) < 2:
        pytest.skip("writer produced a single stripe")
    kept = conn.get_splits(
        h,
        target_split_rows=1,
        constraint=(("k", RangeSet(lo=0, hi=10)),),
    )._splits
    assert len(kept) < len(base)
    rows = []
    for s in kept:
        rows.extend(conn.create_page_source(s, ["k"])["k"].tolist())
    assert set(range(0, 11)) <= set(rows)


def test_pruned_ranges_middle_rowgroup(tmp_path):
    """Pruning the MIDDLE of a coalesced split increases the split
    count while still saving reads: the decision must compare covered
    rows, not split counts (review regression)."""
    from types import SimpleNamespace

    from presto_tpu.plan import nodes as N
    from presto_tpu.server.coordinator import CoordinatorServer
    from presto_tpu.server.coordinator import _Query

    (tmp_path / "s").mkdir()
    n = 300
    pq.write_table(
        pa.table({"k": pa.array(np.arange(n, dtype=np.int64))}),
        tmp_path / "s" / "t.parquet",
        row_group_size=100,
    )
    cats = CatalogManager()
    cats.register("tpch", create_connector("tpch"))
    cats.register("pq", create_connector("parquet", root=str(tmp_path)))
    coord = CoordinatorServer(catalogs=cats)
    try:
        scan = N.TableScanNode(
            handle=TableHandle("pq", "s", "t"),
            columns=("k",),
            schema=(("k", T.BIGINT),),
        )
        q = _Query("q_t0", "test")
        ranges = coord._pruned_ranges(
            q,
            SimpleNamespace(partition_rows=n),
            scan,
            (("k", RangeSet(lo=0, hi=49)),),  # prunes groups 2+3
        )
        assert ranges is not None
        assert sum(hi - lo for lo, hi in ranges) <= 100
        # middle-ONLY pruning: the one coalesced [0,300) split becomes
        # TWO surviving splits — count comparison would read that as
        # "nothing pruned"; covered rows must decide
        ranges2 = coord._pruned_ranges(
            q,
            SimpleNamespace(partition_rows=n),
            scan,
            (("k", (50, 250)),),  # group 2 (100..199) can't match
        )
        assert ranges2 is not None
        assert sum(hi - lo for lo, hi in ranges2) == 200
        assert (100, 200) not in [
            (lo, hi) for lo, hi in ranges2
        ]
    finally:
        coord.shutdown()


# ------------------------------------------------- distributed cluster


def _wait_workers(coord, n, timeout=10.0):
    deadline = time.monotonic() + timeout
    while time.monotonic() < deadline:
        if len(coord.active_workers()) >= n:
            return
        time.sleep(0.05)
    raise TimeoutError("workers not discovered")


@pytest.fixture(scope="module")
def warehouse(tmp_path_factory):
    """Hive-partitioned probe table: year=2022..2025 partitions."""
    root = tmp_path_factory.mktemp("dynf_warehouse")
    rng = np.random.RandomState(11)
    expected = {}
    i = 0
    for year in (2022, 2023, 2024, 2025):
        d = root / "sales" / "orders" / f"year={year}"
        d.mkdir(parents=True)
        n = 150
        amt = rng.randint(1, 100, n).astype(np.int64)
        pq.write_table(
            pa.table(
                {
                    "id": pa.array(
                        np.arange(i, i + n, dtype=np.int64)
                    ),
                    "amount": pa.array(amt),
                }
            ),
            d / "part-0.parquet",
            row_group_size=64,
        )
        expected[year] = (n, int(amt.sum()))
        i += n
    return root, expected


@pytest.fixture(scope="module")
def cluster(warehouse):
    from presto_tpu.server import (
        CoordinatorServer,
        PrestoTpuClient,
        WorkerServer,
    )

    root, _ = warehouse
    mem = create_connector("memory")

    def catalogs():
        c = CatalogManager()
        c.register("tpch", create_connector("tpch"))
        c.register("hive", create_connector("hive", root=str(root)))
        c.register("memory", mem)  # shared: writes visible cluster-wide
        return c

    coord = CoordinatorServer(catalogs=catalogs()).start()
    workers = [
        WorkerServer(coordinator_uri=coord.uri, catalogs=catalogs())
        .start()
        for _ in range(2)
    ]
    _wait_workers(coord, 2)
    client = PrestoTpuClient(coord.uri, timeout_s=300)
    client.execute("create table memory.default.dim (y bigint)")
    client.execute("insert into memory.default.dim values (2024)")
    yield coord, workers, client
    faults.configure(None)
    for w in workers:
        w.shutdown(graceful=False)
    coord.shutdown()


_JOIN_SQL = (
    "select count(*) as n, sum(o.amount) as s "
    "from hive.sales.orders o "
    "join memory.default.dim d on o.year = d.y"
)


def _set_session(coord, key, value):
    coord.local.session.set(key, value)


def test_distributed_splits_pruned(cluster, warehouse):
    """The acceptance headline: a selective build prunes hive
    partitions of the probe scan at SPLIT level, and filtering off
    reproduces the same results."""
    coord, _workers, client = cluster
    _, expected = warehouse
    splits0 = _counter("dynamic_filter.splits_pruned")
    built0 = _counter("dynamic_filter.built")
    on = client.execute(_JOIN_SQL)
    assert on.data == [[expected[2024][0], expected[2024][1]]]
    assert _counter("dynamic_filter.splits_pruned") > splits0
    assert _counter("dynamic_filter.built") > built0
    # per-query stats rolled into QueryInfo
    q = coord.queries[on.query_id]
    assert q.stats.dynamic_filter_splits_pruned > 0
    assert q.stats.dynamic_filters > 0
    assert q.stats.dynamic_filter_wait_ms > 0
    info = coord.query_info(q)
    assert info["dynamic_filter_splits_pruned"] > 0
    # dynfilter span recorded on the query trace
    names = {s.name for s in q.trace.spans()}
    assert "dynfilter" in names
    # OFF must reproduce the results exactly (and prune nothing)
    _set_session(coord, "enable_dynamic_filtering", "false")
    try:
        splits1 = _counter("dynamic_filter.splits_pruned")
        off = client.execute(_JOIN_SQL)
        assert off.data == on.data
        assert _counter("dynamic_filter.splits_pruned") == splits1
    finally:
        _set_session(coord, "enable_dynamic_filtering", "true")


def test_distributed_explain_analyze_renders_dynfilter(cluster):
    _coord, _workers, client = cluster
    res = client.execute("explain analyze " + _JOIN_SQL)
    text = "\n".join(r[0] for r in res.data)
    assert "dynamic filtering:" in text
    assert "splits_pruned" in text


def test_wait_timeout_proceeds_unfiltered(cluster, warehouse):
    """A zero wait budget expires before any summary arrives: the
    probe runs the exact unfiltered plan, correctly."""
    coord, _workers, client = cluster
    _, expected = warehouse
    built0 = _counter("dynamic_filter.built")
    expired0 = _counter("dynamic_filter.wait_expired")
    snap0 = device_snapshot()["dynamic_filter_wait_expired"]
    _set_session(coord, "dynamic_filtering_wait_ms", "0")
    try:
        res = client.execute(_JOIN_SQL)
    finally:
        _set_session(coord, "dynamic_filtering_wait_ms", "2000")
    assert res.data == [[expected[2024][0], expected[2024][1]]]
    assert _counter("dynamic_filter.built") == built0
    assert _counter("dynamic_filter.wait_expired") > expired0
    # served where the benchmark's window line reads the counters
    assert device_snapshot()["dynamic_filter_wait_expired"] > snap0


def test_build_worker_kill_degrades_to_unfiltered(cluster, warehouse):
    """Chaos: the worker executing a build-summary task dies abruptly
    mid-filter. The wait degrades to the unfiltered plan and the
    query still answers correctly on the survivors."""
    from presto_tpu.server import WorkerServer

    coord, workers, client = cluster
    _, expected = warehouse
    # replacement worker keeps the cluster at 2 after the kill
    spare = WorkerServer(
        coordinator_uri=coord.uri,
        catalogs=workers[0].runner.catalogs,
    ).start()
    try:
        _wait_workers(coord, 3)
        faults.configure(
            {
                "rules": [
                    {
                        "action": "kill_worker",
                        "task": ".df.",
                        "count": 1,
                    }
                ]
            }
        )
        res = client.execute(_JOIN_SQL)
        assert res.data == [[expected[2024][0], expected[2024][1]]]
    finally:
        faults.configure(None)
        spare.shutdown(graceful=False)


def test_distributed_fuzz_draw_covers_both_toggles():
    """The per-seed session draw the distributed fuzz path applies
    must exercise dynamic filtering both ON and OFF over a short
    pinned range (the full mesh replay is the slow-tier test below)."""
    from presto_tpu.fuzz import session_draw

    draws = {
        session_draw(s)["enable_dynamic_filtering"] for s in range(8)
    }
    assert draws == {"true", "false"}


@pytest.mark.slow
def test_fuzz_distributed_toggles_dynamic_filtering():
    """The distributed fuzz path draws enable_dynamic_filtering per
    seed (fuzz.session_draw) — a pinned range must stay oracle-exact
    on the mesh (shard_map compiles make this slow-tier)."""
    from presto_tpu.fuzz import run_fuzz_distributed
    from presto_tpu.verifier import SqliteOracle

    failures = run_fuzz_distributed(
        range(0, 8), oracle=SqliteOracle("tiny")
    )
    msg = "\n".join(
        f"seed {s}: {q}\n  -> {str(d)[:300]}"
        for s, q, d in failures[:5]
    )
    assert not failures, f"{len(failures)} fuzz failures:\n{msg}"


# The lint wiring that lived here moved to tests/test_static_analysis.py
# (the one gate running every tools/analysis pass; the tools/check_*.py CLI
# this suite used to invoke is now a shim over the same framework).
