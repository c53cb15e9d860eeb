"""TPC-H Q18 (large volume customer) on the served path, as the benchmark
cell ``sf10_q18_largevolume`` runs it, at ``tpch.tiny`` on the CPU with
the ratio of SF10's ``lineitem`` to ``max_device_rows``: the IN
subquery streams as a stage of its own, one worker's partial pages are
merged on its device, and the few surviving keys feed the probe stage
as a leaf. The answer against the cell's numpy reference and the sqlite
oracle; the device merge against the host merge; the semi join's
push-down against the plan without it. Counts, no times."""

import os
import time

import numpy as np
import pytest

from benchmark import discovery
from benchmark.data import HostData
from presto_tpu import types as T
from presto_tpu.connectors.tpch import TpchConnector
from presto_tpu.exec import streaming as S
from presto_tpu.exec.local_runner import LocalQueryRunner
from presto_tpu.exec.staging import stage_page
from presto_tpu.page import Page
from presto_tpu.plan import nodes as N
from presto_tpu.plan import optimizer
from presto_tpu.server import coordinator as coord_mod
from presto_tpu.server import exchange_spi, pages_wire
from presto_tpu.server import worker as worker_mod
from presto_tpu.server.client import PrestoTpuClient
from presto_tpu.server.coordinator import CoordinatorServer
from presto_tpu.utils.telemetry import device_snapshot
from presto_tpu.verifier import SqliteOracle, diff_results

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
Q18 = discovery.load_module(
    os.path.join(ROOT, "benchmark", "statements", "q18.py")
)
ROWS = 4_096  # rows a split batch: a scan of tiny's lineitem is 15
MAX_ROWS = 4 * ROWS  # lineitem over max_device_rows, as 60 M over 2^24


@pytest.fixture(scope="module")
def cluster():
    coord = CoordinatorServer().start()
    worker = worker_mod.WorkerServer(coordinator_uri=coord.uri).start()
    deadline = time.monotonic() + 30
    while not coord.active_workers():
        assert time.monotonic() < deadline
        time.sleep(0.02)
    coord.local.session.set("page_capacity", ROWS)
    coord.local.session.set("max_device_rows", MAX_ROWS)
    yield coord, worker, PrestoTpuClient(coord.uri)
    worker.shutdown(graceful=False)
    coord.shutdown()


@pytest.fixture(scope="module")
def data():
    return HostData(TpchConnector(), "tpch", "tiny")


@pytest.fixture(scope="module")
def oracle():
    return SqliteOracle("tiny")


def _grew(before):
    return {k: v - before[k] for k, v in device_snapshot().items()
            if isinstance(v, (int, float)) and k in before}


@pytest.mark.parametrize("quantity", [200, 250])
def test_q18_served_equals_the_numpy_reference_and_sqlite(
    quantity, cluster, data, oracle, monkeypatch
):
    coord, worker, client = cluster
    p = {"quantity": quantity}
    text = Q18.sql("tpch.tiny", p, "t")
    loaded = []
    for runner in (worker.runner, coord.local):
        real = runner._load_table

        def spy(scan, _real=real):
            loaded.append(scan.handle.table)
            return _real(scan)

        monkeypatch.setattr(runner, "_load_table", spy)
    chunked = []
    real_chunks = exchange_spi.merge_chunks

    def spy_chunks(*a, **k):
        out = real_chunks(*a, **k)
        chunked.append(None if out is None else len(out))
        return out

    monkeypatch.setattr(exchange_spi, "merge_chunks", spy_chunks)
    before = device_snapshot()
    res = client.execute(text)
    grew = _grew(before)
    rows = res.rows()
    assert rows
    assert Q18.compare(rows, Q18.reference(data, p)) is None
    theirs = oracle.execute(text.replace("tpch.tiny.", ""))
    assert diff_results(rows, theirs, ordered=True) is None
    # lineitem is read by split range only, never staged whole; so is
    # orders, which the semi join filters batch by batch for the build
    assert set(loaded) == {"customer"}, loaded
    # the subquery ran as a stage of its own ahead of the probe stage,
    # each keyed aggregation's partial pages merged on the worker
    kinds = [s["kind"] for s in client.query_info(res.query_id)["stages"]]
    assert kinds[:2] == ["producer", "merge"], kinds
    assert kinds.count("producer") == 2 and kinds.count("merge") == 2
    assert grew["agg_merge_slots"] > 0
    assert grew["host_merge_rows"] == 0
    # the subquery's 15,000 partial rows merge as page-sized chunks of
    # whole groups, as SF10's 15 M do in pages of 2^20 rows
    assert chunked and chunked[0] >= 4, chunked
    # shapes only: a program's run reads the device once at most
    assert 0 < grew["device_syncs"] <= grew["dispatches"]


def test_device_merge_equals_the_host_merge_on_one_partition():
    """Pages of partial groups as one worker's producers leave them (a
    live prefix, padding behind it, a dictionary column, a nullable
    one), merged by ``device_merge`` with no destination vectors and by
    the host (``merge_payloads`` + ``stage_page``): the same rows in the
    same order, bit for bit."""
    schema = {"k": T.BIGINT, "name": T.VARCHAR, "v": T.BIGINT}
    rng = np.random.default_rng(18)
    pages = []
    for i, n in enumerate((700, 0, 1_024, 33, 511)):
        names = [f"n{j}" for j in rng.integers(0, 40, n)]
        vals = [None if x % 7 == 0 else int(x) for x in rng.integers(-50, 50, n)]
        pages.append(Page.from_pydict(
            {"k": list(rng.integers(0, 10**6, n) + i), "name": names, "v": vals},
            schema, capacity=1_024 if n <= 1_024 else 2_048,
        ))
    page, total = exchange_spi.device_merge(
        [[(pg, None) for pg in pages[:2]], [(pg, None) for pg in pages[2:]]],
        0, schema, max_rows=1 << 20,
    )
    host = stage_page(pages_wire.merge_payloads(
        [S._page_to_payload(pg) for pg in pages], schema), schema)
    assert total == sum(int(pg.num_valid) for pg in pages)
    assert page.to_pylist() == host.to_pylist()


@pytest.mark.parametrize("having", ["", "having sum(l_quantity) > 150"])
@pytest.mark.parametrize("keys", ["l_orderkey", "l_orderkey, l_linestatus"])
def test_device_merge_equals_the_host_merge_served(
    keys, having, cluster, monkeypatch
):
    """One statement twice on one worker: its partial pages merged on
    the worker's device, then gathered and merged on the coordinator's
    host (the rule that picks the device refused): every row equal."""
    _, _, client = cluster
    text = (
        f"select {keys}, sum(l_quantity), count(*), min(l_extendedprice), "
        f"max(l_discount) from tpch.tiny.lineitem group by {keys} {having} "
        f"order by {keys}"
    )
    before = device_snapshot()
    on_device = client.execute(text).rows()
    grew = _grew(before)
    assert grew["agg_merge_slots"] > 0 and grew["host_merge_rows"] == 0
    monkeypatch.setattr(coord_mod, "device_merge_slice", lambda *a: "")
    before = device_snapshot()
    on_host = client.execute(text).rows()
    grew = _grew(before)
    assert grew["agg_merge_slots"] == 0 and grew["host_merge_rows"] > 0
    assert on_device == on_host


SEMI = {
    # the IN key on the probe side of the inner join below it
    "probe": (
        "select l_orderkey, count(*) from {s}lineitem, {s}orders "
        "where l_orderkey = o_orderkey and l_partkey in "
        "(select p_partkey from {s}part where p_size < 5) group by l_orderkey"
    ),
    # on its build side: Q18's shape
    "build": (
        "select c_name, o_orderkey, o_totalprice from {s}customer, {s}orders "
        "where c_custkey = o_custkey and o_orderkey in (select l_orderkey "
        "from {s}lineitem group by l_orderkey having sum(l_quantity) > 250)"
    ),
    "exists": (
        "select o_orderkey, c_name from {s}orders, {s}customer "
        "where o_custkey = c_custkey and exists (select 1 from {s}lineitem "
        "where l_orderkey = o_orderkey and l_quantity > 49)"
    ),
}


def _text(name, s="tpch.tiny."):
    return SEMI[name].replace("{s}", s)


def _semi_probes(runner, text):
    """Every semi or anti join of the plan, and the node its probe side
    is."""
    from presto_tpu.plan.planner import plan_statement
    from presto_tpu.sql import parse_statement

    root = plan_statement(
        parse_statement(text), runner.catalogs, runner.session
    ).root
    return [(n.join_type, type(n.left).__name__) for n in N.walk(root)
            if isinstance(n, N.JoinNode) and n.join_type in ("semi", "anti")]


@pytest.mark.parametrize("name", sorted(SEMI))
def test_semi_join_push_down_keeps_every_row(name, oracle, monkeypatch):
    text = _text(name)
    pushed = LocalQueryRunner().execute(text).rows()
    monkeypatch.setattr(optimizer, "push_semi_joins", lambda node: node)
    kept = LocalQueryRunner().execute(text).rows()
    assert pushed and sorted(map(tuple, pushed)) == sorted(map(tuple, kept))
    theirs = oracle.execute(_text(name, ""))
    assert diff_results(pushed, theirs, ordered=False) is None


def test_semi_join_moves_below_the_inner_join_and_not_in_stays():
    runner = LocalQueryRunner()
    # IN: the semi join filters the table that holds its key
    assert _semi_probes(runner, _text("build")) == [("semi", "TableScanNode")]
    assert _semi_probes(runner, _text("probe")) == [("semi", "TableScanNode")]
    # NOT IN keeps its place above the join, with its NULL rule
    not_in = _text("build").replace("o_orderkey in", "o_orderkey not in")
    got = _semi_probes(runner, not_in)
    assert [t for t, _ in got] == ["anti"] and got[0][1] != "TableScanNode"


def test_a_small_page_carries_only_the_dictionary_values_it_uses():
    """A page of a few rows over a table's whole dictionary (Q18's 100
    result rows over ``c_name``) leaves the device with the values its
    rows use, in order; read back, every row is the same."""
    from presto_tpu.connectors.tpch import DictColumn

    values = np.asarray([f"Customer#{i:09d}" for i in range(5_000)], object)
    schema = {"c_name": T.VARCHAR, "k": T.BIGINT}
    ids = np.asarray([4_999, 7, 7, 2_500, 0], np.int32)
    page = stage_page(
        {"c_name": DictColumn(ids=ids, values=values), "k": np.arange(5)},
        schema,
    )
    cols, n = pages_wire.page_to_wire_columns(page)
    (used,) = [c[4] for c in cols if c[0] == "c_name"]
    assert list(used) == sorted(set(values[ids])) and len(used) == 4
    payload, _, rows = pages_wire.deserialize_page(
        pages_wire.serialize_page(cols, n)
    )
    back = stage_page(payload, schema)
    assert rows == 5 and back.to_pylist() == page.to_pylist()
