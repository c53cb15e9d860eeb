"""TPC-H Q15 (top supplier) on the served path, as the benchmark cell
``sf10_q15_topsupplier`` runs it, at ``tpch.tiny`` on the CPU: the
coordinator's own stream evaluates the scalar subquery, the worker's
scan task emits a page of partial groups a split batch, the root stage
merges them. Results and counts, no times."""

import dataclasses
import math
import os
import time

import numpy as np
import pytest

from benchmark import discovery
from benchmark.data import HostData, day, same_sum
from presto_tpu.connectors.tpch import TpchConnector
from presto_tpu.exec.local_runner import LocalQueryRunner
from presto_tpu.exec.staging import bucket_capacity
from presto_tpu.plan import nodes as N
from presto_tpu.plan.optimizer import prune_columns, push_scan_constraints
from presto_tpu.plan.planner import plan_statement
from presto_tpu.server import worker as worker_mod
from presto_tpu.server.client import PrestoTpuClient
from presto_tpu.server.coordinator import CoordinatorServer
from presto_tpu.server.protocol import FragmentSpec
from presto_tpu.server.scheduler import plan_stage
from presto_tpu.session import Session
from presto_tpu.sql import parse_statement
from presto_tpu.utils.telemetry import DEVICE, device_snapshot
from presto_tpu.verifier import SqliteOracle, diff_results

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
Q15 = discovery.load_module(
    os.path.join(ROOT, "benchmark", "statements", "q15.py")
)
ROWS = 4_096  # rows a split batch: a scan of tiny's lineitem is 15
K = 5  # split batches of a hand-made task
SUPPLIER_SLOTS = 1_024  # the bucket of tiny's 100 suppliers

VIEW = (
    "select l_suppkey, sum(l_extendedprice * (1 - l_discount)) r, "
    "count(*) c from tpch.tiny.lineitem "
    "where l_shipdate >= date '1996-01-01' and l_shipdate < date '1996-04-01' "
    "group by l_suppkey"
)
#: every row a group, the keys' proved domain (419,832) over a batch's rows
EVERY_ROW = (
    "select l_orderkey, l_linenumber, count(*) c from tpch.tiny.lineitem "
    "group by l_orderkey, l_linenumber"
)
#: Q15 with a count for the revenue: at tiny, quarters tie at the maximum
TIES = Q15.SQL.replace(
    "sum(l_extendedprice * (1 - l_discount))", "count(*)"
)


@pytest.fixture(scope="module")
def cluster():
    """A coordinator and a worker over HTTP; the coordinator's session
    makes a scan of lineitem several split batches, on the worker and in
    its own stream (the scalar subquery's), as SF10 does on the chip."""
    coord = CoordinatorServer().start()
    worker = worker_mod.WorkerServer(coordinator_uri=coord.uri).start()
    deadline = time.monotonic() + 30
    while not coord.active_workers():
        assert time.monotonic() < deadline
        time.sleep(0.02)
    coord.local.session.set("page_capacity", ROWS)
    coord.local.session.set("max_device_rows", 4 * ROWS)
    yield coord, worker, PrestoTpuClient(coord.uri)
    worker.shutdown(graceful=False)
    coord.shutdown()


@pytest.fixture(scope="module")
def data():
    return HostData(TpchConnector(), "tpch", "tiny")


@pytest.fixture(scope="module")
def oracle():
    return SqliteOracle("tiny")


def _months():
    rng = np.random.default_rng(35)
    drawn = [Q15.params(rng, None) for _ in range(2)]
    first, last = {"year": 1993, "month": 1}, {"year": 1997, "month": 10}
    return [first, last] + drawn


@pytest.mark.parametrize(
    "p", _months(), ids=lambda p: f"{p['year']}-{p['month']:02d}"
)
def test_q15_served_equals_the_numpy_reference_and_sqlite(
    p, cluster, data, oracle
):
    _, _, client = cluster
    before = device_snapshot()
    rows = [tuple(r) for r in client.execute(Q15.sql("tpch.tiny", p, "t")).rows()]
    grew = {k: v - before[k] for k, v in device_snapshot().items()
            if k.startswith("agg_")}
    assert rows
    assert Q15.compare(rows, Q15.reference(data, p)) is None
    theirs = oracle.execute(Q15.sql("tpch.tiny", p, "t").replace("tpch.tiny.", ""))
    assert diff_results(rows, theirs, ordered=True) is None
    # two scans of lineitem, 15 or more batches each, a page of at most
    # 100 suppliers in 1,024 slots a batch: partial pages and both merges ran
    assert grew["agg_out_slots"] >= 2 * 15 * SUPPLIER_SLOTS
    assert grew["agg_out_slots"] % SUPPLIER_SLOTS == 0
    pages = grew["agg_out_slots"] // SUPPLIER_SLOTS
    assert pages < grew["agg_partial_rows"] <= 100 * pages


def _tied_quarter(data):
    """The first quarter in which two or more suppliers ship the most
    rows, with the tied suppliers in key order."""
    li, _ = data.columns("lineitem", ("l_suppkey", "l_shipdate"))
    for m in range(Q15.MONTHS):
        p = {"year": 1993 + m // 12, "month": 1 + m % 12}
        end = m + 3
        lo = day(p["year"], p["month"], 1)
        hi = day(1993 + end // 12, 1 + end % 12, 1)
        keep = (li["l_shipdate"] >= lo) & (li["l_shipdate"] < hi)
        counts = np.bincount(li["l_suppkey"][keep])
        tied = np.nonzero(counts == counts.max())[0]
        if len(tied) > 1:
            return p, [int(k) for k in tied], int(counts.max())
    raise AssertionError("no quarter of tiny has a tie")


def test_a_tie_at_the_maximum_returns_every_tied_supplier_in_order(
    cluster, data, oracle
):
    _, _, client = cluster
    p, tied, top = _tied_quarter(data)
    sql = TIES.format(s="tpch.tiny", date=f"{p['year']:04d}-{p['month']:02d}-01")
    rows = [tuple(r) for r in client.execute(sql).rows()]
    assert [(r[0], r[4]) for r in rows] == [(k, top) for k in tied]
    assert all(r[1] == f"Supplier#{r[0]:09d}" for r in rows)
    theirs = oracle.execute(sql.replace("tpch.tiny.", ""))
    assert diff_results(rows, theirs, ordered=True) is None


class _TwoAtTheTop:
    """Host columns in which suppliers 7 and 3 tie and 5 trails."""

    def columns(self, table, columns):
        d = day(1995, 5, 15)
        cols = {
            "lineitem": {
                "l_suppkey": np.array([7, 3, 5, 3, 7, 9]),
                "l_extendedprice": np.array([10000, 5000, 9000, 5000, 0, 99999]),
                "l_discount": np.array([0, 0, 0, 0, 5, 0]),
                "l_shipdate": np.array([d, d, d, d + 1, d, d + 400]),
            },
            "supplier": {
                "s_suppkey": np.array([3, 5, 7, 9]),
                "s_name": np.array([0, 1, 2, 3]), "s_address": np.array([3, 2, 1, 0]),
                "s_phone": np.array([1, 1, 0, 0]),
            },
        }[table]
        dicts = {
            "s_name": np.array(["n3", "n5", "n7", "n9"], dtype=object),
            "s_address": np.array(["a9", "a7", "a5", "a3"], dtype=object),
            "s_phone": np.array(["p7", "p3"], dtype=object),
        }
        return ({c: cols[c] for c in columns},
                {c: dicts[c] for c in columns if c in dicts})


def test_the_reference_returns_every_supplier_at_the_maximum():
    want = Q15.reference(_TwoAtTheTop(), {"year": 1995, "month": 4})
    assert want == [(3, "n3", "a3", "p3", 1000000), (7, "n7", "a7", "p7", 1000000)]
    rows = [(3, "n3", "a3", "p3", 100.0), (7, "n7", "a7", "p7", 100.0)]
    assert Q15.compare(rows, want) is None
    assert Q15.compare(rows[:1], want) is not None  # a tied supplier missing
    assert Q15.compare(rows[::-1], want) is not None  # out of order
    assert Q15.compare([rows[0], (7, "n7", "a7", "p7", 100.0001)], want)


# ------------------------------------------- the worker's partial pages


@pytest.fixture
def worker():
    w = worker_mod.WorkerServer().start()
    yield w
    w.shutdown(graceful=False)


def _stage(w, sql):
    plan = plan_statement(
        parse_statement(sql), w.runner.catalogs, w.runner.session
    )
    root = push_scan_constraints(prune_columns(plan.root))
    return plan_stage(root, w.runner.catalogs)


def _run_task(w, stage, fragment=None, end=K * ROWS - 100):
    """One scan task of ``K`` split batches on the calling thread:
    ``(pages emitted, counters' growth, retries)``."""
    spec = FragmentSpec(
        task_id="t.0", query_id="q",
        fragment=stage.worker_fragment if fragment is None else fragment,
        partition_scan=stage.partition_scan, split_start=0, split_end=end,
        split_batch_rows=ROWS,
    )
    task = worker_mod._Task(spec, pool=w.memory_pool, node_id=w.node_id)
    pages = []
    w._emit_result = lambda task, out: pages.append(out)
    w.runner._qs_local.value = task.stats
    before = device_snapshot()
    try:
        w._execute(task)
    finally:
        after = device_snapshot()
        w.runner._qs_local.value = None
        del w._emit_result
    grew = {k: after[k] - before[k] for k in
            ("agg_partial_rows", "agg_out_slots", "device_syncs", "dispatches")}
    return pages, grew, task.stats.retries


def test_the_batches_partial_pages_add_up_to_the_one_pass_aggregate(worker):
    stage = _stage(worker, VIEW)
    assert isinstance(stage.worker_fragment, N.AggregationNode)
    assert stage.worker_fragment.key_ranges == ((1, 100),)
    pages, grew, retries = _run_task(worker, stage, end=stage.partition_rows)
    batches = math.ceil(stage.partition_rows / ROWS)
    assert len(pages) == batches == 15 and retries == 0
    merged = {}  # supplier -> [unscaled revenue e-4, rows]
    for page in pages:
        n = int(page.num_valid)
        state = [np.asarray(b.data)[:n] for b in page.blocks]
        for key, revenue, count in zip(*state):
            was = merged.setdefault(int(key), [0, 0])
            was[0] += int(revenue)
            was[1] += int(count)
    whole = {r[0]: r[1:] for r in LocalQueryRunner().execute(VIEW).rows()}
    assert len(whole) == 100 and set(merged) == set(whole)
    for key, (revenue, count) in whole.items():  # group for group, exactly
        assert merged[key][1] == count
        assert same_sum(revenue, merged[key][0], 4)
    # the counters say what was emitted, from shapes and the row counts
    # the batch's one read brings anyway: no read more than dispatches
    assert grew["agg_partial_rows"] == sum(int(p.num_valid) for p in pages)
    assert grew["agg_out_slots"] == batches * SUPPLIER_SLOTS
    assert grew["device_syncs"] == grew["dispatches"] == batches


def test_the_counters_stay_zero_with_telemetry_off(worker):
    stage = _stage(worker, VIEW)
    want, grew, _ = _run_task(worker, stage)
    assert grew["agg_out_slots"] == K * SUPPLIER_SLOTS
    DEVICE.set_enabled(False)
    try:
        pages, grew, _ = _run_task(worker, stage)
    finally:
        DEVICE.set_enabled(True)
    assert grew["agg_partial_rows"] == grew["agg_out_slots"] == 0
    assert [p.to_pylist() for p in pages] == [p.to_pylist() for p in want]


def _shrunk(node, max_groups):
    if isinstance(node, N.AggregationNode):
        return dataclasses.replace(node, max_groups=max_groups)
    return node


@pytest.mark.parametrize("max_groups", [None, 1_024],
                         ids=["planner_s_bucket", "overflowing_bucket"])
def test_a_worker_s_partial_page_has_no_more_slots_than_its_batch_has_rows(
    worker, max_groups
):
    """The planner's bucket (32,768) and the keys' proved domain are
    over a batch's 4,096 rows, so the rows bound the page; a bucket
    under a batch's groups overflows, and the batch runs again."""
    stage = _stage(worker, EVERY_ROW)
    cut = stage.worker_fragment
    assert cut.max_groups > bucket_capacity(ROWS)
    assert cut.key_ranges == ((1, 59_976), (1, 7))
    fragment = cut if max_groups is None else _shrunk(cut, max_groups)
    pages, grew, retries = _run_task(worker, stage, fragment=fragment)
    assert [int(p.num_valid) for p in pages] == [ROWS] * (K - 1) + [ROWS - 100]
    assert grew["agg_out_slots"] == K * bucket_capacity(ROWS)
    assert grew["agg_partial_rows"] == K * ROWS - 100
    assert (retries == 0) if max_groups is None else (retries >= K)
    assert all(list(r.values())[-1] == 1 for p in pages for r in p.to_pylist())


def test_the_runner_s_own_stream_sizes_its_partial_pages_by_the_same_rule():
    runner = LocalQueryRunner(session=Session(properties={
        "max_device_rows": 4 * ROWS, "page_capacity": ROWS,
    }))
    rows = int(runner.execute("select count(*) from tpch.tiny.lineitem").rows()[0][0])
    before = device_snapshot()
    got = runner.execute(EVERY_ROW).rows()
    after = device_snapshot()
    assert len(got) == rows and all(r[2] == 1 for r in got)
    batches = math.ceil(rows / ROWS)
    assert after["agg_out_slots"] - before["agg_out_slots"] == (
        batches * bucket_capacity(ROWS)
    )
    assert after["agg_partial_rows"] - before["agg_partial_rows"] == rows
