"""TPC-H Q5 (local supplier volume) on the served path, as the benchmark
cell ``sf10_q5_localsupplier`` runs it, at ``tpch.tiny`` on the CPU:
lineitem's scan task runs several split batches, each probing five
builds that the task prepared once (``LocalQueryRunner.prepare_builds``).
The answer against the cell's numpy reference and the sqlite oracle;
counts, no times."""

import os
import time

import numpy as np
import pytest

from benchmark import discovery
from benchmark.data import HostData
from presto_tpu.connectors.tpch import TpchConnector
from presto_tpu.server import worker as worker_mod
from presto_tpu.server.client import PrestoTpuClient
from presto_tpu.server.coordinator import CoordinatorServer
from presto_tpu.utils.telemetry import device_snapshot
from presto_tpu.verifier import SqliteOracle, diff_results

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
Q5 = discovery.load_module(os.path.join(ROOT, "benchmark", "statements", "q5.py"))
ROWS = 4_096  # rows a split batch: a scan of tiny's lineitem is 15
JOINS = 5


@pytest.fixture(scope="module")
def cluster():
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


@pytest.mark.parametrize("region", ["ASIA", "EUROPE"])
@pytest.mark.parametrize("year", [1994, 1997])
def test_q5_served_equals_the_numpy_reference_and_sqlite(
    year, region, cluster, data, oracle
):
    _, _, client = cluster
    p = {"year": year, "region": region}
    text = Q5.sql("tpch.tiny", p, "t")
    before = device_snapshot()
    res = client.execute(text)
    grew = {k: v - before[k] for k, v in device_snapshot().items()
            if isinstance(v, (int, float)) and k in before}
    rows = res.rows()
    assert len(rows) == 5  # the region's five nations
    assert Q5.compare(rows, Q5.reference(data, p)) is None
    theirs = oracle.execute(text.replace("tpch.tiny.", ""))
    assert diff_results(rows, theirs, ordered=True) is None
    # lineitem's tasks each prepared the five builds once, and ran more
    # batches than that: a batch reads the device once, a build once
    # (its matchable rows), the root and dynamic-filter stages a few
    # times a statement — nothing else a batch
    tasks = [
        t for s in client.query_info(res.query_id)["stages"]
        if s["kind"] == "source" for t in s["tasks"]
    ]
    assert tasks
    batches = int(np.ceil(data.rows("lineitem") / ROWS))
    assert grew["join_builds"] == JOINS * len(tasks)
    assert batches > len(tasks)
    assert grew["join_build_slots"] >= grew["join_builds"] * 1_024
    # every key of the five joins has a stated range: a batch's probes
    # sort five lanes, not ten (a task's last batch may be a short one)
    assert batches <= grew["join_probe_lanes"] / JOINS <= batches + len(tasks)
    assert 0 <= grew["device_syncs"] - batches - grew["join_builds"] <= 4


def test_reference_agrees_with_sqlite_for_every_region_and_year(data, oracle):
    """The yardstick itself, against the oracle, wherever qgen can draw
    (and the four other regions)."""
    rng = np.random.default_rng(37)
    years = {Q5.params(rng, data)["year"] for _ in range(8)} | {1993, 1997}
    for region in ("AFRICA", "AMERICA", "ASIA", "EUROPE", "MIDDLE EAST"):
        for year in sorted(years):
            p = {"year": year, "region": region}
            want = Q5.reference(data, p)
            theirs = oracle.execute(
                Q5.sql("tpch.tiny", p, "t").replace("tpch.tiny.", "")
            )
            assert diff_results(
                [(n, r / 10 ** 4) for n, r in want], theirs, ordered=True
            ) is None, p
