"""Device-plane telemetry suite.

Covers the tentpole end to end: the accounting choke points produce
nonzero dispatch/transfer counts on a real distributed query; the
plane disabled is BIT-EXACT off (zero counter delta, identical
results); federation merge math; sampler ring retention + rates;
live-progress monotonicity observed MID-query; backend-diag shape on
a forced failure; and the QueryCompletedEvent JSONL sink's
back-compat (every pre-existing field still present beside the new
``device`` section).
"""

import json
import threading
import time

import pytest

from presto_tpu.exec.local_runner import LocalQueryRunner
from presto_tpu.utils import devicediag
from presto_tpu.utils.telemetry import (
    DEVICE,
    MetricsFederation,
    MetricsSampler,
    device_snapshot,
    pad_waste_pct,
    parse_prometheus,
)


@pytest.fixture(autouse=True)
def _telemetry_on():
    """Every test starts (and leaves) the plane enabled — the process
    default."""
    DEVICE.set_enabled(True)
    yield
    DEVICE.set_enabled(True)


@pytest.fixture(scope="module")
def cluster():
    from presto_tpu.server import (
        CoordinatorServer,
        PrestoTpuClient,
        WorkerServer,
    )

    coord = CoordinatorServer().start()
    workers = [
        WorkerServer(coordinator_uri=coord.uri).start()
        for _ in range(2)
    ]
    deadline = time.time() + 10
    while time.time() < deadline and len(coord.active_workers()) < 2:
        time.sleep(0.05)
    client = PrestoTpuClient(coord.uri, timeout_s=600)
    yield coord, client
    for w in workers:
        w.shutdown(graceful=False)
    coord.shutdown()


# ------------------------------------------------- device accounting


def test_distributed_query_counts_device_work(cluster):
    """A distributed join moves real bytes and launches real
    programs: the process counters AND the per-query rollup must both
    see it."""
    coord, client = cluster
    before = device_snapshot()
    res = client.execute(
        "SELECT o.o_orderpriority, COUNT(*) FROM orders o "
        "JOIN lineitem l ON o.o_orderkey = l.l_orderkey "
        "GROUP BY o.o_orderpriority"
    )
    assert len(res.rows()) > 0
    after = device_snapshot()
    assert after["dispatches"] > before["dispatches"]
    assert (
        after["h2d_bytes"] + after["d2h_bytes"]
        > before["h2d_bytes"] + before["d2h_bytes"]
    )
    # per-query attribution: the QueryInfo device section is populated
    info = client.query_info(res.query_id)
    dev = info["device"]
    assert dev["dispatches"] > 0
    assert dev["h2d_bytes"] + dev["d2h_bytes"] > 0
    assert 0.0 <= dev["pad_waste_pct"] <= 100.0


def test_explain_analyze_renders_device_line(cluster):
    _coord, client = cluster
    res = client.execute(
        "EXPLAIN ANALYZE SELECT n.n_name, COUNT(*) FROM nation n "
        "JOIN region r ON n.n_regionkey = r.r_regionkey "
        "GROUP BY n.n_name"
    )
    text = "\n".join(r[0] for r in res.rows())
    (line,) = [
        ln
        for ln in text.splitlines()
        if ln.strip().startswith("device:")
    ]
    assert "dispatches" in line and "compiles" in line
    assert "h2d" in line and "d2h" in line and "pad waste" in line
    # nonzero dispatch/transfer on the analyzed join (acceptance
    # criterion)
    import re

    disp = int(re.search(r"dispatches (\d+)", line).group(1))
    assert disp > 0


def test_disabled_plane_is_bit_exact_off():
    """telemetry.enabled=false: EXACTLY zero counter delta and
    identical query results."""
    runner = LocalQueryRunner()
    sql = (
        "SELECT r_name, COUNT(*) FROM tpch.tiny.nation, "
        "tpch.tiny.region WHERE n_regionkey = r_regionkey "
        "GROUP BY r_name ORDER BY r_name"
    )
    enabled_res = runner.execute(sql)
    DEVICE.set_enabled(False)
    try:
        before = device_snapshot()
        disabled_res = runner.execute(sql)
        after = device_snapshot()
        assert after == before  # zero delta, every field, bit-exact
    finally:
        DEVICE.set_enabled(True)
    assert disabled_res.rows() == enabled_res.rows()


def test_local_query_stats_device_section():
    runner = LocalQueryRunner()
    runner.execute("SELECT COUNT(*) FROM tpch.tiny.orders")
    qs = runner.history.snapshot()[-1]
    d = qs.device_dict()
    assert d["dispatches"] >= 1
    assert d["h2d_bytes"] > 0 or d["d2h_bytes"] > 0


def test_pad_waste_pct_math():
    assert pad_waste_pct(0, 0) == 0.0
    assert pad_waste_pct(25, 75) == 25.0
    assert pad_waste_pct(10, 0) == 100.0


# ------------------------------------------------- event-sink compat


def test_event_sink_back_compat(tmp_path):
    """The JSONL QueryCompletedEvent record keeps every pre-existing
    top-level field AND gains the device section — old consumers keep
    parsing."""
    from presto_tpu.exec.stats import JsonlQueryEventListener

    path = tmp_path / "events.jsonl"
    runner = LocalQueryRunner()
    runner.history.add_listener(JsonlQueryEventListener(str(path)))
    runner.execute("SELECT COUNT(*) FROM tpch.tiny.nation")
    rec = json.loads(path.read_text().splitlines()[-1])
    # the pre-PR contract fields, all still present
    for field in (
        "event", "query_id", "state", "elapsed_ms", "planning_ms",
        "staging_ms", "execution_ms", "compile_cache_hit",
        "input_rows", "input_bytes", "output_rows", "operators",
        "stages", "spilled_bytes", "peak_memory_bytes",
    ):
        assert field in rec, field
    assert rec["event"] == "query_completed"
    # the additive device section
    for field in (
        "dispatches", "compiles", "compile_ms", "h2d_bytes",
        "d2h_bytes", "pad_rows", "live_rows", "pad_waste_pct",
    ):
        assert field in rec["device"], field


# --------------------------------------------------------- federation


def test_parse_prometheus_skips_noise():
    text = (
        "# HELP x_total help\n"
        "# TYPE x_total counter\n"
        "x_total 3\n"
        'y_ms{quantile="0.5"} 1.5\n'
        "torn line without value\n"
        "z_total not_a_number\n"
    )
    samples = parse_prometheus(text)
    assert ("x_total", "", 3.0) in samples
    assert ("y_ms", 'quantile="0.5"', 1.5) in samples
    assert len(samples) == 2


def test_federation_merge_math():
    """Per-node labels + node="cluster" sums of monotone families;
    quantiles are labeled but never summed."""
    expos = {
        "w1": 'a_total 3\nlat{quantile="0.5"} 10\n',
        "w2": 'a_total 4\nlat{quantile="0.5"} 20\n',
    }
    fed = MetricsFederation(lambda uri: expos[uri])
    by_node = fed.scrape([("w1", "w1"), ("w2", "w2")])
    out = fed.render(by_node)
    assert 'a_total{node="w1"} 3.0' in out
    assert 'a_total{node="w2"} 4.0' in out
    assert 'a_total{node="cluster"} 7.0' in out
    # quantile stream re-labeled per node, NOT cluster-summed
    assert 'lat{node="w1",quantile="0.5"} 10.0' in out
    assert 'lat{node="cluster"' not in out


def test_federation_drops_failed_scrapes():
    def fetch(uri):
        if uri == "dead":
            raise OSError("connection refused")
        return "ok_total 1\n"

    fed = MetricsFederation(fetch)
    by_node = fed.scrape([("w1", "live"), ("w2", "dead")])
    assert set(by_node) == {"w1"}  # dead node dropped, not fatal


# ------------------------------------------------------------ sampler


def test_sampler_retention_and_rate():
    samp = MetricsSampler(retention=4)
    samp.observe("n1", [("c_total", 10.0)], ts=100.0)
    samp.observe("n1", [("c_total", 40.0)], ts=110.0)
    rows = samp.rows()
    assert rows[-1]["rate"] == pytest.approx(3.0)  # (40-10)/10s
    # retention bounds TOTAL rows: oldest drop first
    for i in range(5):
        samp.observe("n1", [("c_total", 50.0 + i)], ts=120.0 + i)
    rows = samp.rows()
    assert len(rows) == 4
    assert rows[0]["value"] == 51.0  # the 10.0/40.0 rows aged out


def test_sampler_rate_resets_on_counter_restart():
    """A restarted worker's counter going backwards rates 0, never
    negative."""
    samp = MetricsSampler(retention=8)
    samp.observe("w", [("c_total", 100.0)], ts=10.0)
    samp.observe("w", [("c_total", 5.0)], ts=20.0)
    assert samp.rows()[-1]["rate"] == 0.0


def test_sampler_persistence_rotation_and_torn_tail(tmp_path):
    path = str(tmp_path / "metrics.jsonl")
    samp = MetricsSampler(retention=16, path=path)
    samp.observe("n", [("a_total", 1.0)], ts=1.0)
    samp.observe("n", [("a_total", 2.0)], ts=2.0)
    # torn tail: a partial line must not poison the replay
    with open(path, "a") as f:
        f.write('{"node": "n", "ts": 3.0, "na')
    rows = MetricsSampler.read_persisted(path)
    assert [r["value"] for r in rows] == [1.0, 2.0]


def test_metrics_history_system_table_local_is_empty():
    """No cluster / sampler off: an empty view, not an error."""
    runner = LocalQueryRunner()
    res = runner.execute(
        "SELECT * FROM system.runtime.metrics_history"
    )
    assert res.rows() == []


# ------------------------------------------------------ live progress


def test_progress_monotone_mid_query(cluster):
    """Poll the progress endpoint WHILE a distributed query runs: the
    done counts and byte/dispatch counters must never go backwards,
    and the terminal observation is complete."""
    coord, client = cluster
    polls = []
    stop = threading.Event()
    seen_qid = {}

    def poll():
        while not stop.is_set():
            qs = client.list_queries()
            running = [
                q for q in qs if q["state"] not in ("FINISHED", "FAILED")
            ]
            for q in running:
                try:
                    p = client.query_progress(q["query_id"])
                except Exception:
                    continue  # query finished between list and get
                polls.append(p)
                seen_qid[q["query_id"]] = True
            time.sleep(0.02)

    t = threading.Thread(target=poll, daemon=True)
    t.start()
    try:
        res = client.execute(
            "SELECT l.l_returnflag, COUNT(*), SUM(l.l_quantity) "
            "FROM lineitem l JOIN orders o "
            "ON l.l_orderkey = o.o_orderkey "
            "GROUP BY l.l_returnflag"
        )
        assert len(res.rows()) > 0
    finally:
        stop.set()
        t.join(timeout=5)
    final = client.query_progress(res.query_id)
    assert final["done"] and final["progress"] == 1.0
    assert final["eta_ms"] == 0.0
    assert final["splits_done"] == final["splits_total"] > 0
    assert final["device_dispatches"] > 0
    # monotonicity over the mid-query observations of THIS query
    series = [
        p for p in polls if p["query_id"] == res.query_id
    ] + [final]
    for a, b in zip(series, series[1:]):
        for key in ("splits_done", "rows", "bytes",
                    "device_dispatches", "elapsed_ms"):
            assert b[key] >= a[key], (key, a, b)


def test_progress_unknown_query_404s(cluster):
    _coord, client = cluster
    with pytest.raises(Exception):
        client.query_progress("q_nope_000000")


# --------------------------------------------------------- diagnosis


def test_backend_diag_ok_shape():
    diag = devicediag.probe_backend()
    d = diag.to_dict()
    assert d["ok"] is True and d["phase"] == "ok"
    assert d["backend"] != "" and d["device_count"] >= 1
    assert d["probed_at"] > 0


def test_backend_diag_forced_failure_shape():
    """A dead platform produces a structured diagnosis — failing
    phase, error class, truncated error — and never raises."""
    diag = devicediag.probe_backend(platform="no_such_platform")
    d = diag.to_dict()
    assert d["ok"] is False
    assert d["phase"] == "enumerate"
    assert d["error_class"] != "" and d["error"] != ""
    assert len(d["error"]) <= 300
    assert "fallback" not in d  # nothing falls back: a dead probe raises
    # a worker refuses to start on a dead probe instead of announcing
    # a node that cannot compute
    from presto_tpu.server.worker import WorkerServer

    w = WorkerServer()
    try:
        with pytest.MonkeyPatch.context() as mp:
            mp.setattr(
                devicediag, "probe_backend",
                lambda: devicediag.record_diag(diag),
            )
            with pytest.raises(RuntimeError, match="device probe failed"):
                w.start()
    finally:
        w.httpd.server_close()
    # a later successful re-probe replaces the failed diag (and leaves
    # a clean one for other tests in this process)
    again = devicediag.probe_backend()
    assert again.ok and devicediag.last_diag() is again


def test_backend_diag_on_worker_status_and_nodes(cluster):
    coord, client = cluster
    import urllib.request

    w = coord.active_workers()[0]
    st = json.loads(
        urllib.request.urlopen(w.uri + "/v1/status").read()
    )
    assert st["backend_diag"]["phase"] in ("ok", "enumerate",
                                           "compile", "execute")
    res = client.execute(
        "SELECT node_id, backend_diag FROM system.runtime.nodes"
    )
    for _node, diag_json in res.rows():
        diag = json.loads(diag_json)
        assert diag == {} or "phase" in diag
