"""Host time per layer: span self times as counters, named waits, true
XLA compiles, the spans in a device profile, and the attribution of the
device's idle time to them (``utils/tracing.py``, ``utils/telemetry.py``,
``benchmark/idle_spans.py``).
"""

import ast
import os
import sys
import tempfile
import threading
import time

import jax
import jax.numpy as jnp
import pytest

from presto_tpu.utils import tracing
from presto_tpu.utils.telemetry import DEVICE, device_snapshot

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from benchmark import discovery, harness, idle_spans, reduce  # noqa: E402

WORK_KEYS = [f"span_ms.{n}" for n in tracing.WORK]


@pytest.fixture(autouse=True)
def _telemetry_on():
    DEVICE.set_enabled(True)
    yield
    DEVICE.set_enabled(True)


def _delta(fn):
    before = device_snapshot()
    fn()
    after = device_snapshot()
    return {k: after[k] - before.get(k, 0) for k in after}


# ------------------------------------------------------------ the primitive


def test_self_time_is_duration_minus_same_thread_children():
    def body():
        with tracing.phase("exec"):
            time.sleep(0.02)
            with tracing.phase("plan"):
                time.sleep(0.03)
                with tracing.wait("test.nested"):
                    time.sleep(0.04)

    d = _delta(body)
    assert 20 <= d["span_ms.exec"] < 30
    assert 30 <= d["span_ms.plan"] < 40
    assert 40 <= d["wait_ms.test.nested"] < 50
    # the wait came out of its parent; nothing else moved
    assert all(d[k] == 0 for k in WORK_KEYS if k[8:] not in ("exec", "plan"))


def test_span_on_another_thread_does_not_reduce_self_time():
    tr = tracing.Trace()

    def other():
        with tr.span("schedule"):
            time.sleep(0.03)

    def body():
        with tr.span("query"):
            t = threading.Thread(target=other)
            t.start()
            t.join()

    d = _delta(body)
    # both threads ran the whole 30 ms: the tree span accumulates under
    # its layer (query -> exec) and is not shortened by the other thread
    assert d["span_ms.exec"] >= 30
    assert d["span_ms.schedule"] >= 30
    root = tr.to_tree()[0]
    assert root["name"] == "query"
    assert [c["name"] for c in root["children"]] == ["schedule"]
    assert root["duration_ms"] >= 30


def test_phase_needs_no_trace_and_nests_with_tree_spans():
    tr = tracing.Trace()

    def body():
        with tr.span("gather") as span:
            with tracing.phase("fetch", site="x"):
                time.sleep(0.02)
        assert span.dur_ns >= 20_000_000
        assert span.duration_ms == span.dur_ns / 1e6

    d = _delta(body)
    assert d["span_ms.fetch"] >= 20
    assert d["span_ms.exec"] < 15  # the fetch came out of gather's time
    assert [s.name for s in tr.spans()] == ["gather"]  # no node for a phase


@pytest.mark.parametrize("name", ["compile", "Plan", ""])
def test_names_outside_the_vocabulary_are_refused(name):
    with pytest.raises(ValueError):
        tracing.phase(name)
    with pytest.raises(ValueError):
        tracing.Trace().span(name)


@pytest.mark.parametrize("name,layer", sorted(tracing._TREE_LAYER.items()))
def test_every_tree_name_accumulates_under_a_work_name(name, layer):
    assert layer in tracing.WORK

    def body():
        with tracing.Trace().span(name) as span:
            time.sleep(0.002)
        assert span.name == name  # the tree keeps its display name

    d = _delta(body)
    assert d[f"span_ms.{layer}"] >= 2
    assert d["stmt_wall_ms"] == 0  # a span is no statement


def test_blocked_thread_adds_nothing_to_a_work_name():
    go = threading.Event()

    def worker():
        with tracing.phase("dispatch"):
            time.sleep(0.03)
        go.set()

    def body():
        with tracing.phase("schedule"):
            t = threading.Thread(target=worker)
            t.start()
            with tracing.wait("test.blocked"):
                go.wait(5)
            t.join()

    d = _delta(body)
    assert d["span_ms.dispatch"] >= 30
    assert d["wait_ms.test.blocked"] >= 25
    assert d["span_ms.schedule"] < 15  # blocked time is not work


def test_disabled_plane_freezes_spans_and_compiles():
    DEVICE.set_enabled(False)
    try:
        def body():
            with tracing.phase("exec"), tracing.wait("test.off"):
                time.sleep(0.005)
            tracing.add_stmt_wall(1_000_000)
            jax.jit(lambda x: x * 3 + 7)(jnp.arange(13))

        assert all(v == 0 for v in _delta(body).values())
    finally:
        DEVICE.set_enabled(True)


def test_snapshot_always_has_the_keys_the_benchmark_reads():
    snap = device_snapshot()
    for k in WORK_KEYS + ["span_ms.unworked", "stmt_wall_ms", "xla_compiles",
                          "xla_cache_loads", "xla_compile_ms",
                          "stage_col_hits", "stage_col_misses",
                          "stage_evictions", "stage_resident_bytes",
                          "program_out_bytes", "dynamic_filter_wait_expired",
                          "join_probe_lanes"]:
        assert isinstance(snap[k], (int, float)), k


def test_xla_compiles_rise_on_a_new_shape_only():
    fn = jax.jit(lambda x: (x * 5 + 11).sum())
    a, b = jnp.arange(17), jnp.arange(19)  # their own compiles, outside
    first = _delta(lambda: fn(a).block_until_ready())
    assert first["xla_compiles"] + first["xla_cache_loads"] == 1
    assert first["xla_compile_ms"] >= 0
    again = _delta(lambda: fn(a).block_until_ready())
    assert again["xla_compiles"] == 0 and again["xla_cache_loads"] == 0
    assert again["xla_compile_ms"] == 0
    new_shape = _delta(lambda: fn(b).block_until_ready())
    assert new_shape["xla_compiles"] + new_shape["xla_cache_loads"] == 1
    # the engine's own count does not see a compile it did not dispatch
    assert new_shape["compiles"] == 0


def test_a_scalar_subquery_runs_in_an_exec_span(monkeypatch):
    """The subquery's run is execution (site ``subquery``), not the
    planning of the statement that binds its value."""
    from presto_tpu.exec.local_runner import LocalQueryRunner

    runner = LocalQueryRunner()
    opened = []
    real = tracing.phase

    def spy(name, site=""):
        opened.append((name, site, len(getattr(tracing._tls, "stack", ()))))
        return real(name, site)

    monkeypatch.setattr(tracing, "phase", spy)
    res = runner.execute(
        "select count(*) from tpch.tiny.nation where n_regionkey = "
        "(select max(r_regionkey) from tpch.tiny.region)")
    assert res.rows() == [(5,)]
    sub = [o for o in opened if o[:2] == ("exec", "subquery")]
    assert len(sub) == 1
    # the subquery's own phases open inside it, under their own names
    inside = opened[opened.index(sub[0]) + 1:]
    assert any(depth > sub[0][2] for _, _, depth in inside)


# ------------------------------------------------------- a served statement


@pytest.fixture(scope="module")
def served():
    from presto_tpu.server import (
        CoordinatorServer,
        PrestoTpuClient,
        WorkerServer,
    )

    coord = CoordinatorServer().start()
    worker = WorkerServer(coordinator_uri=coord.uri).start()
    deadline = time.time() + 10
    while time.time() < deadline and not coord.active_workers():
        time.sleep(0.05)
    client = PrestoTpuClient(coord.uri, timeout_s=600)
    sql = (
        "select l_returnflag, count(*), sum(l_quantity) from "
        "tpch.tiny.lineitem where l_discount < 0.05 group by l_returnflag"
    )
    client.execute(sql)  # compiles
    yield client, sql
    worker.shutdown(graceful=False)
    coord.shutdown()


def test_served_statement_splits_its_wall_time(served):
    client, sql = served
    t0 = time.perf_counter()
    d = _delta(lambda: client.execute(sql).rows())
    wall_ms = (time.perf_counter() - t0) * 1e3
    for name in ("protocol", "plan", "exec", "schedule", "dispatch", "fetch"):
        assert d[f"span_ms.{name}"] > 0, name
    assert 0 < d["stmt_wall_ms"] <= wall_ms
    assert d["stmt_wall_ms"] > 0.9 * wall_ms - 5
    total = sum(d[k] for k in WORK_KEYS) + d["span_ms.unworked"]
    assert total == pytest.approx(d["stmt_wall_ms"], abs=1e-6)
    # every round trip of the statement is a named wait
    for site in ("client.http_post", "client.http", "coordinator.long_poll",
                 "coordinator.task_post", "coordinator.stage_futures"):
        assert d[f"wait_ms.{site}"] > 0, site
    assert d["xla_compiles"] == 0  # warm: nothing compiled


def test_served_statement_off_is_zero_delta(served):
    client, sql = served
    DEVICE.set_enabled(False)
    try:
        d = _delta(lambda: client.execute(sql).rows())
    finally:
        DEVICE.set_enabled(True)
    assert all(v == 0 for v in d.values())


def test_served_tree_keeps_its_display_names(served):
    client, sql = served
    info = client.query_info(client.execute(sql).query_id)

    def walk(nodes):
        for n in nodes:
            yield n
            yield from walk(n["children"])

    spans = list(walk(info["trace"]))
    assert {"query", "plan", "fragment", "schedule", "task", "gather"} <= {
        s["name"] for s in spans
    }
    assert all(s["duration_ms"] >= 0 for s in spans)


def test_held_results_get_is_a_wait_not_schedule_work():
    """A results GET held 100 ms while the task's thread works: the
    handler's ``schedule`` span keeps its few ms of self time, and the
    statement has no unworked time — the task worked throughout."""
    from presto_tpu import types as T
    from presto_tpu.server import pages_wire, rpc
    from presto_tpu.server import worker as worker_mod
    from presto_tpu.server.protocol import FragmentSpec
    import numpy as np

    w = worker_mod.WorkerServer().start()
    try:
        spec = FragmentSpec(
            task_id="adhoc.span.0", query_id="adhoc", fragment=None,
            partition_scan=0, split_start=0, split_end=0,
        )
        t = worker_mod._Task(spec, pool=w.memory_pool, node_id=w.node_id)
        t.state = "RUNNING"
        w.tasks[spec.task_id] = t
        page = pages_wire.serialize_page(
            [("x", np.arange(4, dtype=np.int64), None, T.BIGINT, None)], 4
        )
        url = f"{w.uri}/v1/task/{spec.task_id}/results/0/0"
        rpc.call("GET", url)  # the handler's first call, outside the delta

        def task_thread():
            with tracing.phase("exec", site="task"):
                time.sleep(0.1)
                t.offer_page(page)

        def body():
            t0 = time.perf_counter_ns()
            th = threading.Thread(target=task_thread)
            th.start()
            resp = rpc.call(
                "GET", url, headers={rpc.MAX_WAIT_HEADER: "1000"},
                wait_site="test.held_get",
            )
            th.join()
            tracing.add_stmt_wall(time.perf_counter_ns() - t0)
            assert resp.status == 200

        d = _delta(body)
    finally:
        w.shutdown(graceful=False)
    assert d["worker.results_waits"] == 1
    assert d["wait_ms.worker.results_wait"] >= 90
    assert d["span_ms.exec"] >= 100
    assert d["span_ms.schedule"] < 5
    assert d["span_ms.unworked"] < 10


# -------------------------------------------- spans on the device's clock


def test_phase_is_an_annotation_only_inside_a_profile(tmp_path, monkeypatch):
    """Outside a profiler session a phase builds no annotation; inside
    one it leaves ``presto:<name>/<site>`` in the written profile."""
    built = []

    class Counted(tracing._Annotation):
        def __init__(self, *a, **kw):
            built.append(a[0])
            super().__init__(*a, **kw)

    monkeypatch.setattr(tracing, "_Annotation", Counted)
    with tracing.phase("exec", site="x"):
        pass
    assert built == []
    jax.profiler.start_trace(str(tmp_path))
    try:
        with tracing.phase("exec", site="x"), tracing.Trace().span("gather"):
            pass
    finally:
        jax.profiler.stop_trace()
    assert built == ["presto:exec/x", "presto:exec/gather"]
    planes = idle_spans.load_planes_by_thread(reduce.find_xplane(str(tmp_path)))
    names = {e[0] for lines in planes.values() for evs in lines.values() for e in evs}
    assert {"presto:exec/x", "presto:exec/gather"} <= names


def _planes(threads, ops=((0, 10), (90, 100))):
    """A window of 100 ns with the device busy at its two ends."""
    host = {"main#0": [("bench:window", 0.0, 100.0), ("stmt:q", 0.0, 100.0)]}
    for i, evs in enumerate(threads):
        host[f"t#{i + 1}"] = [(f"presto:{n}", float(s), float(e)) for n, s, e in evs]
    return {
        "/host:CPU": host,
        "/device:TPU:0": {
            "XLA Modules": [("jit_x(1)", 0.0, 100.0)],
            "XLA Ops": [("%f = f()", float(s), float(e)) for s, e in ops],
        },
    }


def _by_span(out):
    """``idle_by_stmt`` summed over statements: seconds by span."""
    got = {}
    for k, v in out["idle_by_stmt"].items():
        span = k.split("|", 1)[1]
        got[span] = got.get(span, 0.0) + v
    return got


@pytest.mark.parametrize("threads,want,parts", [
    # a gap under two nested spans goes to the inner one
    ([[("exec/query", 0, 100), ("schedule", 5, 95)]], "schedule", {"schedule": 80}),
    # work on one thread beats a wait on another
    ([[("exec/task", 5, 95)], [("protocol/client", 0, 100), ("wait/client.http", 1, 99)]],
     "exec/task", {"exec/task": 80}),
    # of two waits, the one that started last wins
    ([[("wait/coordinator.long_poll", 1, 99)], [("wait/coordinator.pull_stall", 5, 95)]],
     "wait/coordinator.pull_stall", {"wait/coordinator.pull_stall": 80}),
    # ... only while it lasts: the chain's earlier wait takes the rest
    ([[("wait/coordinator.long_poll", 1, 99)], [("wait/coordinator.pull_stall", 20, 80)]],
     "wait/coordinator.pull_stall",
     {"wait/coordinator.pull_stall": 60, "wait/coordinator.long_poll": 20}),
    # a wait nested in work on the same thread is the innermost
    ([[("schedule/attempt", 0, 100), ("wait/coordinator.pull_stall", 12, 88)]],
     "wait/coordinator.pull_stall",
     {"wait/coordinator.pull_stall": 76, "schedule/attempt": 4}),
    # a span covering under half the gap does not take it
    ([[("dispatch", 10, 30)]], "untraced", {"dispatch": 20, "untraced": 60}),
    # a gap under none is untraced
    ([[("protocol/client", 0, 5)]], "untraced", {"untraced": 80}),
    # ... and so is one only the client's round trip covers: the server
    # ran code that opened no span
    ([[("protocol/client", 0, 100), ("wait/client.http", 1, 99)]],
     "untraced", {"untraced": 80}),
    # a server's wait under the client's takes what it covers
    ([[("protocol/client", 0, 100), ("wait/client.http", 1, 99)],
      [("wait/coordinator.long_poll", 20, 80)]],
     "wait/coordinator.long_poll", {"wait/coordinator.long_poll": 60, "untraced": 20}),
])
def test_trace_gaps_attribution(threads, want, parts):
    out = idle_spans.idle_by_span(_planes(threads), "tpu")
    assert sum(out[f"idle_s.{x}"] for x in idle_spans.NAMES) == pytest.approx(80e-9)
    assert _by_span(out) == {k: pytest.approx(v * 1e-9) for k, v in parts.items()}
    assert max(_by_span(out).items(), key=lambda kv: kv[1])[0] == want
    assert set(out["idle_by_stmt"]) == {f"q|{k}" for k in parts}
    for x in idle_spans.NAMES:
        held = sum(v for k, v in parts.items() if k.split("/", 1)[0] == x)
        assert out[f"idle_s.{x}"] == pytest.approx(held * 1e-9), x
    assert out["presto_events"] == sum(len(t) for t in threads)


def test_trace_gaps_splits_gaps_between_spans():
    planes = _planes(
        [[("exec/task", 10, 40), ("wait/worker.output_buffer", 50, 90)]],
        ops=((0, 10), (40, 50), (90, 100)),
    )
    out = idle_spans.idle_by_span(planes, "tpu")
    assert _by_span(out) == {
        "exec/task": pytest.approx(30e-9),
        "wait/worker.output_buffer": pytest.approx(40e-9),
    }
    assert out["idle_s.exec"] == pytest.approx(30e-9)
    assert out["idle_s.wait"] == pytest.approx(40e-9)
    assert out["idle_s.untraced"] == 0.0


def _busy_trace():
    """Two devices, three threads, statements back to back: spans that
    nest, overlap across threads and end inside gaps."""
    spans = [
        [("protocol/client", 0, 1000), ("wait/client.http", 10, 990)],
        [("exec/task", 50, 400), ("dispatch", 60, 80), ("fetch/control", 90, 300),
         ("staging/stage_column", 420, 480), ("exec/task", 500, 900),
         ("dispatch", 510, 530), ("fetch/control", 540, 870)],
        [("schedule", 30, 45), ("plan", 600, 640), ("wait/coordinator.pull", 650, 700)],
    ]
    planes = _planes(spans, ops=((70, 95), (300, 330), (700, 760)))
    planes["/host:CPU"]["main#0"] = [("bench:window", 0.0, 1000.0), ("stmt:q1", 0.0, 480.0),
                                     ("stmt:q6", 480.0, 1000.0), ("PjitFunction(f)", 60.0, 75.0)]
    planes["/device:TPU:1"] = {
        "XLA Modules": [("jit_y(2)", 100.0, 900.0)],
        "XLA Ops": [("%g = g()", 100.0, 250.0), ("%g = g()", 800.0, 890.0)],
    }
    return planes


def _without_spans(planes):
    return {p: {ln: [e for e in evs if not e[0].startswith(idle_spans.PREFIX)]
                for ln, evs in lines.items()} for p, lines in planes.items()}


def test_the_nine_sum_to_the_idle_time_and_need_a_span():
    planes = _busy_trace()
    red = reduce.reduce_trace(planes, "tpu")
    out = idle_spans.idle_by_span(planes, "tpu")
    idle_s = red["window_s"] - red["busy_s"]  # per device, averaged
    assert sum(out[f"idle_s.{x}"] for x in idle_spans.NAMES) == pytest.approx(idle_s)
    assert sum(out["idle_by_stmt"].values()) == pytest.approx(idle_s)
    assert {k.split("|")[0] for k in out["idle_by_stmt"]} <= {"q1", "q6"}
    assert out["presto_events"] == 12
    assert idle_spans.idle_by_span(_without_spans(planes), "tpu") == {}


def test_spans_leave_the_device_numbers_and_relabel_the_gaps():
    """The spans add host events. The device's numbers and the idle gaps'
    times and statements stay what they were; ``reduce.py`` counts the
    spans as JAX calls, so a gap they cover reads ``in-jax-runtime``
    where it read ``outside-jax`` (the ``breakdown``'s split is lost
    until ``reduce.py`` leaves them out)."""
    planes = _busy_trace()
    got = reduce.reduce_trace(planes, "tpu", top=100)
    bare = reduce.reduce_trace(_without_spans(planes), "tpu", top=100)
    for k in ("window_s", "busy_s", "devices", "events", "programs", "device_ops"):
        assert got[k] == bare[k], k

    def gaps(red):
        return sorted((k[len("gap:"):].split("/")[0], v)
                      for k, v in red["idle_gaps"] if k.startswith("gap:"))

    def by_stmt(red):
        out = {}
        for k, v in red["idle_gaps"]:
            if k.startswith("sum:"):
                stmt = k[len("sum:"):].split("/")[0]
                out[stmt] = out.get(stmt, 0.0) + v
        return out

    assert gaps(got) == gaps(bare)
    assert by_stmt(got) == pytest.approx(by_stmt(bare))
    split = {k.split("/")[-1] for k, _ in got["idle_gaps"]}
    assert split == {reduce.IN_JAX}
    assert reduce.OUTSIDE_JAX in {k.split("/")[-1] for k, _ in bare["idle_gaps"]}


def test_layer_metrics_read_the_nine(tmp_path, monkeypatch):
    cell = discovery.load_cell(ROOT, "sf1_scan_agg")
    names = [f"idle_{x}_ms_per_stmt.pass" for x in idle_spans.NAMES]
    assert names == [m.name for m in cell.per_layer if m.name.startswith("idle_")]
    fields = {f"idle_s.{x}": 0.001 * (i + 1) for i, x in enumerate(idle_spans.NAMES)}
    obs = {"trace": dict(fields, window_s=1.0, busy_s=0.5), "stmts": 4,
           "input_bytes": 0, "counters": {}, "peak": {}}
    got = harness._layer_metrics(cell, obs)
    for i, name in enumerate(names):
        assert got[name] == {"value": pytest.approx(0.25 * (i + 1)), "unit": "ms/stmt"}
    # no field and no trace of this run's window: all nine left out
    monkeypatch.setattr(tempfile, "tempdir", str(tmp_path))
    obs["trace"] = {"window_s": 1.0, "busy_s": 0.5}
    assert not set(names) & set(harness._layer_metrics(cell, obs))
    assert obs["idle_spans"] == {}  # looked for once, not nine times


def test_the_readers_find_the_runs_own_trace(tmp_path, monkeypatch):
    """The path a traced run takes: a real profile in a ``bench_trace_*``
    directory of the temporary directory, its window annotated as the
    harness does, reduced by ``reduce.py``; the nine readers find it by
    the window's length and sum to its idle time."""
    monkeypatch.setattr(tempfile, "tempdir", str(tmp_path))
    trace_dir = tempfile.mkdtemp(prefix="bench_trace_")
    f = jax.jit(lambda x: jnp.cumsum(x * 2.0 + 1.0))
    x = jnp.ones(1 << 16)
    f(x).block_until_ready()  # compiled before the window, as the warm-up does
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    jax.profiler.start_trace(trace_dir, profiler_options=opts)
    try:
        with jax.profiler.TraceAnnotation(reduce.WINDOW):
            for _ in range(3):
                with jax.profiler.TraceAnnotation(reduce.STMT + "q"), \
                        tracing.phase("exec", site="task"):
                    with tracing.phase("dispatch"):
                        y = f(x)
                    with tracing.phase("fetch"):
                        jax.device_get(y)
                time.sleep(0.002)  # between statements: no span
    finally:
        jax.profiler.stop_trace()
    red = reduce.reduce_trace(reduce.load_planes(reduce.find_xplane(trace_dir)), "cpu")
    assert red["busy_s"] > 0
    cell = discovery.load_cell(ROOT, "sf1_scan_agg")
    obs = {"trace": dict(red), "stmts": 3, "input_bytes": 0, "counters": {}, "peak": {}}
    got = harness._layer_metrics(cell, obs)
    names = [f"idle_{x}_ms_per_stmt.pass" for x in idle_spans.NAMES]
    assert set(names) <= set(got)
    idle_ms = 1e3 * (red["window_s"] - red["busy_s"]) / 3
    assert sum(got[n]["value"] for n in names) == pytest.approx(idle_ms, rel=1e-9)
    assert got["idle_untraced_ms_per_stmt.pass"]["value"] >= 1.5  # the sleeps
    assert obs["idle_spans"]["presto_events"] == 9


# ------------------------------------------------------------ static check


def _wait_sites():
    """Every literal wait site of ``presto_tpu/``: ``tracing.wait("...")``
    and the ``wait_site=`` / ``site=`` keywords of the RPC helpers."""
    sites = []
    for base, _, files in os.walk(os.path.join(ROOT, "presto_tpu")):
        for f in files:
            if not f.endswith(".py"):
                continue
            path = os.path.join(base, f)
            with open(path) as fh:
                tree = ast.parse(fh.read())
            for node in ast.walk(tree):
                if not isinstance(node, ast.Call):
                    continue
                fn = node.func
                name = fn.attr if isinstance(fn, ast.Attribute) else getattr(fn, "id", "")
                if name == "wait" and isinstance(fn, ast.Attribute) and getattr(
                    fn.value, "id", ""
                ) == "tracing":
                    arg = node.args[0]
                    if isinstance(arg, ast.Constant):
                        sites.append((arg.value, path, node.lineno))
                for kw in node.keywords:
                    if kw.arg == "wait_site" or (
                        kw.arg == "site" and name in ("_rpc_json", "pull_pages")
                    ):
                        if isinstance(kw.value, ast.Constant):
                            sites.append((kw.value.value, path, node.lineno))
    return sites


def _blocking_calls(path):
    """``(line, receiver.method, named)`` of every ``.wait(``,
    ``.wait_for(`` and ``time.sleep(`` in a file; ``named`` = lexically inside a
    ``with tracing.wait(...)``."""
    with open(path) as fh:
        tree = ast.parse(fh.read())
    parent = {}
    for node in ast.walk(tree):
        for child in ast.iter_child_nodes(node):
            parent[child] = node
    out = []
    for node in ast.walk(tree):
        if not (isinstance(node, ast.Call) and isinstance(node.func, ast.Attribute)):
            continue
        recv = ast.unparse(node.func.value)
        if (node.func.attr, recv) != ("sleep", "time") and (
            node.func.attr not in ("wait", "wait_for") or recv == "tracing"
        ):
            continue
        named, p = False, node
        while p in parent and not named:
            p = parent[p]
            named = isinstance(p, ast.With) and any(
                ast.unparse(i.context_expr).startswith("tracing.wait(")
                for i in p.items
            )
        out.append((node.lineno, f"{recv}.{node.func.attr}", named))
    return out


def test_every_condition_wait_of_the_exchange_is_a_named_wait():
    """The exchange's two ends: every ``cond.wait`` / ``event.wait``
    of ``server/worker.py`` and every blocking call of ``pull_pages``
    sits inside a ``tracing.wait`` — a thread held there adds to no
    layer's self time."""
    server = os.path.join(ROOT, "presto_tpu", "server")
    waits = [c for c in _blocking_calls(os.path.join(server, "worker.py"))
             if c[1].endswith((".wait", ".wait_for"))]
    assert len(waits) >= 3
    assert all(named for _, _, named in waits), waits
    assert "worker.results_wait" in {s for s, _, _ in _wait_sites()}
    # rpc.py: the one bare sleep is call()'s backoff, inside the
    # caller's wait_site; the pull loop's fallback sleep is named
    rpc_calls = _blocking_calls(os.path.join(server, "rpc.py"))
    assert [c[1] for c in rpc_calls if not c[2]] == ["time.sleep"]
    assert sum(1 for c in rpc_calls if c[2]) == 1


def test_every_wait_site_is_named_once():
    sites = _wait_sites()
    assert len(sites) >= 40
    seen = {}
    for site, path, line in sites:
        assert site not in seen, f"{site}: {path}:{line} and {seen[site]}"
        seen[site] = f"{path}:{line}"
        head = site.split(".", 1)[0]
        # "<module>.<what>"; pull_pages takes the module alone
        assert head == os.path.basename(path)[:-3] or site in (
            "coordinator", "worker"
        ), (site, path)
