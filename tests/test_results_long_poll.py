"""The results GET is a long-poll (``server/rpc.py: pull_pages``,
``server/worker.py: Handler._task_results``): the puller's request for
its head token carries ``X-Max-Wait`` and the worker holds it on the
task's condition until the page exists, the task is terminal, the
worker drains or shuts down, or the wait ran out. Speculative requests
are answered at once, and their stale "no page yet" is dropped.

Tasks here are bare ``_Task`` objects registered on a worker with no
coordinator: the test thread plays the producer.
"""

import functools
import threading
import time
import types
import urllib.error

import numpy as np
import pytest

from presto_tpu import types as T
from presto_tpu.server import pages_wire, rpc
from presto_tpu.server import worker as worker_mod
from presto_tpu.server.protocol import FragmentSpec
from presto_tpu.utils import faults
from presto_tpu.utils.telemetry import DEVICE, device_snapshot


def within(seconds):
    """The test's own time limit: a pull that hangs fails its test
    instead of the run."""

    def deco(fn):
        @functools.wraps(fn)
        def run(*a, **kw):
            box = {}

            def body():
                try:
                    fn(*a, **kw)
                except BaseException as e:  # re-raised on the test's thread
                    box["exc"] = e

            th = threading.Thread(target=body, daemon=True)
            th.start()
            th.join(seconds)
            if th.is_alive():
                pytest.fail(f"{fn.__name__} still running after {seconds} s")
            if "exc" in box:
                raise box["exc"]

        return run

    return deco


def _boot():
    return worker_mod.WorkerServer().start()


@pytest.fixture(scope="module")
def worker():
    w = _boot()
    yield w
    w.shutdown(graceful=False)


_seq = iter(range(1 << 30))


def _task(w, nparts=1):
    """A RUNNING task with empty buffers, registered like a posted one."""
    spec = FragmentSpec(
        task_id=f"adhoc.lp.{next(_seq)}", query_id="adhoc", fragment=None,
        partition_scan=0, split_start=0, split_end=0, n_partitions=nparts,
    )
    t = worker_mod._Task(spec, pool=w.memory_pool, node_id=w.node_id)
    t.state = "RUNNING"
    with w._lock:
        w.tasks[spec.task_id] = t
    return t


def _page(value, n=4):
    return pages_wire.serialize_page(
        [("x", np.full(n, value, np.int64), None, T.BIGINT, None)], n
    )


def _finish(t, state="FINISHED", error=None):
    """The terminal publish of ``WorkerServer._run_task_body``."""
    with t.cond:
        t.error = error
        t.state = state
        t.cond.notify_all()


def _get(w, t, token=0, part=0, wait_ms=None, ack=None):
    hdrs = {"X-Ack": str(token if ack is None else ack)}
    if wait_ms is not None:
        hdrs[rpc.MAX_WAIT_HEADER] = str(wait_ms)
    return rpc.call(
        "GET", f"{w.uri}/v1/task/{t.spec.task_id}/results/{part}/{token}",
        headers=hdrs,
    )


def _later(delay_s, fn, *a):
    th = threading.Timer(delay_s, fn, a)
    th.daemon = True
    th.start()
    return th


def _values(pages):
    """The ``x`` value each pulled page carries, in order."""
    return [int(np.asarray(p[0]["x"])[0]) for p in pages]


def _no_sleep(monkeypatch):
    """``rpc``'s own ``time.sleep`` records instead of sleeping (the
    module's clock only: other threads keep the real one)."""
    sleeps = []
    monkeypatch.setattr(rpc, "time", types.SimpleNamespace(
        monotonic=time.monotonic, sleep=sleeps.append,
    ))
    return sleeps


def _counts():
    snap = device_snapshot()
    return {k: snap[k] for k in (
        "coordinator.pull_stalls", "worker.results_waits",
        "worker.results_wait_timeouts",
    )}


# ----------------------------------------------------------- the worker's end


@within(30)
def test_page_reaches_a_held_get_when_it_is_offered(worker):
    """A page offered 30 ms after the GET arrives with the offer, not
    at the next tick of a 50 ms poll (best of three: the host is shared)."""
    lags = []
    for i in range(3):
        t = _task(worker)
        offered = {}

        def offer(t=t):
            offered["at"] = time.monotonic()
            t.offer_page(_page(i))

        t0 = time.monotonic()
        _later(0.03, offer)
        resp = _get(worker, t, wait_ms=1000)
        got = time.monotonic()
        assert resp.status == 200
        assert resp.headers.get("X-Complete") == "false"
        assert offered["at"] - t0 >= 0.03  # the GET was held across the offer
        lags.append(got - offered["at"])
    assert min(lags) < 0.02, lags


@within(30)
def test_no_header_answers_at_once(worker):
    t = _task(worker)
    before = _counts()
    t0 = time.monotonic()
    resp = _get(worker, t)
    assert time.monotonic() - t0 < 0.5  # far under the 1 s a held GET may take
    assert resp.status == 204
    assert resp.headers.get("X-Complete") == "false"
    assert _counts() == before  # it did not wait


@pytest.mark.parametrize("value", ["1.5", "abc", "-5", ""])
@within(30)
def test_malformed_max_wait_answers_at_once(worker, value):
    """An outside header: what is no whole number of ms is no wait, and
    never an error after the ack has freed pages."""
    t = _task(worker)
    t.offer_page(_page(0))
    before = _counts()
    t0 = time.monotonic()
    resp = _get(worker, t, token=1, wait_ms=value)
    assert time.monotonic() - t0 < 0.5
    assert resp.status == 204
    assert resp.headers.get("X-Complete") == "false"
    assert _counts() == before


@within(30)
def test_max_wait_is_clamped_and_runs_out_with_a_204(worker, monkeypatch):
    monkeypatch.setattr(rpc, "PULL_MAX_WAIT_S", 0.1)
    t = _task(worker)
    before = _counts()
    t0 = time.monotonic()
    resp = _get(worker, t, wait_ms=60_000)
    held = time.monotonic() - t0
    assert resp.status == 204 and resp.headers.get("X-Complete") == "false"
    assert 0.1 <= held < 1.0
    after = _counts()
    assert after["worker.results_waits"] == before["worker.results_waits"] + 1
    assert (
        after["worker.results_wait_timeouts"]
        == before["worker.results_wait_timeouts"] + 1
    )


@within(30)
def test_failed_while_a_get_waits_answers_500_with_the_error(worker):
    t = _task(worker)
    _later(0.05, _finish, t, "FAILED", "ValueError: the worker's own text")
    t0 = time.monotonic()
    with pytest.raises(urllib.error.HTTPError) as ei:
        _get(worker, t, wait_ms=1000)
    assert time.monotonic() - t0 < 0.6
    assert ei.value.code == 500
    assert b"the worker's own text" in ei.value.read()


@within(30)
def test_finished_while_a_get_waits_completes_the_stream(worker):
    t = _task(worker)
    _later(0.05, _finish, t)
    resp = _get(worker, t, wait_ms=1000)
    assert resp.status == 204 and resp.headers.get("X-Complete") == "true"
    assert t.complete_served == [True]


@within(30)
def test_delete_wakes_a_held_get(worker):
    """DELETE aborts the task: the held GET answers, and a puller
    whose stall asks for the status learns the task is gone."""
    t = _task(worker)
    _later(0.05, worker.delete_task, t.spec.task_id)
    t0 = time.monotonic()
    resp = _get(worker, t, wait_ms=1000)
    assert time.monotonic() - t0 < 0.6
    assert resp.status == 204 and resp.headers.get("X-Complete") == "false"

    t = _task(worker)
    _later(0.05, worker.delete_task, t.spec.task_id)

    def stall():
        rpc.call_json("GET", f"{worker.uri}/v1/task/{t.spec.task_id}/status")

    t0 = time.monotonic()
    with pytest.raises(urllib.error.HTTPError) as ei:
        rpc.pull_pages(worker.uri, t.spec.task_id, 0, stall=stall)
    assert ei.value.code == 404
    assert time.monotonic() - t0 < 0.6


@pytest.mark.parametrize("how", ["drain", "shutdown", "fault_kill"])
@within(60)
def test_a_worker_that_leaves_holds_no_request(how):
    """The worker leaves 0.1 s after the handler began to hold the GET
    (not after the GET was sent: under load the request may take that
    long to arrive): the GET is answered then, not at its max-wait."""
    w = _boot()
    try:
        t = _task(w)
        stop = {
            "drain": lambda: w.drain(grace_s=0.3),
            "shutdown": lambda: w.shutdown(graceful=False),
            "fault_kill": w._fault_kill,
        }[how]
        held, left = threading.Event(), {}
        wait_for = t.cond.wait_for

        def holding(*a):
            held.set()
            return wait_for(*a)

        t.cond.wait_for = holding

        def leave():
            assert held.wait(30)
            time.sleep(0.1)
            left["at"] = time.monotonic()
            stop()

        th = threading.Thread(target=leave, daemon=True)
        th.start()
        before = _counts()
        resp = _get(w, t, wait_ms=1000)
        got = time.monotonic()
        th.join(30)
        assert resp.status == 204 and resp.headers.get("X-Complete") == "false"
        after = _counts()
        assert after["worker.results_waits"] == before["worker.results_waits"] + 1
        assert (  # woken, not run out
            after["worker.results_wait_timeouts"]
            == before["worker.results_wait_timeouts"]
        )
        assert got - left["at"] < 0.6
    finally:
        w.shutdown(graceful=False)


# ----------------------------------------------------------- the puller's end


@pytest.mark.parametrize("depth", [1, 2, 3])
@within(30)
def test_one_page_task_is_pulled_without_a_stall(worker, monkeypatch, depth):
    """The page at 30 ms, FINISHED at 60 ms: the speculative "no page
    yet" of token 1, asked for before the page existed, is dropped and
    not slept on; the pull ends with the task."""
    sleeps = _no_sleep(monkeypatch)
    t = _task(worker)
    stalls = []
    _later(0.03, t.offer_page, _page(7))
    done = _later(0.06, _finish, t)
    t0 = time.monotonic()
    pages = rpc.pull_pages(
        worker.uri, t.spec.task_id, 0, depth=depth,
        stall=lambda: stalls.append(1), site="coordinator",
    )
    took = time.monotonic() - t0
    done.join()
    assert _values(pages) == [7]
    assert stalls == [] and sleeps == []
    assert 0.06 <= took < 0.5, took


@pytest.fixture(scope="module")
def served():
    from presto_tpu.server import CoordinatorServer, PrestoTpuClient

    coord = CoordinatorServer().start()
    w = worker_mod.WorkerServer(coordinator_uri=coord.uri).start()
    deadline = time.monotonic() + 10
    while time.monotonic() < deadline and not coord.active_workers():
        time.sleep(0.05)
    client = PrestoTpuClient(coord.uri, timeout_s=600)
    sql = (
        "select count(*), sum(l_quantity) from tpch.tiny.lineitem "
        "where l_discount < 0.05"
    )
    want = client.execute(sql).rows()  # compiles
    yield client, sql, want
    faults.configure(None)
    w.shutdown(graceful=False)
    coord.shutdown()


@within(60)
def test_served_statement_pulls_with_no_stall_and_no_sleep(served):
    """Every task 50 ms late: the coordinator's GETs are held, none
    runs out, nothing stalls or sleeps."""
    client, sql, want = served
    faults.configure({"seed": 1, "rules": [
        {"action": "delay", "task": ".", "delay_s": 0.05, "count": -1},
    ]})
    try:
        before = device_snapshot()
        assert client.execute(sql).rows() == want
        after = device_snapshot()
    finally:
        faults.configure(None)
    d = {k: after[k] - before.get(k, 0) for k in after}
    assert d["coordinator.pull_stalls"] == 0
    assert d["worker.results_wait_timeouts"] == 0
    assert d["worker.results_waits"] >= 1
    assert d["wait_ms.worker.results_wait"] >= 40
    assert d.get("wait_ms.coordinator.pull_idle", 0) == 0
    assert "wait_ms.coordinator.pull_stall" not in after


@within(60)
def test_served_statement_surfaces_a_failed_task_that_was_waited_on(served):
    from presto_tpu.server.client import QueryFailed

    client, sql, _ = served
    faults.configure({"seed": 1, "rules": [
        {"action": "delay", "task": ".", "delay_s": 0.05, "count": -1},
        {"action": "kill_task", "task": ".", "count": -1},
    ]})
    try:
        with pytest.raises(QueryFailed) as ei:
            client.execute(sql).rows()
    finally:
        faults.configure(None)
    assert "injected task kill" in str(ei.value)


@within(30)
def test_max_wait_elapsed_stalls_once_without_a_sleep(worker, monkeypatch):
    monkeypatch.setattr(rpc, "PULL_MAX_WAIT_S", 0.1)
    sleeps = _no_sleep(monkeypatch)
    t = _task(worker)
    stalls = []
    _later(0.15, t.offer_page, _page(3))
    done = _later(0.17, _finish, t)
    pages = rpc.pull_pages(
        worker.uri, t.spec.task_id, 0, stall=lambda: stalls.append(1),
    )
    done.join()
    assert _values(pages) == [3]
    assert stalls == [1]  # one max-wait ran out, then the page came
    assert sleeps == []


@within(30)
def test_a_peer_that_does_not_hold_is_polled_with_a_sleep(worker, monkeypatch):
    """The fallback: a draining peer (or one that ignores the header)
    answers at once, and the puller must not spin on it."""
    t = _task(worker)
    monkeypatch.setattr(worker, "_draining", True)
    stalls = []
    _later(0.1, t.offer_page, _page(5))
    done = _later(0.12, _finish, t)
    before = device_snapshot().get("wait_ms.worker.pull_idle", 0)
    pages = rpc.pull_pages(
        worker.uri, t.spec.task_id, 0, stall=lambda: stalls.append(1),
        site="worker",
    )
    done.join()
    assert _values(pages) == [5]
    assert 1 <= len(stalls) <= 8  # ~20 ms a round, not a spin
    assert device_snapshot()["wait_ms.worker.pull_idle"] - before >= 20


@pytest.mark.parametrize("depth", [1, 2])
@within(120)
def test_finished_race_drops_no_page(worker, depth):
    """The last page and FINISHED land back to back while requests for
    the token beyond the end are in flight or held: every page arrives,
    once, in order."""
    for trial in range(25):
        t = _task(worker)
        n = 1 + trial % 3

        def produce(t=t, n=n, trial=trial):
            for i in range(n):
                time.sleep(0.001 * (trial % 4))
                t.offer_page(_page(100 * trial + i))
            _finish(t)

        th = threading.Thread(target=produce, daemon=True)
        th.start()
        pages = rpc.pull_pages(worker.uri, t.spec.task_id, 0, depth=depth)
        th.join()
        assert _values(pages) == [100 * trial + i for i in range(n)]
        assert t.complete_served == [True]
        # consumed pages were freed only below the acked floor
        assert t.part_acked[0] <= n


@within(30)
def test_speculative_request_frees_nothing_unconsumed(worker):
    """X-Ack floor: a held head request and a speculative one beyond
    it leave the page at the head in the buffer."""
    t = _task(worker)
    t.offer_page(_page(1))
    t.offer_page(_page(2))
    assert _get(worker, t, token=1, ack=0).status == 200  # speculative
    assert t.parts[0][0] is not None
    assert _get(worker, t, token=0, ack=0, wait_ms=1000).status == 200
    assert _get(worker, t, token=2, ack=2).status == 204
    assert t.parts[0][:2] == [None, None]


@within(60)
def test_forty_slow_pulls_do_not_queue_behind_the_pool(worker):
    """More held pulls than the shared pool has threads: the head
    requests ride their own threads, so the pool stays free while all
    forty are held, and every pull ends when its page comes. (Starts
    and offers are spread out: a burst of forty connections overruns
    the HTTP server's listen backlog, which is not what this tests.)"""
    n = 40
    assert n > rpc._PULL_POOL_WORKERS
    tasks = [_task(worker) for _ in range(n)]
    got, ended = [None] * n, [0.0] * n

    def pull(i):
        got[i] = rpc.pull_pages(worker.uri, tasks[i].spec.task_id, 0, depth=2)
        ended[i] = time.monotonic()

    threads = [threading.Thread(target=pull, args=(i,), daemon=True)
               for i in range(n)]
    for th in threads:
        th.start()
        time.sleep(0.01)
    time.sleep(0.2)
    # all forty are held now; a job given to the pool runs at once
    t0 = time.monotonic()
    probe = rpc._pull_executor().submit(time.monotonic)
    assert probe.result(timeout=1.0) - t0 < 0.2
    offered = []
    for i, t in enumerate(tasks):
        offered.append(time.monotonic())
        t.offer_page(_page(i))
        _finish(t)
        time.sleep(0.01)
    for th in threads:
        th.join(10)
    assert [_values(p) for p in got] == [[i] for i in range(n)]
    lags = sorted(e - o for e, o in zip(ended, offered))
    assert lags[n // 2] < 0.2, lags  # the median pull ends with its task


@within(30)
def test_counters_follow_the_telemetry_switch(worker, monkeypatch):
    """The three counters are the registry's, served by
    ``device_snapshot()`` from the start and still with telemetry off."""
    assert all(isinstance(v, int) for v in _counts().values())
    monkeypatch.setattr(rpc, "PULL_MAX_WAIT_S", 0.05)
    before = _counts()
    DEVICE.set_enabled(False)
    try:
        assert _get(worker, _task(worker), wait_ms=1000).status == 204
    finally:
        DEVICE.set_enabled(True)
    assert _counts() == before
