"""The reduction from a trace to busy time, idle share, top operations
and labelled gaps gives known numbers on a known trace."""

import os

import pytest

from benchmark import reduce

DATA = os.path.join(os.path.dirname(__file__), "data")


@pytest.fixture(scope="module")
def planes():
    return reduce.load_planes(os.path.join(DATA, "known_trace.pbtxt"))


def test_planes_and_lines_are_read(planes):
    assert set(planes) == {"/device:TPU:0", "/host:CPU"}
    ops = reduce.device_ops(planes, "tpu")["/device:TPU:0"]
    assert sorted(ops) == [
        ("?/copy.3", 500.0, 1500.0), ("jit_fragment/fusion.1", 2000.0, 3000.0),
        ("jit_fragment/fusion.1", 6000.0, 7000.0), ("jit_fragment/fusion.2", 2500.0, 4000.0),
    ]  # named <program>/<operation>; the "XLA Modules" line is not an operation
    assert reduce.programs(planes, 1000.0, 11000.0) == 1


def test_union_counts_overlap_once():
    assert reduce.union([(2, 3), (2.5, 4), (6, 7), (7, 8), (9, 9)]) == [(2, 4), (6, 8)]


def test_busy_idle_and_window(planes):
    red = reduce.reduce_trace(planes, "tpu")
    assert red["window_s"] == pytest.approx(10e-6)
    assert red["busy_s"] == pytest.approx(3.5e-6)  # not 4.0: overlap once, clipped
    assert red["devices"] == 1 and red["events"] == 4
    assert 100 * (1 - red["busy_s"] / red["window_s"]) == pytest.approx(65.0)


def test_top_ops_by_name(planes):
    red = reduce.reduce_trace(planes, "tpu")
    assert [n for n, _ in red["device_ops"]] == [
        "jit_fragment/fusion.1", "jit_fragment/fusion.2", "?/copy.3"]
    got = dict(red["device_ops"])
    assert got["jit_fragment/fusion.1"] == pytest.approx(2e-6)
    assert got["jit_fragment/fusion.2"] == pytest.approx(1.5e-6)
    assert got["?/copy.3"] == pytest.approx(0.5e-6)  # clipped to the window


def test_gaps_are_labelled_by_the_statement_in_flight(planes):
    red = reduce.reduce_trace(planes, "tpu")
    got = dict()
    for name, s in red["idle_gaps"]:
        got.setdefault(name, []).append(s)
    assert got["sum:q1/in-jax-runtime"] == [pytest.approx(4e-6)]
    assert got["sum:q6/outside-jax"] == [pytest.approx(2.5e-6)]
    assert sorted(got["gap:q6/outside-jax"]) == [pytest.approx(0.5e-6), pytest.approx(2e-6)]
    assert got["gap:q1/in-jax-runtime"] == [pytest.approx(4e-6)]
    assert len(red["idle_gaps"]) == 5
    # idle + busy = window
    total = sum(s for n, s in red["idle_gaps"] if n.startswith("sum:"))
    assert total + red["busy_s"] == pytest.approx(red["window_s"])


def test_gap_outside_any_statement():
    assert reduce.label((0, 10), [("stmt:q1", 0, 4)]) == reduce.BETWEEN
    assert reduce.label((0, 10), [("stmt:a", 0, 10), ("stmt:b", 2, 9)]) == "a+b"


def test_no_device_events_reads_as_nothing(planes):
    red = reduce.reduce_trace({"/host:CPU": planes["/host:CPU"]}, "tpu")
    assert red["busy_s"] == 0.0 and red["devices"] == 0 and red["programs"] == 0


def test_covered():
    merged = [(0.0, 2.0), (5.0, 6.0), (9.0, 20.0)]
    starts = [m[0] for m in merged]
    assert reduce.covered(merged, starts, 1.0, 10.0) == 3.0
    assert reduce.covered(merged, starts, 2.0, 5.0) == 0.0
    assert reduce.covered(merged, starts, 9.5, 30.0) == 10.5
    assert reduce.covered([], [], 0.0, 1.0) == 0.0


def test_short_names():
    assert reduce.short_op("%reduce-window.3 = (u32[8192,128]{0,1}) reduce-window(...)") \
        == "reduce-window.3"
    assert reduce.short_module("jit_trace(16379248631086762812)") == "jit_trace"


def test_at_most_ten_entries():
    evs = [(f"op{i}", 100.0 * i, 100.0 * i + 10) for i in range(40)]
    planes = {
        "/device:TPU:0": {"XLA Ops": evs},
        "/host:CPU": {"t": [(reduce.WINDOW, 0.0, 4000.0)]},
    }
    red = reduce.reduce_trace(planes, "tpu")
    assert len(red["device_ops"]) == 10 and len(red["idle_gaps"]) == 10
