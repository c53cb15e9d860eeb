"""``chip_smoke.py`` rehearsed on the CPU: the same phases as on the
chip at ``--schema tiny``, every one compared and ``ok``, and a last
line that can never read ``ok: true`` off the chip."""

import contextlib
import io
import json
import os
import sys

import pytest

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import chip_smoke  # noqa: E402

SERVED_PHASES = [
    "device", "boot", "q6", "q1", "q3", "q1_warm", "point_lookup",
    "double_key",
]


def _run(argv):
    out = io.StringIO()
    with contextlib.redirect_stdout(out):
        rc = chip_smoke.main(argv)
    lines = [json.loads(ln) for ln in out.getvalue().splitlines()]
    return rc, lines


@pytest.fixture(scope="module")
def served():
    return _run(["--schema", "tiny"])


def test_every_phase_runs_and_is_ok(served):
    _, lines = served
    phases = {ln["phase"]: ln for ln in lines if "phase" in ln}
    assert list(phases) == SERVED_PHASES + ["total"]
    for name in SERVED_PHASES:
        assert phases[name]["ok"] is True, phases[name]
        assert phases[name]["seconds"] >= 0
    assert phases["total"]["failed"] == []
    assert phases["device"]["compile_cache_dir"]
    assert phases["point_lookup"]["lookups"] == 32
    for name in SERVED_PHASES[2:]:
        dev = phases[name]["device"]
        assert dev["dispatches"] > 0, (name, dev)
        assert phases[name]["rows"] > 0


def test_warm_q1_neither_compiles_nor_restages(served):
    _, lines = served
    phases = {ln["phase"]: ln for ln in lines if "phase" in ln}
    cold, warm = phases["q1"]["device"], phases["q1_warm"]["device"]
    assert warm["compile_ms"] == 0
    assert warm["h2d_bytes"] * 4 < cold["h2d_bytes"]


def test_last_line_says_not_ok_on_the_cpu(served):
    rc, lines = served
    last = lines[-1]
    assert rc == 1
    assert set(last) == {"ok", "device"}
    assert last["ok"] is False
    assert set(last["device"]) == {"platform", "kind", "count"}
    assert last["device"]["platform"] == "cpu"
    assert last["device"]["count"] >= 1


def test_a_failed_phase_fails_the_run_and_the_rest_still_run(monkeypatch):
    """One phase mismatches, one raises: both print ``ok: false`` with
    the error, every other phase still runs and passes, exit code 1."""
    real_q6 = chip_smoke._numpy_q6
    monkeypatch.setattr(
        chip_smoke, "_numpy_q6", lambda *a: real_q6(*a) + 1
    )
    monkeypatch.setattr(
        chip_smoke, "_DOUBLE_KEY",
        "select no_such_column from tpch.{s}.lineitem",
    )
    rc, lines = _run(["--schema", "tiny"])
    phases = {ln["phase"]: ln for ln in lines if "phase" in ln}
    assert rc == 1
    assert list(phases) == SERVED_PHASES + ["total"]
    assert phases["q6"]["ok"] is False
    assert "!= numpy" in phases["q6"]["error"]
    assert phases["double_key"]["ok"] is False
    assert "no_such_column" in phases["double_key"]["error"]
    for name in SERVED_PHASES:
        if name not in ("q6", "double_key"):
            assert phases[name]["ok"] is True, phases[name]
    assert phases["total"]["failed"] == ["q6", "double_key"]
    assert lines[-1]["ok"] is False


def test_mesh_phases_on_four_virtual_devices():
    """``--chips 4`` runs only the mesh executor and its one-device
    reference: sharded inputs on four distinct devices, an all-to-all
    in a compiled fragment, rows equal."""
    rc, lines = _run(["--schema", "tiny", "--chips", "4"])
    phases = {ln["phase"]: ln for ln in lines if "phase" in ln}
    assert list(phases) == ["device", "mesh", "mesh_q3", "mesh_q1", "total"]
    for name in ("mesh", "mesh_q3", "mesh_q1"):
        assert phases[name]["ok"] is True, phases[name]
    assert len(phases["mesh"]["mesh_devices"]) == 4
    for name in ("mesh_q3", "mesh_q1"):
        assert phases[name]["shard_devices"] == 4
        assert "all-to-all" in phases[name]["collectives"]
    assert rc == 1 and lines[-1]["ok"] is False
    assert lines[-1]["device"]["platform"] == "cpu"
