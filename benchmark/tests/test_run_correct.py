"""A whole run of the harness with the look for a chip skipped
(``jax.devices`` answers as one v5e would, the cell's schema is the
rehearsal's): sound, ``correct`` is true and every compared number
holds its limit; with the served path broken underneath — an answer
altered as it leaves the program, a statement that never answers —
``correct`` comes out false and the numbers say which limit broke."""

import argparse
import dataclasses
import json
import os
import time

import pytest

from benchmark import discovery, harness

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


class _Chip:
    platform = "tpu"
    device_kind = "TPU v5 lite"

    def memory_stats(self):
        return {"peak_bytes_in_use": 1}


def _altered(execute):
    """Every answer's first number off by one unit of its last place."""
    def wrapped(self, sql):
        got = execute(self, sql)
        if got.data and got.data[0]:
            row = list(got.data[0])
            j = next(i for i, v in enumerate(row)
                     if isinstance(v, (int, float)) and not isinstance(v, bool))
            row[j] = row[j] + 1
            got.data[0] = row
        return got
    return wrapped


def _silent(execute):
    """The window's first statement never answers; the others do."""
    calls = {"n": 0}

    def wrapped(self, sql):
        calls["n"] += 1
        if calls["n"] == 5:
            raise TimeoutError("no answer")
        return execute(self, sql)
    return wrapped


@pytest.mark.parametrize("fault,correct,broke", [
    (None, True, None),
    (_altered, False, "wrong"),
    (_silent, False, "unanswered"),
], ids=["sound", "answer-altered", "never-answers"])
def test_a_run_is_correct_only_while_every_answer_is_the_references(
        fault, correct, broke, monkeypatch, capsys):
    import jax
    from presto_tpu.server.client import PrestoTpuClient

    cell = discovery.load_cell(ROOT, "sf1_scan_agg")
    cell = dataclasses.replace(
        cell, config=dict(cell.config, schema=cell.config["rehearsal_schema"]))
    monkeypatch.setattr(jax, "devices", lambda *a, **k: [_Chip()])
    if fault is not None:
        monkeypatch.setattr(PrestoTpuClient, "execute", fault(PrestoTpuClient.execute))
    args = argparse.Namespace(workload=cell.name, seed=2 ** 31 + 34, seconds=0.5,
                              trace=0, keep_trace=None)
    rc = harness.run(cell, args, time.monotonic())
    out, err = capsys.readouterr()
    result = json.loads(out.strip().splitlines()[-1])
    assert result["correct"] is correct and rc == (0 if correct else 1)
    assert list(result)[-1] == "compared" and result["attempted"] >= 6
    held = {k: (c["value"] >= c["limit"] if c["holds"] == "at least"
                else c["value"] <= c["limit"]) for k, c in result["compared"].items()}
    assert [k for k, ok in held.items() if not ok] == ([broke] if broke else [])
    # the same numbers, each beside its limit, are the last lines of standard error
    last = err.strip().splitlines()[-len(held):]
    assert last == [f"compared {k}: {c['value']} ({c['holds']} {c['limit']})"
                    for k, c in result["compared"].items()]
