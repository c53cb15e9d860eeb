"""bench.py failure lines: a config that could
not be measured — backend-init failure included — must emit a
``"skipped": true`` line with NO value, never ``value: 0`` (a zero
reads as a measured 0 rows/s and poisons the metric trajectory)."""

import json
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(__file__)))

import bench  # noqa: E402


def test_skip_line_has_no_value():
    line = bench.skip_line(
        "tpch_q1_sf1_rows_per_sec",
        RuntimeError("Unable to initialize backend 'tpu'"),
    )
    assert line["skipped"] is True
    assert "value" not in line
    assert line["metric"] == "tpch_q1_sf1_rows_per_sec"
    assert "Unable to initialize backend" in line["error"]
    json.dumps(line)  # driver contract: one JSON-able line


def test_skip_line_truncates_long_errors():
    line = bench.skip_line("m", RuntimeError("x" * 1000))
    assert len(line["error"]) <= 300


def test_bench_source_never_emits_zero_value_error_lines():
    """Every failure path in the driver must route through skip_line:
    no hand-built '"value": 0 + error' dict may reappear."""
    src = open(bench.__file__, encoding="utf-8").read()
    assert '"value": 0' not in src
    assert src.count("skip_line(") >= 3  # def + both failure paths


def test_every_print_site_routes_through_emit():
    """The ONE raw print of a result line lives inside _emit — every
    other site calls it, so the skip contract is enforced at the last
    moment for every line the driver will ever emit (the hole was a
    failure path that printed its own dict)."""
    src = open(bench.__file__, encoding="utf-8").read()
    assert src.count("print(json.dumps(") == 1  # _emit's own print
    assert src.count("_emit(") >= 15


def test_emit_converts_error_value_line_to_skip(capsys):
    """Defense in depth: a line that somehow carries BOTH an error and
    a value is demoted to a skip at print time — value: 0 beside an
    error can never reach the metric trajectory again."""
    bench._emit(
        {
            "metric": "tpch_q1_sf1_rows_per_sec",
            "value": 0,
            "unit": "rows/s",
            "error": "Unable to initialize backend 'tpu'",
        }
    )
    line = json.loads(capsys.readouterr().out.strip())
    assert line["skipped"] is True
    assert "value" not in line
    assert line["metric"] == "tpch_q1_sf1_rows_per_sec"
    assert "'tpu'" in line["error"]


def test_emit_passes_clean_lines_through(capsys):
    good = {"metric": "m", "value": 42, "unit": "rows/s"}
    bench._emit(good)
    line = json.loads(capsys.readouterr().out.strip())
    assert line == good


def test_emit_leaves_real_skips_alone(capsys):
    skip = bench.skip_line("m", RuntimeError("boom"))
    bench._emit(skip)
    line = json.loads(capsys.readouterr().out.strip())
    assert line == skip
