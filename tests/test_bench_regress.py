"""tools/check_bench_regress.py: the bench-trajectory gate.

Fixture-driven: synthetic BENCH_*.json artifacts exercise the skip
contract (modern ``skipped: true`` lines, the legacy r04/r05
``value: 0`` + ``error`` shape, null values), both unit directions,
and the consecutive-pair diffing — plus the real repo artifacts,
which must never fail the gate (r04/r05 carry error lines)."""

import glob
import json
import os
import sys

sys.path.insert(
    0,
    os.path.join(os.path.dirname(os.path.dirname(__file__)), "tools"),
)

from check_bench_regress import (  # noqa: E402
    check_files,
    compare,
    is_skipped,
    main,
    parse_artifact,
    parse_lines,
)


def _artifact(tmp_path, name, lines, parsed=None):
    tail = "\n".join(json.dumps(ln) for ln in lines)
    p = tmp_path / name
    p.write_text(
        json.dumps(
            {"n": 1, "cmd": "bench", "rc": 0, "tail": tail,
             "parsed": parsed}
        )
    )
    return str(p)


def _line(metric, value, unit="rows/s", **kw):
    out = {"metric": metric, "value": value, "unit": unit}
    out.update(kw)
    return out


# ------------------------------------------------------ skip contract


def test_skipped_flag_is_skip():
    assert is_skipped(
        {"metric": "m", "skipped": True, "unit": "rows/s",
         "error": "X: boom"}
    )


def test_legacy_error_beside_value_is_skip():
    # the r04/r05 pre-contract shape: a zero that was never measured
    assert is_skipped(
        {"metric": "m", "value": 0, "unit": "rows/s", "error": "X"}
    )


def test_null_or_missing_value_is_skip():
    assert is_skipped({"metric": "m", "value": None, "unit": "x"})
    assert is_skipped({"metric": "m", "unit": "x"})
    assert is_skipped({"metric": "m", "value": True, "unit": "x"})
    assert not is_skipped({"metric": "m", "value": 3.5, "unit": "x"})


def test_skipped_lines_never_flag():
    prev = {"m": _line("m", 1000)}
    cur = {"m": _line("m", 0, error="backend died")}
    assert compare(prev, cur) == []
    # and a skip as the BASELINE must not make the recovery round
    # look like a regression (or crash on the missing value)
    assert compare(cur, prev) == []


# --------------------------------------------------------- directions


def test_throughput_drop_flags():
    prev = {"m": _line("m", 1000)}
    cur = {"m": _line("m", 700)}
    (f,) = compare(prev, cur)
    assert f["metric"] == "m" and f["change_pct"] == -30.0


def test_throughput_drop_within_threshold_passes():
    assert compare({"m": _line("m", 1000)}, {"m": _line("m", 850)}) == []


def test_latency_rise_flags():
    prev = {"p99": _line("p99", 10.0, unit="ms")}
    cur = {"p99": _line("p99", 14.0, unit="ms")}
    (f,) = compare(prev, cur)
    assert f["metric"] == "p99" and f["change_pct"] == 40.0


def test_latency_drop_is_improvement():
    prev = {"p99": _line("p99", 14.0, unit="ms")}
    cur = {"p99": _line("p99", 7.0, unit="ms")}
    assert compare(prev, cur) == []


def test_zero_baseline_never_divides():
    prev = {"m": _line("m", 0.0, unit="x")}
    cur = {"m": _line("m", 5.0, unit="x")}
    assert compare(prev, cur) == []


# ------------------------------------------------------------ parsing


def test_parse_lines_skips_noise_and_keeps_last():
    tail = "\n".join(
        [
            "WARNING: not json",
            json.dumps(_line("m", 10)),
            "{torn json",
            json.dumps(_line("m", 20)),
        ]
    )
    lines = parse_lines(tail)
    assert lines["m"]["value"] == 20


def test_parse_artifact_parsed_backstops_truncated_tail():
    obj = {"tail": "no json here", "parsed": _line("hl", 42)}
    assert parse_artifact(obj)["hl"]["value"] == 42


# ------------------------------------------------- end-to-end on files


def test_check_files_consecutive_pairs(tmp_path):
    a = _artifact(tmp_path, "BENCH_t01.json", [_line("m", 1000)])
    b = _artifact(tmp_path, "BENCH_t02.json", [_line("m", 950)])
    c = _artifact(tmp_path, "BENCH_t03.json", [_line("m", 600)])
    findings, pairs = check_files([a, b, c])
    assert pairs == 2
    # only the b->c drop flags; a->c (non-consecutive, -40%) is not
    # a pair the gate judges
    (f,) = findings
    assert f["from"] == "BENCH_t02.json" and f["to"] == "BENCH_t03.json"


def test_main_exit_codes(tmp_path, capsys):
    a = _artifact(tmp_path, "BENCH_t01.json", [_line("m", 1000)])
    b = _artifact(tmp_path, "BENCH_t02.json", [_line("m", 100)])
    assert main([a, b]) == 1
    assert "REGRESSION" in capsys.readouterr().out
    skip = _artifact(
        tmp_path, "BENCH_t03.json",
        [{"metric": "m", "skipped": True, "unit": "rows/s",
          "error": "X"}],
    )
    assert main([a, skip]) == 0
    assert main([a]) == 0  # one artifact: nothing to diff, not a failure


def test_real_repo_artifacts_pass():
    """Whatever ``BENCH_*.json`` artifacts sit at the repo root must
    not fail the gate (none are kept in the tree today: the records of
    the retired back end were deleted, so this returns early)."""
    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    paths = sorted(glob.glob(os.path.join(root, "BENCH_*.json")))
    if len(paths) < 2:
        return
    findings, _ = check_files(paths)
    assert findings == []
