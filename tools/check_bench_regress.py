#!/usr/bin/env python3
"""Bench-trajectory regression gate.

Diffs consecutive ``BENCH_*.json`` artifacts (the bench driver's
``{n, cmd, rc, tail, parsed}`` capture, where ``tail`` holds the
JSONL result lines) and flags any metric that degraded by more than
the threshold (default 20%) between two consecutive rounds.

Skip discipline (see bench.py ``_emit``):

- a line with ``skipped: true`` is a skip — it carries no value and
  never participates in a comparison, in either role;
- a LEGACY line carrying ``error`` beside a value (the pre-contract
  ``value: 0`` shape old artifacts shipped) is treated as skipped
  too — that zero was never a measurement and must neither flag a
  drop against the round before it nor serve as the baseline that
  makes the next real round look like an infinite improvement;
- a missing/None/non-numeric value is a skip (null-safe end to end).

Direction comes from the unit: throughput-like units (rows/s,
queries/s, qps, x, queries) regress by DROPPING; time-like units (ms,
s, seconds) regress by RISING. Unknown units default to higher-better.

Exit status: 1 if any regression was flagged, else 0. Skipped lines
alone can never fail the gate.

Usage::

    python tools/check_bench_regress.py [--threshold 0.2] [FILES...]

With no FILES, globs ``BENCH_*.json`` in the repo root (sorted, so
``_rNN`` ordering is the round ordering).
"""

from __future__ import annotations

import argparse
import glob
import json
import os
import sys
from typing import Dict, List, Optional, Tuple

#: units where a SMALLER value is the regression (hits: the serving
#: result-cache hit count — a cache that silently stopped hitting is
#: a serving regression even when raw qps survives)
_HIGHER_BETTER = {"rows/s", "queries/s", "qps", "x", "queries", "hits"}
#: units where a LARGER value is the regression (dispatches/bytes:
#: the exchange-plane device accounting — per-query dispatch counts
#: and transfer bytes regress upward)
_LOWER_BETTER = {"ms", "s", "seconds", "dispatches", "bytes"}


def is_skipped(line: dict) -> bool:
    """True when the line carries no real measurement (skip contract
    + legacy error-beside-value shape + null safety)."""
    if line.get("skipped"):
        return True
    if "error" in line:
        # pre-contract artifacts: value 0 beside the
        # error — a failed measurement, not a measured zero
        return True
    value = line.get("value")
    return not isinstance(value, (int, float)) or isinstance(
        value, bool
    )


def parse_lines(tail: str) -> Dict[str, dict]:
    """Extract metric lines from a JSONL tail, last write wins
    (re-measured metrics supersede), non-JSON noise skipped."""
    out: Dict[str, dict] = {}
    for raw in tail.splitlines():
        raw = raw.strip()
        if not raw.startswith("{"):
            continue
        try:
            line = json.loads(raw)
        except ValueError:
            continue
        if isinstance(line, dict) and "metric" in line:
            out[line["metric"]] = line
    return out


def parse_artifact(obj: dict) -> Dict[str, dict]:
    """Metric -> result line of one BENCH_*.json capture. ``tail`` is
    authoritative; ``parsed`` (the headline line) backstops artifacts
    whose tail was truncated past the JSONL."""
    lines = parse_lines(obj.get("tail") or "")
    parsed = obj.get("parsed")
    if (
        isinstance(parsed, dict)
        and parsed.get("metric")
        and parsed["metric"] not in lines
    ):
        lines[parsed["metric"]] = parsed
    return lines


def _direction(unit: Optional[str]) -> int:
    """+1 = higher is better (drop regresses), -1 = lower is better."""
    return -1 if (unit or "") in _LOWER_BETTER else 1


def compare(
    prev: Dict[str, dict],
    cur: Dict[str, dict],
    threshold: float = 0.2,
) -> List[dict]:
    """Regressions between two rounds: metrics measured (non-skipped)
    in BOTH whose value moved against its unit's direction by more
    than ``threshold`` (relative). Returns finding dicts."""
    findings: List[dict] = []
    for metric in sorted(set(prev) & set(cur)):
        a, b = prev[metric], cur[metric]
        if is_skipped(a) or is_skipped(b):
            continue
        va, vb = float(a["value"]), float(b["value"])
        if va == 0:
            continue  # no meaningful relative change from zero
        change = (vb - va) / abs(va)
        if _direction(b.get("unit") or a.get("unit")) * change < -threshold:
            findings.append(
                {
                    "metric": metric,
                    "unit": b.get("unit") or a.get("unit"),
                    "before": va,
                    "after": vb,
                    "change_pct": round(100.0 * change, 1),
                }
            )
    return findings


def check_files(
    paths: List[str], threshold: float = 0.2
) -> Tuple[List[dict], int]:
    """Run the gate over consecutive artifact pairs; returns
    (findings, rounds_compared)."""
    rounds: List[Tuple[str, Dict[str, dict]]] = []
    for p in paths:
        try:
            with open(p) as f:
                obj = json.load(f)
        except (OSError, ValueError) as e:
            print(f"bench-regress: unreadable {p}: {e}", file=sys.stderr)
            continue
        rounds.append((p, parse_artifact(obj)))
    findings: List[dict] = []
    for (pa, a), (pb, b) in zip(rounds, rounds[1:]):
        for f in compare(a, b, threshold):
            f["from"], f["to"] = os.path.basename(pa), os.path.basename(pb)
            findings.append(f)
    return findings, max(len(rounds) - 1, 0)


def main(argv: Optional[List[str]] = None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("files", nargs="*", help="BENCH_*.json artifacts, in round order")
    ap.add_argument(
        "--threshold",
        type=float,
        default=0.2,
        help="relative degradation that flags (default 0.2 = 20%%)",
    )
    args = ap.parse_args(argv)
    paths = args.files or sorted(
        glob.glob(
            os.path.join(
                os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
                "BENCH_*.json",
            )
        )
    )
    if len(paths) < 2:
        print("bench-regress: need at least two artifacts; nothing to diff")
        return 0
    findings, pairs = check_files(paths, args.threshold)
    for f in findings:
        print(
            f"REGRESSION {f['metric']} [{f['unit']}] "
            f"{f['from']} -> {f['to']}: "
            f"{f['before']:g} -> {f['after']:g} ({f['change_pct']:+.1f}%)"
        )
    if not findings:
        print(f"bench-regress: OK ({pairs} consecutive pairs, no regressions)")
    return 1 if findings else 0


if __name__ == "__main__":
    sys.exit(main())
