#!/usr/bin/env python3
"""Which of the engine's spans was the host in while the device idled?

    PRESTO_TPU_PROFILE_SPANS=1 python3 benchmark/run.py --workload sf1_scan_agg \\
        --seed 1 --seconds 51 --trace 1 --keep-trace DIR
    python3 tools/trace_gaps.py DIR

With the switch on, every ``tracing.phase`` is a ``presto:<name>[/<site>]``
annotation in the same ``.xplane.pb`` as the device's operations. Each idle
gap of the device inside the harness's ``bench:window`` goes to

- the innermost *work* span on any thread (the host was busy: with what), else
- every thread of the statement being in a ``wait``, the ``wait`` that
  **started last** — the end of the chain, the one nothing else waited for —
  by its site, else
- ``unattributed`` (no span of the engine covered it),

instant by instant; the gap as a whole goes to the label holding most of it.

Prints ``sum:`` per name/site by whole gaps and ``slice:`` by exact time,
``stmt:`` the same per statement in flight, the ten longest gaps, the
unattributed rest and the idle time per statement under waits plus
unattributed, which is what the ``unworked_ms_per_stmt`` counter should agree
with. The arithmetic is ``benchmark/reduce.py``'s; only the labels are new.
"""

from __future__ import annotations

import argparse
import json
import os
import sys
from bisect import bisect_right
from typing import Dict, List, Tuple

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)

from benchmark import reduce  # noqa: E402

PREFIX = "presto:"
WAIT = "wait"
UNATTRIBUTED = "unattributed"

Event = Tuple[str, float, float]


def load_planes_by_thread(path: str) -> Dict[str, Dict[str, List[Event]]]:
    """``reduce.load_planes`` with the host's lines kept apart: the
    profiler names every Python thread's line after the process, and one
    merged line would make another thread's span look like a parent."""
    from jax.profiler import ProfileData

    planes: Dict[str, Dict[str, List[Event]]] = {}
    for plane in ProfileData.from_file(path).planes:
        lines = planes.setdefault(plane.name, {})
        host = plane.name.startswith("/host:")
        for i, line in enumerate(plane.lines):
            key = f"{line.name}#{i}" if host else line.name
            evs = lines.setdefault(key, [])
            for e in line.events:
                evs.append(
                    (e.name, float(e.start_ns), float(e.start_ns + e.duration_ns))
                )
    return planes


def span_threads(planes) -> List[List[Event]]:
    """The engine's spans, one sorted list a host thread."""
    out = []
    for name, lines in planes.items():
        if not name.startswith("/host:"):
            continue
        for evs in lines.values():
            mine = sorted(
                (e for e in evs if e[0].startswith(PREFIX)), key=lambda e: e[1]
            )
            if mine:
                out.append(mine)
    return out


def flatten(thread: List[Event]) -> List[Tuple[float, float, str, float]]:
    """One thread's spans as disjoint segments ``(start, end, label,
    span_start)``, each carrying the innermost span open at that time
    (the spans of one thread nest)."""
    segs: List[Tuple[float, float, str, float]] = []
    stack: List[Event] = []
    cur = float("-inf")

    def close(upto: float) -> None:
        nonlocal cur
        if stack and upto > cur:
            name, start, _ = stack[-1]
            segs.append((cur, upto, name[len(PREFIX):], start))
        cur = max(cur, upto)

    for ev in sorted(thread, key=lambda e: (e[1], -e[2])):
        while stack and stack[-1][2] <= ev[1]:
            close(stack[-1][2])
            stack.pop()
        close(ev[1])
        stack.append(ev)
    while stack:
        close(stack[-1][2])
        stack.pop()
    return segs


def _is_wait(label: str) -> bool:
    return label.split("/", 1)[0] == WAIT


def slices(gap: Tuple[float, float], timelines) -> Dict[str, float]:
    """The gap's time by label. At every instant: the innermost span
    of each thread; a *work* span on any thread takes the instant (the
    one that started last, should two threads work); else the ``wait``
    that started last; else ``unattributed``."""
    lo, hi = gap
    segs = []
    for starts, tl in timelines:
        i = max(bisect_right(starts, lo) - 1, 0)
        while i < len(tl) and tl[i][0] < hi:
            if tl[i][1] > lo:
                segs.append(tl[i])
            i += 1
    if not segs:
        return {UNATTRIBUTED: hi - lo}
    cuts = sorted({lo, hi, *(min(max(x, lo), hi) for s in segs for x in s[:2])})
    out: Dict[str, float] = {}
    for a, b in zip(cuts, cuts[1:]):
        best = None  # (is work, span start, label)
        for s, e, label, start in segs:
            if s <= a and e >= b:
                key = (not _is_wait(label), start, label)
                if best is None or key > best:
                    best = key
        what = best[2] if best else UNATTRIBUTED
        out[what] = out.get(what, 0.0) + (b - a)
    return out


def timelines_of(planes):
    out = []
    for thread in span_threads(planes):
        tl = flatten(thread)
        out.append(([seg[0] for seg in tl], tl))
    return out


def analyse(planes, platform: str, top: int = 10) -> dict:
    """Every idle gap of the window, labelled; seconds throughout.
    ``sums`` gives each gap whole to the label holding most of it (what
    ``gap:`` entries show); ``slices`` splits every gap exactly."""
    win = reduce.annotations(planes, reduce.WINDOW)
    if not win:
        raise ValueError(f"the trace has no '{reduce.WINDOW}' annotation")
    lo, hi = win[0][1], win[0][2]
    stmts = [e for e in reduce.annotations(planes, reduce.STMT) if e[2] > lo and e[1] < hi]
    timelines = timelines_of(planes)
    idle = []
    for evs in reduce.device_ops(planes, platform).values():
        evs = reduce.clip(evs, lo, hi)
        if evs:
            idle.extend(reduce.gaps(reduce.union((s, e) for _, s, e in evs), lo, hi))
    by_label: Dict[str, List[float]] = {}
    by_slice: Dict[str, float] = {}
    by_stmt: Dict[str, float] = {}
    for g in idle:
        parts = slices(g, timelines)
        what = max(parts.items(), key=lambda kv: kv[1])[0]
        by_label.setdefault(what, []).append((g[1] - g[0]) / 1e9)
        stmt = reduce.label(g, stmts)
        for k, ns in parts.items():
            by_slice[k] = by_slice.get(k, 0.0) + ns / 1e9
            key = f"{stmt}|{k}"
            by_stmt[key] = by_stmt.get(key, 0.0) + ns / 1e9
    return {
        "window_s": (hi - lo) / 1e9,
        "idle_s": sum(sum(v) for v in by_label.values()),
        "stmts": len(stmts),
        "span_threads": len(timelines),
        "sums": sorted(((k, sum(v)) for k, v in by_label.items()), key=lambda kv: -kv[1]),
        "slices": sorted(by_slice.items(), key=lambda kv: -kv[1]),
        "by_stmt": sorted(by_stmt.items(), key=lambda kv: -kv[1]),
        "longest": sorted(
            ((k, x) for k, v in by_label.items() for x in v), key=lambda kv: -kv[1]
        )[:top],
        "unattributed_s": by_slice.get(UNATTRIBUTED, 0.0),
        "waits_plus_unattributed_s": sum(
            v for k, v in by_slice.items() if k == UNATTRIBUTED or _is_wait(k)
        ),
    }


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("trace_dir", help="the DIR of a run with --keep-trace DIR")
    ap.add_argument("--platform", default="tpu", choices=("tpu", "cpu"))
    ap.add_argument("--json", action="store_true", help="one JSON object instead of lines")
    args = ap.parse_args(argv)
    planes = load_planes_by_thread(reduce.find_xplane(args.trace_dir))
    out = analyse(planes, args.platform)
    if args.json:
        print(json.dumps(out))
        return 0
    idle = out["idle_s"] or 1.0
    print(f"window {out['window_s']:.3f} s, device idle {out['idle_s']:.3f} s, "
          f"{out['stmts']} statements, {out['span_threads']} threads with spans")
    for k, v in out["sums"]:
        print(f"sum:{k} {v:.4f} s {100.0 * v / idle:.1f}%")
    for k, v in out["slices"]:
        print(f"slice:{k} {v:.4f} s {100.0 * v / idle:.1f}%")
    for k, v in out["by_stmt"][:20]:
        print(f"stmt:{k} {v:.4f} s")
    for k, v in out["longest"]:
        print(f"gap:{k} {v:.4f} s")
    print(f"unattributed {out['unattributed_s']:.4f} s "
          f"{100.0 * out['unattributed_s'] / idle:.1f}% of idle")
    if out["stmts"]:
        per = 1e3 * out["waits_plus_unattributed_s"] / out["stmts"]
        print(f"waits+unattributed {per:.2f} ms/stmt (compare unworked_ms_per_stmt)")
    if not out["span_threads"]:
        print("no presto: span in the trace: was PRESTO_TPU_PROFILE_SPANS=1 set?",
              file=sys.stderr)
        return 1
    return 0


if __name__ == "__main__":
    sys.exit(main())
