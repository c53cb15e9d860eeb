"""From the profiler's trace to numbers: device busy time as the union
of the intervals in which an operation ran, the idle share, the
operations that took most time, and the idle gaps labelled by the
statement that was in flight. Pure functions over plain tuples, so the
arithmetic is tested on a known trace (``tests/test_reduce.py``).

An event is ``(name, start_ns, end_ns)``.
"""

from __future__ import annotations

import glob
import os
import re
from bisect import bisect_right
from typing import Dict, Iterable, List, Sequence, Tuple

Event = Tuple[str, float, float]

WINDOW = "bench:window"  # the harness's annotation around the traced slice
STMT = "stmt:"  # prefix of its annotation around each client call
BETWEEN = "between-statements"
IN_JAX = "in-jax-runtime"  # the host was inside a JAX/PjRt call (dispatch, transfer)
OUTSIDE_JAX = "outside-jax"  # the host was elsewhere: Python, HTTP, waiting


def find_xplane(trace_dir: str) -> str:
    found = sorted(glob.glob(os.path.join(trace_dir, "plugins", "profile", "*", "*.xplane.pb")))
    if not found:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    return found[-1]


def load_planes(path: str) -> Dict[str, Dict[str, List[Event]]]:
    """``{plane: {line: [event, ...]}}`` of an ``.xplane.pb`` file (a
    ``.pbtxt`` is read as the text form of the same message)."""
    from jax.profiler import ProfileData

    if path.endswith(".pbtxt"):
        with open(path) as f:
            data = ProfileData.from_serialized_xspace(
                ProfileData.text_proto_to_serialized_xspace(f.read())
            )
    else:
        data = ProfileData.from_file(path)
    planes: Dict[str, Dict[str, List[Event]]] = {}
    for plane in data.planes:
        lines = planes.setdefault(plane.name, {})
        for line in plane.lines:
            evs = lines.setdefault(line.name, [])
            for e in line.events:
                evs.append((e.name, float(e.start_ns), float(e.start_ns + e.duration_ns)))
    return planes


_DEVICE_PLANE = re.compile(r"^/device:TPU:\d+$")
# XLA:CPU runs its programs on the calling thread; the rehearsal takes
# the executor's own TraceMe as "the device" so every reader has input
_CPU_OPS = re.compile(r"^(PjRtCpuExecutable|TfrtCpuExecutable)::Execute$")


def short_op(name: str) -> str:
    """``%fusion.17 = (u32[6]...) fusion(...)`` -> ``fusion.17``."""
    return name.split(" = ", 1)[0].lstrip("%")[:48]


def short_module(name: str) -> str:
    """``jit_trace(16379248631086762812)`` -> ``jit_trace``."""
    return name.split("(", 1)[0][:30]


def device_ops(planes, platform: str) -> Dict[str, List[Event]]:
    """Per device, the events of operations that ran on it, named
    ``<program>/<operation>`` in XLA's names. On a TPU: the ``XLA Ops``
    and ``Async XLA Ops`` (DMA) lines of each ``/device:TPU:n`` plane,
    the program taken from the ``XLA Modules`` event around the op."""
    out: Dict[str, List[Event]] = {}
    if platform == "tpu":
        for name, lines in planes.items():
            if not _DEVICE_PLANE.match(name):
                continue
            mods = sorted(lines.get("XLA Modules", ()), key=lambda e: e[1])
            starts = [m[1] for m in mods]
            evs = []
            for ln in ("XLA Ops", "Async XLA Ops"):
                for n, s, e in lines.get(ln, ()):
                    i = bisect_right(starts, s) - 1
                    mod = short_module(mods[i][0]) if i >= 0 and s < mods[i][2] else "?"
                    evs.append((f"{mod}/{short_op(n)}", s, e))
            out[name] = evs
        return out
    evs = [
        e for name, lines in planes.items() if name.startswith("/host:")
        for line in lines.values() for e in line if _CPU_OPS.match(e[0])
    ]
    return {"/host:CPU": evs}


def annotations(planes, prefix: str) -> List[Event]:
    """Host events whose name starts with ``prefix``, from any thread."""
    return sorted(
        (e for name, lines in planes.items() if name.startswith("/host:")
         for line in lines.values() for e in line if e[0].startswith(prefix)),
        key=lambda e: e[1],
    )


def union(intervals: Iterable[Tuple[float, float]]) -> List[Tuple[float, float]]:
    """Merged, sorted intervals: time covered twice counts once."""
    out: List[List[float]] = []
    for lo, hi in sorted(i for i in intervals if i[1] > i[0]):
        if out and lo <= out[-1][1]:
            out[-1][1] = max(out[-1][1], hi)
        else:
            out.append([lo, hi])
    return [(lo, hi) for lo, hi in out]


def clip(events: Sequence[Event], lo: float, hi: float) -> List[Event]:
    return [(n, max(s, lo), min(e, hi)) for n, s, e in events if e > lo and s < hi]


def gaps(busy: Sequence[Tuple[float, float]], lo: float, hi: float):
    """The idle intervals of ``[lo, hi]`` given merged busy intervals."""
    out, at = [], lo
    for s, e in busy:
        if s > at:
            out.append((at, s))
        at = max(at, e)
    if hi > at:
        out.append((at, hi))
    return out


def programs(planes, lo: float, hi: float) -> int:
    """Program executions (``XLA Modules`` events) that began on any
    device inside ``[lo, hi)``: every launch, counted or not by the
    engine's own ``dispatches``."""
    return sum(
        1 for name, lines in planes.items() if _DEVICE_PLANE.match(name)
        for _, s, _ in lines.get("XLA Modules", ()) if lo <= s < hi
    )


def covered(merged: Sequence[Tuple[float, float]], starts: Sequence[float],
            lo: float, hi: float) -> float:
    """How much of ``[lo, hi]`` the merged, sorted intervals cover;
    ``starts`` are their starts (kept by the caller, for the bisection)."""
    i, total = max(bisect_right(starts, lo) - 1, 0), 0.0
    while i < len(merged) and merged[i][0] < hi:
        total += max(0.0, min(merged[i][1], hi) - max(merged[i][0], lo))
        i += 1
    return total


def label(gap: Tuple[float, float], stmts: Sequence[Event]) -> str:
    """The statement(s) in flight over most of the gap, else BETWEEN."""
    lo, hi = gap
    cover: Dict[str, float] = {}
    for name, s, e in stmts:
        o = min(e, hi) - max(s, lo)
        if o > 0:
            cover[name[len(STMT):]] = cover.get(name[len(STMT):], 0.0) + o
    names = sorted(n for n, o in cover.items() if o >= 0.5 * (hi - lo))
    return "+".join(names) if names else BETWEEN


def reduce_trace(planes, platform: str, top: int = 10) -> dict:
    """Everything the traced run reports, in seconds."""
    win = annotations(planes, WINDOW)
    if not win:
        raise ValueError(f"the trace has no '{WINDOW}' annotation")
    lo, hi = win[0][1], win[0][2]
    stmts = annotations(planes, STMT)
    # where the host was inside a traced JAX/PjRt call (any thread)
    in_jax = union(
        (s, e) for name, lines in planes.items() if name.startswith("/host:")
        for line in lines.values() for n, s, e in line
        if not n.startswith((WINDOW, STMT))
    )
    in_jax_starts = [m[0] for m in in_jax]

    def what(gap) -> str:
        share = covered(in_jax, in_jax_starts, gap[0], gap[1]) / (gap[1] - gap[0])
        return f"{label(gap, stmts)}/{IN_JAX if share >= 0.5 else OUTSIDE_JAX}"

    per_device = {
        d: clip(evs, lo, hi) for d, evs in device_ops(planes, platform).items()
    }
    per_device = {d: evs for d, evs in per_device.items() if evs}
    if not per_device:
        return {"window_s": (hi - lo) / 1e9, "busy_s": 0.0, "devices": 0, "programs": 0,
                "device_ops": [], "idle_gaps": [], "events": 0}
    busy_s, op_s, idle = [], {}, []
    for evs in per_device.values():
        merged = union((s, e) for _, s, e in evs)
        busy_s.append(sum(e - s for s, e in merged) / 1e9)
        for n, s, e in evs:
            op_s[n] = op_s.get(n, 0.0) + (e - s) / 1e9
        idle.extend(gaps(merged, lo, hi))
    by_label: Dict[str, List[float]] = {}
    for g in idle:
        by_label.setdefault(what(g), []).append((g[1] - g[0]) / 1e9)
    # half the entries: where the idle time is, summed by what was in
    # flight; the other half: the longest single gaps
    totals = sorted(((f"sum:{k}", sum(v)) for k, v in by_label.items()),
                    key=lambda kv: -kv[1])[: top // 2]
    longest = sorted(((f"gap:{k}", x) for k, v in by_label.items() for x in v),
                     key=lambda kv: -kv[1])[: top - len(totals)]
    n = len(per_device)
    return {
        "window_s": (hi - lo) / 1e9,
        "busy_s": sum(busy_s) / n,
        "devices": n,
        "events": sum(len(v) for v in per_device.values()),
        "programs": programs(planes, lo, hi),
        "device_ops": [[k, v / n] for k, v in
                       sorted(op_s.items(), key=lambda kv: -kv[1])[:top]],
        "idle_gaps": [[k, v] for k, v in totals + longest],
    }
