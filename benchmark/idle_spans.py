"""The device's idle time put down to the engine's spans.

While a profiler session collects, the engine opens each of its phases
(``presto_tpu/utils/tracing.py``) as a ``presto:<name>[/<site>]``
annotation, so a traced run's ``.xplane.pb`` holds them on the device's
clock. Every instant inside ``bench:window`` at which a device runs no
operation goes to

- the innermost *work* span open on any host thread (the host was busy:
  with what); should two threads work, the span that started last;
- else the ``wait`` that started last (the end of the chain, the one
  nothing else waited for);
- else ``untraced`` (no span of the engine was open).

The client's own waits (``wait/client.*``) take no instant. The
benchmark's client runs in the server's process and waits on its round
trip for the whole statement, so under the rule above every instant at
which no server span is open would go to it; it goes to ``untraced``
instead, which is what it is: server code that opened no span. The
client's own work (``protocol/client`` outside its waits) still counts
as ``protocol``.

``idle_s.<name>`` sums the idle time so attributed by the span's name,
per device and averaged over the devices as ``reduce.busy_s`` is; the
nine add up to the window's idle time. A trace with no ``presto:`` event
in the window (a program that does not annotate) gives nothing, so its
metrics are left out rather than read as all ``untraced``. The
``idle_<name>_ms_per_stmt`` metrics read the fields through :func:`read`.

An event is ``(name, start_ns, end_ns)``, as in ``reduce.py``.
"""

from __future__ import annotations

import glob
import heapq
import os
import tempfile
from typing import Dict, List, Sequence, Tuple

from benchmark import reduce

Event = reduce.Event
Segment = Tuple[float, float, str, float]  # start, end, label, span start

PREFIX = "presto:"
WAIT = "wait"
CLIENT_WAIT = WAIT + "/client."  # the client's own waits: they take no instant
UNTRACED = "untraced"
#: the engine's span names (``tracing.WORK``, then ``wait``) and the rest
NAMES = ("protocol", "plan", "schedule", "staging", "dispatch", "fetch", "exec",
         WAIT, UNTRACED)
TOP = 20  # entries of the ``idle_spans`` line


def load_planes_by_thread(path: str) -> Dict[str, Dict[str, List[Event]]]:
    """``reduce.load_planes`` with the host's lines kept apart, keyed
    ``<line>#<index>``: the profiler names every Python thread's line
    after the process, and one merged line would make another thread's
    span look like a parent."""
    from jax.profiler import ProfileData

    planes: Dict[str, Dict[str, List[Event]]] = {}
    for plane in ProfileData.from_file(path).planes:
        lines = planes.setdefault(plane.name, {})
        host = plane.name.startswith("/host:")
        for i, line in enumerate(plane.lines):
            evs = lines.setdefault(f"{line.name}#{i}" if host else line.name, [])
            for e in line.events:
                evs.append((e.name, float(e.start_ns), float(e.start_ns + e.duration_ns)))
    return planes


def span_threads(planes) -> List[List[Event]]:
    """The engine's spans, one sorted list a host thread."""
    out = []
    for name, lines in planes.items():
        if not name.startswith("/host:"):
            continue
        for evs in lines.values():
            mine = sorted((e for e in evs if e[0].startswith(PREFIX)), key=lambda e: e[1])
            if mine:
                out.append(mine)
    return out


def flatten(thread: List[Event]) -> List[Segment]:
    """One thread's spans as disjoint segments ``(start, end, label,
    span_start)``, each carrying the innermost span open at that time
    (the spans of one thread nest)."""
    segs: List[Segment] = []
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


def attribute(threads: Sequence[List[Segment]]) -> List[Tuple[float, float, str]]:
    """The process's one timeline: disjoint, sorted ``(start, end,
    label)``, at every instant the label of the segment that takes it —
    a work span over a wait, and of two alike the one whose span started
    last. Instants that no segment but the client's waits covers are
    not in it."""
    segs = sorted((s for thread in threads for s in thread
                   if s[1] > s[0] and not s[2].startswith(CLIENT_WAIT)),
                  key=lambda s: s[0])
    rank = {lb: i for i, lb in enumerate(sorted({s[2] for s in segs}))}
    cuts = sorted({x for s in segs for x in s[:2]})
    out: List[Tuple[float, float, str]] = []
    heap: list = []  # the largest (is work, span start, label) on top
    i = 0
    for a, b in zip(cuts, cuts[1:]):
        while i < len(segs) and segs[i][0] <= a:
            s, e, label, start = segs[i]
            heapq.heappush(heap, (_is_wait(label), -start, -rank[label], e, label))
            i += 1
        while heap and heap[0][3] <= a:
            heapq.heappop(heap)
        if not heap:
            continue
        label = heap[0][4]
        if out and out[-1][1] == a and out[-1][2] == label:
            out[-1] = (out[-1][0], b, label)
        else:
            out.append((a, b, label))
    return out


def split(gaps: Sequence[Tuple[float, float]], timeline) -> List[Dict[str, float]]:
    """Each of the sorted, disjoint ``gaps`` by label (ns), the part no
    label holds as ``untraced``."""
    out, j = [], 0
    for lo, hi in gaps:
        while j < len(timeline) and timeline[j][1] <= lo:
            j += 1
        parts: Dict[str, float] = {}
        held, k = 0.0, j
        while k < len(timeline) and timeline[k][0] < hi:
            o = min(timeline[k][1], hi) - max(timeline[k][0], lo)
            if o > 0:
                parts[timeline[k][2]] = parts.get(timeline[k][2], 0.0) + o
                held += o
            k += 1
        if hi - lo > held:
            parts[UNTRACED] = parts.get(UNTRACED, 0.0) + (hi - lo - held)
        out.append(parts)
    return out


def idle_by_span(planes, platform: str) -> dict:
    """``idle_s.<name>`` for each of :data:`NAMES`, ``idle_by_stmt``
    (seconds by ``<statement in flight>|<name>[/<site>]``) and
    ``presto_events`` (the engine's spans in the window); ``{}`` where
    the window holds no such span or no device ran an operation in it."""
    win = reduce.annotations(planes, reduce.WINDOW)
    if not win:
        raise ValueError(f"the trace has no '{reduce.WINDOW}' annotation")
    lo, hi = win[0][1], win[0][2]
    threads = [[e for e in t if e[2] > lo and e[1] < hi] for t in span_threads(planes)]
    events = sum(len(t) for t in threads)
    per_device = [
        evs for evs in (reduce.clip(v, lo, hi)
                        for v in reduce.device_ops(planes, platform).values())
        if evs
    ]
    if not events or not per_device:
        return {}
    timeline = attribute([flatten(t) for t in threads if t])
    stmts = reduce.annotations(planes, reduce.STMT)
    n = len(per_device)
    out = {f"idle_s.{x}": 0.0 for x in NAMES}
    by_stmt: Dict[str, float] = {}
    for evs in per_device:
        idle = reduce.gaps(reduce.union((s, e) for _, s, e in evs), lo, hi)
        for g, parts in zip(idle, split(idle, timeline)):
            stmt = reduce.label(g, stmts)
            for k, ns in parts.items():
                out[f"idle_s.{k.split('/', 1)[0]}"] += ns / 1e9 / n
                by_stmt[f"{stmt}|{k}"] = by_stmt.get(f"{stmt}|{k}", 0.0) + ns / 1e9 / n
    return dict(out, presto_events=events, idle_by_stmt=by_stmt)


# ------------------------------------------------------- the readers' entry


def _run_trace(window_s: float) -> dict:
    """``idle_by_span`` of this run's trace: the harness's newest
    profile directory whose ``bench:window`` lasts ``window_s`` (the
    reduction's own reading of the same events, so to the nanosecond),
    or ``{}`` where none does. The harness removes its directory after
    the run, so an older one is another run's, left by a run killed."""
    import jax

    found = sorted(
        glob.glob(os.path.join(tempfile.gettempdir(), "bench_trace_*", "plugins",
                               "profile", "*", "*.xplane.pb")),
        key=os.path.getmtime, reverse=True)
    for path in found:
        planes = load_planes_by_thread(path)
        win = reduce.annotations(planes, reduce.WINDOW)
        if win and abs((win[0][2] - win[0][1]) / 1e9 - window_s) < 1e-9:
            return idle_by_span(planes, jax.devices()[0].platform)
    return {}


def read(obs: dict, spec: dict):
    """The reader of every ``idle_<name>_ms_per_stmt`` metric: where
    ``obs["trace"]`` lacks the ``idle_s.*`` fields, the first of the nine
    reads them from the run's trace into it (keeping what it found as
    ``obs["idle_spans"]``, and logging the top entries by statement);
    then ``spec["read"]`` is read as the harness's generic reader does.
    A program without the annotations gives no field, so the
    ``KeyError`` leaves the metric out."""
    from benchmark import harness

    trace = obs["trace"]
    if spec["read"]["field"] not in trace and "idle_spans" not in obs:
        got = obs["idle_spans"] = _run_trace(trace["window_s"])
        if not got:
            # no trace of this window, or no span in it: said, not silent
            harness.log(event="idle_spans", presto_events=0)
        else:
            stmts = max(obs["stmts"], 1)
            harness.log(
                event="idle_spans", presto_events=got["presto_events"],
                presto_events_per_stmt=got["presto_events"] / stmts,
                idle_ms_per_stmt={x: 1e3 * got[f"idle_s.{x}"] / stmts for x in NAMES},
                top=[[k, 1e3 * v / stmts] for k, v in sorted(
                    got["idle_by_stmt"].items(), key=lambda kv: -kv[1])[:TOP]])
            trace.update((f"idle_s.{x}", got[f"idle_s.{x}"]) for x in NAMES)
    return harness.generic_read(obs, spec["read"])
