"""Query-lifecycle tracing: per-query span trees + traceparent headers.

Reference parity: presto attributes every query's wall time to a tree
of runtime objects (QueryStats -> StageStats -> TaskStats ->
OperatorStats) and exposes it at ``GET /v1/query/{id}`` (SURVEY.md
§5.1). Here the same attribution is a span tree: each phase of the
lifecycle (plan -> fragment -> schedule -> task -> staging/execute ->
gather) opens a :class:`Span`, and the coordinator propagates a
W3C-``traceparent``-style header on every worker call so worker-side
spans join the query's tree under one trace id — the id that appears
in both coordinator and worker logs.

The tree is servable WHILE the query runs (an open span has
``end == 0``), which is what makes "what is query q_7 doing right now"
answerable from ``/v1/query/{id}``.

Measurement rides the same primitive. :func:`phase` times a piece of
a statement's path on ``time.perf_counter_ns()`` and, on close, adds
its SELF time (duration minus the phases that closed inside it on the
same thread) to a process-wide accumulator keyed by one of eight
names: seven kinds of *work* (:data:`WORK`, one a layer of PERF.md §3)
and ``wait`` — every place a statement's thread blocks on another
thread, a timer or a socket (``site=`` says which).
``telemetry.device_snapshot()`` serves the accumulator as
``span_ms.<name>`` / ``wait_ms.<site>`` / ``stmt_wall_ms``, which is
how the benchmark reads host time per layer. :meth:`Trace.span` is a
:func:`phase` that also hangs a node on the query's tree. With
``PRESTO_TPU_PROFILE_SPANS=1`` every phase is also a
``jax.profiler.TraceAnnotation`` named ``presto:<name>[/<site>]``, so
a profile holds the engine's spans beside the device's operations
(``tools/trace_gaps.py`` reads them).
"""

from __future__ import annotations

import dataclasses
import os
import threading
import time
import uuid
from typing import Dict, List, Optional

#: the work names: the thread is running the engine's code (for
#: ``fetch``: blocked on the device). One name a layer boundary;
#: detail goes in ``site=`` and attributes, never in the name.
WORK = (
    "protocol", "plan", "schedule", "staging", "dispatch", "fetch", "exec",
)
#: every blocking on another thread, a timer or a socket
WAIT = "wait"
#: prefix of the profiler annotations (``presto:<name>[/<site>]``)
ANNOTATION = "presto:"

#: ``PRESTO_TPU_PROFILE_SPANS=1``: phases are also TraceAnnotations on
#: the profiler's clock. Read once; off by default because the
#: benchmark's reduction counts every host event it does not know as
#: time inside the JAX runtime.
PROFILE_SPANS = os.environ.get("PRESTO_TPU_PROFILE_SPANS", "") not in (
    "", "0",
)

#: the served tree's display names (README "Observability") and the
#: accumulator name each one's self time belongs to
_TREE_LAYER = {
    "query": "exec",
    "execute": "exec",
    "gather": "exec",
    "task": "exec",
    "execute-local": "exec",
    "execute-local-fallback": "exec",
    "fragment": "plan",
    "dynfilter": "schedule",
    "recovery": "schedule",
    "stage:prefetch": "staging",
}
_TREE_LAYER.update({n: n for n in WORK})

_tls = threading.local()
_acc_lock = threading.Lock()
_self_ns: Dict[str, int] = {n: 0 for n in WORK + (WAIT,)}
_wait_ns: Dict[str, int] = {}
_stmt_wall_ns = 0
_accumulate = True  # follows telemetry.DEVICE.enabled


def set_accumulating(flag: bool) -> None:
    """``telemetry.enabled=false`` freezes the accumulator too."""
    global _accumulate
    _accumulate = bool(flag)


class _Phase:
    """Context manager of :func:`phase`; ``span`` is the tree node
    when :meth:`Trace.span` made it."""

    __slots__ = (
        "name", "site", "span", "_trace", "_t0", "_child_ns", "_ann",
    )

    def __init__(self, name, site, trace=None, span=None):
        self.name = name
        self.site = site
        self.span = span
        self._trace = trace
        self._child_ns = 0
        self._ann = None

    def __enter__(self):
        try:
            stack = _tls.stack
        except AttributeError:
            stack = _tls.stack = []
        stack.append(self)
        if self._trace is not None:
            self._trace._push(self.span)
        if PROFILE_SPANS:
            import jax

            label = ANNOTATION + self.name
            if self.site:
                label += "/" + self.site
            if self.span is not None:
                self._ann = jax.profiler.TraceAnnotation(
                    label, trace_id=self.span.trace_id
                )
            else:
                self._ann = jax.profiler.TraceAnnotation(label)
            self._ann.__enter__()
        self._t0 = time.perf_counter_ns()
        return self.span

    def __exit__(self, exc_type, exc, tb):
        dur = time.perf_counter_ns() - self._t0
        if self._ann is not None:
            self._ann.__exit__(exc_type, exc, tb)
        stack = _tls.stack
        if stack and stack[-1] is self:
            stack.pop()
        elif self in stack:
            stack.remove(self)
        if stack:
            stack[-1]._child_ns += dur
        if self._trace is not None:
            self._trace._pop(self.span, dur, failed=exc is not None)
        if _accumulate:
            with _acc_lock:
                _self_ns[self.name] += max(dur - self._child_ns, 0)
                if self.name == WAIT:
                    _wait_ns[self.site] = (
                        _wait_ns.get(self.site, 0) + dur
                    )
        return False


def phase(name: str, site: str = "") -> _Phase:
    """Time one piece of a statement's path: ``with phase("staging"):``.
    ``name`` is one of :data:`WORK` or ``"wait"``. Only code running
    for a statement opens phases — heartbeats, samplers and announcers
    do not. Never keep one open across a generator's ``yield``: the
    nesting is per thread."""
    if name not in _self_ns:
        raise ValueError(f"unknown phase name {name!r}")
    return _Phase(name, site)


def wait(site: str) -> _Phase:
    """The phase around a blocking call; ``site`` is
    ``"<module>.<what>"``, unique per call site."""
    return _Phase(WAIT, site)


def add_stmt_wall(ns: int) -> None:
    """One statement's whole wall time, client side (a duration, not
    a self time): what the work names are subtracted from."""
    global _stmt_wall_ns
    if _accumulate:
        with _acc_lock:
            _stmt_wall_ns += ns


def span_snapshot() -> Dict[str, float]:
    """The accumulator in ms: ``span_ms.<work name>`` self times,
    ``span_ms.unworked`` = statement wall minus the seven — the time
    statements spent with no thread working on them (a poller that
    woke late, a queue, a sleep, code with no phase) —
    ``stmt_wall_ms``, and ``wait_ms.<site>`` per wait site, raw and
    overlapping across threads."""
    with _acc_lock:
        work = {n: _self_ns[n] / 1e6 for n in WORK}
        waits = {s: ns / 1e6 for s, ns in _wait_ns.items()}
        wall = _stmt_wall_ns / 1e6
    out = {f"span_ms.{n}": v for n, v in work.items()}
    out["span_ms.unworked"] = wall - sum(work.values())
    out["stmt_wall_ms"] = wall
    for s in sorted(waits):
        out[f"wait_ms.{s}"] = waits[s]
    return out

#: traceparent version field (only 00 exists; parsed leniently)
_TP_VERSION = "00"


def new_trace_id() -> str:
    return uuid.uuid4().hex  # 32 lowercase hex chars, W3C width


def new_span_id() -> str:
    return uuid.uuid4().hex[:16]  # 16 hex chars, W3C width


def format_traceparent(trace_id: str, span_id: str) -> str:
    """``00-{trace}-{span}-01`` (sampled flag always on)."""
    return f"{_TP_VERSION}-{trace_id}-{span_id}-01"


def parse_traceparent(header: Optional[str]):
    """Header -> (trace_id, parent_span_id), or None when absent or
    malformed (a bad header must never fail the task carrying it)."""
    if not header:
        return None
    parts = header.strip().split("-")
    if len(parts) != 4:
        return None
    _, trace_id, span_id, _ = parts
    if len(trace_id) != 32 or len(span_id) != 16:
        return None
    return trace_id, span_id


@dataclasses.dataclass
class Span:
    """One timed phase of a query. ``end == 0`` means still open."""

    trace_id: str
    span_id: str
    parent_id: Optional[str]
    name: str
    start: float
    end: float = 0.0
    attrs: Dict[str, object] = dataclasses.field(default_factory=dict)
    #: ``time.perf_counter_ns()`` duration, set when a live span
    #: closes; ``start``/``end`` are wall-clock, for display only
    dur_ns: int = 0

    @property
    def duration_ms(self) -> float:
        if self.dur_ns:
            return self.dur_ns / 1e6
        end = self.end or time.time()
        return (end - self.start) * 1000.0

    def to_dict(self) -> dict:
        return {
            "trace_id": self.trace_id,
            "span_id": self.span_id,
            "parent_id": self.parent_id,
            "name": self.name,
            "start": self.start,
            "end": self.end,
            "duration_ms": self.duration_ms,
            "attrs": dict(self.attrs),
        }

    @staticmethod
    def from_dict(d: dict) -> "Span":
        return Span(
            trace_id=d.get("trace_id", ""),
            span_id=d.get("span_id", ""),
            parent_id=d.get("parent_id"),
            name=d.get("name", ""),
            start=float(d.get("start", 0.0)),
            end=float(d.get("end", 0.0)),
            attrs=dict(d.get("attrs") or {}),
        )


class Trace:
    """One query's span tree; thread-safe, servable mid-flight.

    Spans opened on the same thread nest implicitly (a thread-local
    stack provides the parent); spans opened on OTHER threads (stage
    runner pools, exchange pull threads) parent to the trace's root
    span unless an explicit ``parent`` is given — so a fan-out of
    concurrent stages still hangs off the one query root.
    """

    def __init__(self, trace_id: Optional[str] = None):
        self.trace_id = trace_id or new_trace_id()
        self._lock = threading.Lock()
        self._spans: List[Span] = []
        self.root: Optional[Span] = None

    # ------------------------------------------------------------ spans

    def span(self, name: str, parent: Optional[Span] = None, **attrs):
        """Open a span; use as ``with trace.span("plan"):``. ``name``
        is one of the tree's display names; its self time accumulates
        under the name's layer (a :func:`phase` with ``site=name``)."""
        layer = _TREE_LAYER.get(name)
        if layer is None:
            raise ValueError(f"unknown span name {name!r}")
        if parent is None:
            # the innermost open span of THIS trace on this thread
            # (the phase stack is the one nesting record), else root
            parent = next(
                (
                    p.span
                    for p in reversed(getattr(_tls, "stack", ()))
                    if p._trace is self
                ),
                self.root,
            )
        s = Span(
            trace_id=self.trace_id,
            span_id=new_span_id(),
            parent_id=parent.span_id if parent is not None else None,
            name=name,
            start=time.time(),
            attrs=dict(attrs),
        )
        return _Phase(
            layer, "" if layer == name else name, trace=self, span=s
        )

    def _push(self, span: Span) -> None:
        with self._lock:
            self._spans.append(span)
            if self.root is None:
                self.root = span

    def _pop(self, span: Span, dur_ns: int, failed: bool = False) -> None:
        span.end = time.time()
        span.dur_ns = dur_ns
        if failed:
            span.attrs["error"] = True

    def graft(self, span_dicts) -> None:
        """Attach foreign (worker-side) spans to this tree. Spans whose
        trace id differs are re-homed under this trace — a worker that
        ignored the traceparent still lands in the right query."""
        spans = [
            Span.from_dict(d) if isinstance(d, dict) else d
            for d in (span_dicts or ())
        ]
        with self._lock:
            for s in spans:
                s.trace_id = self.trace_id
                if s.parent_id is None and self.root is not None:
                    s.parent_id = self.root.span_id
                self._spans.append(s)

    def traceparent(self, span: Optional[Span] = None) -> str:
        """Header value carrying this trace + the given (or root) span
        as parent, for coordinator->worker propagation."""
        parent = span or self.root
        sid = parent.span_id if parent is not None else new_span_id()
        return format_traceparent(self.trace_id, sid)

    # -------------------------------------------------------- rendering

    def spans(self) -> List[Span]:
        with self._lock:
            return list(self._spans)

    def to_tree(self) -> List[dict]:
        """Nested span dicts (children under ``"children"``), roots
        first. Orphans (parent never seen, e.g. pruned worker spans)
        surface as roots rather than vanishing."""
        spans = self.spans()
        by_id = {s.span_id: s.to_dict() for s in spans}
        for d in by_id.values():
            d["children"] = []
        roots: List[dict] = []
        for s in spans:
            d = by_id[s.span_id]
            parent = by_id.get(s.parent_id) if s.parent_id else None
            if parent is not None and parent is not d:
                parent["children"].append(d)
            else:
                roots.append(d)
        return roots


def synthesize_task_spans(
    trace_id: str,
    parent_span_id: Optional[str],
    task_id: str,
    node_id: str,
    start: float,
    end: float,
    staging_ms: float,
    execute_ms: float,
    prefetch_ms: float = 0.0,
) -> List[dict]:
    """Worker-side span tree for one task, synthesized from its phase
    accumulators: a ``task`` span with ``staging`` and ``execute``
    children (plus a ``stage:prefetch`` child when pipelined prefetch
    staging overlapped host transfers with device execution — its
    duration co-anchored with ``execute`` makes the overlap visible in
    EXPLAIN ANALYZE). Batches interleave staging and execution, so the
    children carry aggregate durations anchored at the task start
    rather than one span per batch (bounded payload however many
    splits streamed).
    """
    task_span = Span(
        trace_id=trace_id,
        span_id=new_span_id(),
        parent_id=parent_span_id,
        name="task",
        start=start,
        end=end,
        attrs={"task_id": task_id, "node_id": node_id},
    )
    out = [task_span]
    for name, dur_ms in (
        ("staging", staging_ms),
        ("stage:prefetch", prefetch_ms),
        ("execute", execute_ms),
    ):
        if dur_ms <= 0:
            continue
        out.append(
            Span(
                trace_id=trace_id,
                span_id=new_span_id(),
                parent_id=task_span.span_id,
                name=name,
                start=start,
                end=start + dur_ms / 1000.0,
                attrs={"task_id": task_id},
            )
        )
    return [s.to_dict() for s in out]
