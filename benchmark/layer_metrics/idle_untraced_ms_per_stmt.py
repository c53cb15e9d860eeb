"""Of the device's idle time, the milliseconds a statement during which the
host was in no span of the engine (field ``idle_s.untraced`` of
``benchmark/idle_spans.py``)."""

from benchmark.idle_spans import read  # noqa: F401
