"""Of the device's idle time, the milliseconds a statement during which the
host was in a ``fetch`` span (field ``idle_s.fetch`` of
``benchmark/idle_spans.py``)."""

from benchmark.idle_spans import read  # noqa: F401
