"""Of the device's idle time, the milliseconds a statement during which the
host was in a ``protocol`` span (field ``idle_s.protocol`` of
``benchmark/idle_spans.py``)."""

from benchmark.idle_spans import read  # noqa: F401
