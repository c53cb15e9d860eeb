"""Of the device's idle time, the milliseconds a statement during which the
host was in no work span but in a ``wait`` (field ``idle_s.wait`` of
``benchmark/idle_spans.py``)."""

from benchmark.idle_spans import read  # noqa: F401
