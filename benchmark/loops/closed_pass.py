"""Closed loop of whole passes: each client runs the cell's statement
list in order, each statement with its next parameter set, and starts
the next pass when the last statement returns — an analyst (or a TPC-H
power run) waiting for a report. The end-to-end metric is the median
wall of a pass, client side.

No pass starts after the deadline; the pass in flight finishes, so
every sample is a whole pass and the window really elapsed is logged.
"""

import time

from benchmark.harness import percentile

VARIANT = "pass"


def _pass(ctx, k: int, phase: str):
    t0 = time.monotonic()
    ok = True
    for stmt in ctx.names:
        ok &= ctx.execute(0, stmt, k, phase).error is None
    return time.monotonic() - t0, ok


def warm(ctx) -> None:
    """``warm_passes`` untimed passes: the first stages and compiles (or
    loads from the compile cache), the rest show that another parameter
    set compiles nothing."""
    ctx.prepare(0)
    for k in range(int(ctx.traffic["warm_passes"])):
        _pass(ctx, k, "setup")


def window(ctx, seconds: float) -> dict:
    t0 = time.monotonic()
    passes, k = [], 0
    while time.monotonic() - t0 < seconds:
        dt, ok = _pass(ctx, k, "window")
        if ok:
            passes.append(dt)
        k += 1
    elapsed = time.monotonic() - t0
    return {
        "elapsed_s": elapsed,
        "metrics": {"pass_s.p50": percentile(passes, 50)} if passes else {},
        "info": {"passes": len(passes), "pass_min_s": min(passes, default=None),
                 "pass_max_s": max(passes, default=None)},
    }
