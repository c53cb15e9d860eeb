"""Closed loop of independent clients: each of ``clients`` threads owns
an HTTP client session, sends what its statements ``prepare`` once in
set-up, and then executes the cell's statements round-robin, each with
the next parameter set of its pool, as fast as replies come back — a
service's worker threads, each waiting for its reply. End-to-end: the
95th percentile of one statement (all clients pooled) and statements
completed per second of the time really elapsed.

No statement is issued after the deadline; those in flight finish.
"""

import threading
import time

from benchmark.harness import percentile

VARIANT = "serve"


def _drive(ctx, until, phase: str) -> None:
    """Every client issues statements until ``until(client, n)`` is true."""
    n_clients, pool = len(ctx.clients), int(ctx.traffic["param_sets"])

    def client(c: int) -> None:
        n = 0
        while not until(c, n):
            # each client walks the pool from its own offset
            ctx.execute(c, ctx.names[n % len(ctx.names)],
                        c * pool // n_clients + n // len(ctx.names), phase)
            n += 1

    threads = [threading.Thread(target=client, args=(c,), name=f"bench-client-{c}")
               for c in range(n_clients)]
    for t in threads:
        t.start()
    for t in threads:
        t.join()


def warm(ctx) -> None:
    """Each client prepares, then ``warm_per_client`` untimed statements:
    between them the clients touch every parameter set of the pool."""
    for c in range(len(ctx.clients)):
        ctx.prepare(c)
    per_client = int(ctx.traffic["warm_per_client"])
    _drive(ctx, lambda c, n: n >= per_client, "setup")


def window(ctx, seconds: float) -> dict:
    before = len(ctx.samples)
    t0 = time.monotonic()
    _drive(ctx, lambda c, n: time.monotonic() - t0 >= seconds, "window")
    elapsed = time.monotonic() - t0
    done = [s.seconds for s in ctx.samples[before:] if s.error is None]
    metrics = {}
    if done:
        metrics = {"stmt_ms.p95": 1e3 * percentile(done, 95),
                   "stmts_per_s": len(done) / elapsed}
    return {
        "elapsed_s": elapsed,
        "metrics": metrics,
        "info": {"completed": len(done),
                 "stmt_p50_ms": 1e3 * percentile(done, 50) if done else None,
                 "samples_beyond_p95": len(done) // 20},
    }
