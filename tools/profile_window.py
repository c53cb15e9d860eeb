"""Per-phase profile of the window benchmark config (VERDICT r3 weak 2:
537k rows/s with no written breakdown).

Splits one steady-state iteration of the window query (row_number +
rank over 1.5M orders) into:

  compute   device program + ONE control round trip (fetch_result=False
            path: flags + live count only — no result bytes)
  transfer  materialize_page of the full 1.5M-row result (the batched
            device->host prefix fetch)
  host      host root stage (sort/limit/output over numpy)
  e2e       full runner.execute_plan for cross-checking

The hypothesis this tool tests: the window wall is RESULT TRANSFER
(~36-48 MB device->host), not window compute. Not measured on the
chip.

Usage: python tools/profile_window.py [--sf sf1] [--iters 3]
       [--platform cpu]
"""

import argparse
import os
import statistics
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

_WINDOW = """
select o_orderkey, o_custkey,
  row_number() over (partition by o_custkey order by o_orderdate) as rn,
  rank() over (partition by o_orderpriority order by o_totalprice) as rk
from tpch.SCHEMA.orders
"""


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--sf", default="sf1")
    ap.add_argument("--iters", type=int, default=3)
    ap.add_argument("--platform", default=None)
    args = ap.parse_args()

    import jax

    if args.platform:
        jax.config.update("jax_platforms", args.platform)

    from presto_tpu.exec.host_ops import apply_host_ops, peel_host_ops
    from presto_tpu.exec.local_runner import (
        LocalQueryRunner,
        materialize_page,
    )
    from presto_tpu.plan import nodes as N
    from presto_tpu.plan.optimizer import prune_columns, push_scan_constraints
    from presto_tpu.plan.planner import plan_statement
    from presto_tpu.sql import parse_statement

    runner = LocalQueryRunner()
    sql = _WINDOW.replace("SCHEMA", args.sf)
    plan = plan_statement(
        parse_statement(sql), runner.catalogs, runner.session
    )

    # warmup (stages tables, compiles)
    res = runner.execute_plan(plan)
    nrows = int(res.page.num_valid)
    print(f"result rows: {nrows}")
    bytes_out = sum(
        int(b.data.dtype.itemsize) * nrows for b in res.page.blocks
    )
    print(f"result bytes (data): {bytes_out / 1e6:.1f} MB")

    root = push_scan_constraints(prune_columns(runner._bind_params(plan)))
    host_ops = []
    if runner.session.get("host_root_stage"):
        root, host_ops = peel_host_ops(root)
    scans, pages = runner.leaf_pages(root)

    phases = {k: [] for k in ("compute", "transfer", "host", "e2e")}
    for _ in range(args.iters):
        t0 = time.perf_counter()
        page, n = runner._run_with_pages(
            root, scans, pages, fetch_result=False
        )
        phases["compute"].append(time.perf_counter() - t0)

        t0 = time.perf_counter()
        host_page = materialize_page(page, n)
        phases["transfer"].append(time.perf_counter() - t0)

        t0 = time.perf_counter()
        if host_ops:
            apply_host_ops(host_page, host_ops)
        phases["host"].append(time.perf_counter() - t0)

        t0 = time.perf_counter()
        runner.execute_plan(plan)
        phases["e2e"].append(time.perf_counter() - t0)

    for k, v in phases.items():
        print(
            f"{k:>9}: best {min(v)*1000:8.1f} ms   "
            f"median {statistics.median(v)*1000:8.1f} ms"
        )
    best_e2e = min(phases["e2e"])
    print(f"rows/s (best e2e): {nrows / best_e2e:,.0f}")


if __name__ == "__main__":
    main()
