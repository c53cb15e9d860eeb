#!/usr/bin/env python3
"""One run of one cell of ``BENCHMARK.json``.

    python3 benchmark/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

One process, the only one that touches JAX: it boots a coordinator and
a worker, drives the cell's statements through the HTTP client, checks
every result against a numpy reference and prints, as the last line of
standard output, the result object the driver reads. See README.md.
"""

import time

_T0 = time.monotonic()  # process start, as near as Python lets us see it

import argparse  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--keep-trace", default=None, metavar="DIR",
                    help="copy the profiler's files of a traced run to DIR")
    args = ap.parse_args(argv)

    from benchmark import discovery

    try:
        cell = discovery.load_cell(ROOT, args.workload)
    except discovery.Missing as e:
        print(f"benchmark: {e}", file=sys.stderr)
        return 2

    from benchmark import harness

    return harness.run(cell, args, _T0)


if __name__ == "__main__":
    sys.exit(main())
