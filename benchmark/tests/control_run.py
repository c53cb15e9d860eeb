#!/usr/bin/env python3
"""The control of ``test_run_correct.py`` on the chip, at a cell's own size:
one run of ``run.py`` with the served path broken underneath.

    python3 benchmark/tests/control_run.py <altered|silent> --workload <cell> --seed <n> --seconds <s>

``altered``: every answer's first number is off by one unit of its last
place as it leaves the program; ``silent``: the window's first statement
never answers. The run has to print ``correct: false`` with the limit
that broke under ``compared``, and exit 1. The benchmark's own runs
never come here.
"""

import os
import sys

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, os.path.dirname(os.path.dirname(HERE)))


def main(argv) -> int:
    from benchmark import run
    from benchmark.tests import test_run_correct as faults
    from presto_tpu.server.client import PrestoTpuClient

    fault = {"altered": faults._altered, "silent": faults._silent}[argv[0]]
    PrestoTpuClient.execute = fault(PrestoTpuClient.execute)
    return run.main(argv[1:])


if __name__ == "__main__":
    sys.exit(main(sys.argv[1:]))
