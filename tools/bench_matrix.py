"""Benchmark-matrix wrapper: one process per config, honest rc.

Runs every ``bench.py --only <config>`` in its OWN subprocess (a
crash on one config must not poison the rest; one process at a time,
since a chip belongs to one process) and records an HONEST status per
config: a config counts as failed when the subprocess exits nonzero,
times out, OR its JSON line carries an ``error``/zero value
("process exited" is not "measurement succeeded").

Usage:
    python tools/bench_matrix.py [--timeout SECONDS] [CONFIG ...]

Outputs tools/benchout/<config>.jsonl + .err per config and a summary
``progress.log``; exits nonzero if any config failed.
"""

from __future__ import annotations

import json
import os
import subprocess
import sys

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
OUT = os.path.join(REPO, "tools", "benchout")

#: default matrix = every --all config, cheapest first so a late crash
#: loses the least
CONFIGS = [
    "q3_sf1",
    "q5_sf1",
    "q18_sf1_rows",
    "q18_sf1_streamed",
    "window",
    "tpcds_q95",
    "tpcds_q64",
    "tpcds_q72_sf1",
    "q3_sf10",
    "q5_sf10",
    "q18_sf10",
]


def run_config(config: str, timeout: float) -> tuple[int, str]:
    """-> (rc, status) where status is ok|error|crash|timeout."""
    os.makedirs(OUT, exist_ok=True)
    out_path = os.path.join(OUT, f"{config}.jsonl")
    err_path = os.path.join(OUT, f"{config}.err")
    with open(out_path, "w") as out, open(err_path, "w") as err:
        try:
            proc = subprocess.run(
                [sys.executable, "-u", "bench.py", "--only", config],
                cwd=REPO,
                stdout=out,
                stderr=err,
                timeout=timeout,
            )
            rc = proc.returncode
        except subprocess.TimeoutExpired:
            return 124, "timeout"
    status = "ok" if rc == 0 else ("crash" if rc < 0 else "error")
    try:
        with open(out_path) as f:
            lines = [json.loads(ln) for ln in f if ln.strip()]
        if not lines:
            status = "crash" if rc != 0 else "error"
        for rec in lines:
            if rec.get("error") or not rec.get("value"):
                status = "error" if rc == 0 else status
                rc = rc or 1
    except (json.JSONDecodeError, OSError):
        status, rc = "crash", rc or 1
    return rc, status


def main() -> None:
    args = sys.argv[1:]
    timeout = 2400.0
    if "--timeout" in args:
        i = args.index("--timeout")
        timeout = float(args[i + 1])
        del args[i: i + 2]
    configs = args or CONFIGS
    log_path = os.path.join(OUT, "progress.log")
    os.makedirs(OUT, exist_ok=True)
    any_failed = False
    with open(log_path, "w") as log:
        for c in configs:
            rc, status = run_config(c, timeout)
            line = f"=== {c} rc={rc} status={status}"
            print(line, flush=True)
            log.write(line + "\n")
            log.flush()
            any_failed |= status != "ok"
        log.write("ALL-DONE\n")
    sys.exit(1 if any_failed else 0)


if __name__ == "__main__":
    main()
