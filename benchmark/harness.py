"""One run of one cell: boot, set-up, the measured window, references,
the result line. The cell's loop, statements, configuration, traffic
and layer metrics are files found by name (``discovery.py``); nothing
here knows any of them.
"""

from __future__ import annotations

import dataclasses
import json
import os
import shutil
import sys
import tempfile
import threading
import time
import traceback
import zlib
from typing import Any, Dict, List, Optional

from benchmark import discovery, reduce
from benchmark.data import HostData

TRACE_SLICE_S = 10.0  # a traced run measures this long, or one whole pass


def log(**kv) -> None:
    """An earlier line: for people, never read by the driver."""
    print(json.dumps(kv, default=str), flush=True)


@dataclasses.dataclass
class Sample:
    stmt: str
    pidx: int
    phase: str  # "setup" | "window"
    client: int
    seconds: float
    rows: Optional[list]
    error: Optional[str] = None


class Context:
    """What a loop drives: clients, statements, parameter pools."""

    def __init__(self, cell: discovery.Cell, schema: str, uri: str, data: HostData,
                 seed: int):
        import numpy as np
        from presto_tpu.server.client import PrestoTpuClient

        self.cell = cell
        self.traffic = cell.traffic
        self.schema = schema
        self.statements = cell.statements()
        self.names: List[str] = list(cell.traffic["statements"])
        self.clients = [
            PrestoTpuClient(uri, timeout_s=float(cell.traffic.get("timeout_s", 1000)))
            for _ in range(int(cell.traffic["clients"]))
        ]
        # one pool per statement, seeded by (seed, statement name): the
        # same seed gives the same sets whatever else the cell runs
        self.pools: Dict[str, List[dict]] = {}
        for name in self.names:
            rng = np.random.default_rng([seed, zlib.crc32(name.encode())])
            self.pools[name] = [
                self.statements[name].params(rng, data)
                for _ in range(int(cell.traffic["param_sets"]))
            ]
        self.samples: List[Sample] = []
        self._lock = threading.Lock()

    def prepare(self, client: int) -> None:
        """What a client session sends once before its statements."""
        for name in self.names:
            for sql in discovery.prepare_sql(self.statements[name], self.schema,
                                             f"c{client}"):
                self.clients[client].execute(sql)

    def execute(self, client: int, stmt: str, pidx: int, phase: str) -> Sample:
        """One statement through the HTTP client, timed on its side.
        Never raises: a failure is a failed statement of the run."""
        import jax

        pidx %= len(self.pools[stmt])
        sql = self.statements[stmt].sql(self.schema, self.pools[stmt][pidx], f"c{client}")
        rows, error = None, None
        with jax.profiler.TraceAnnotation(reduce.STMT + stmt):
            t0 = time.monotonic()
            try:
                rows = self.clients[client].execute(sql).rows()
            except Exception as e:  # counted, reported, not fatal
                error = f"{type(e).__name__}: {e}"[:400]
            dt = time.monotonic() - t0
        s = Sample(stmt, pidx, phase, client, dt, rows, error)
        with self._lock:
            self.samples.append(s)
        return s


def percentile(values, q: float) -> float:
    """Linear-interpolated percentile (numpy's default), in pure Python."""
    v = sorted(values)
    if not v:
        raise ValueError("no samples")
    k = (len(v) - 1) * q / 100.0
    lo = int(k)
    hi = min(lo + 1, len(v) - 1)
    return v[lo] + (v[hi] - v[lo]) * (k - lo)


def _boot():
    """Coordinator + one worker in this process, engine defaults only
    (as ``chip_smoke.py::_run_served`` boots them)."""
    from presto_tpu.server.coordinator import CoordinatorServer
    from presto_tpu.server.worker import WorkerServer

    coord = CoordinatorServer().start()
    try:
        worker = WorkerServer(coordinator_uri=coord.uri).start()
    except Exception:
        coord.shutdown()
        raise
    deadline = time.monotonic() + 30
    while not coord.active_workers():
        if time.monotonic() > deadline:
            worker.shutdown(graceful=False)
            coord.shutdown()
            raise RuntimeError("the worker never announced itself")
        time.sleep(0.02)
    return coord, worker


def _dir_bytes(path: Optional[str]) -> int:
    total = 0
    for base, _, files in os.walk(path or ""):
        for f in files:
            try:
                total += os.path.getsize(os.path.join(base, f))
            except OSError:
                pass
    return total


def _delta(after: dict, before: dict) -> dict:
    return {k: after[k] - before.get(k, 0) for k in after}


def _check(ctx: Context, data: HostData) -> List[str]:
    """Compare every result of the run with its reference; one
    reference per distinct (statement, parameter set) executed."""
    wants: Dict[Any, Any] = {}
    errors = []
    for s in ctx.samples:
        if s.error is None:
            key = (s.stmt, s.pidx)
            mod = ctx.statements[s.stmt]
            try:
                if key not in wants:
                    wants[key] = mod.reference(data, ctx.pools[s.stmt][s.pidx])
                s.error = mod.compare(s.rows, wants[key])
            except Exception as e:
                traceback.print_exc(file=sys.stderr)
                s.error = f"reference {type(e).__name__}: {e}"[:400]
            if s.error is not None:
                s.error = f"mismatch: {s.error}"
        if s.error is not None:
            errors.append(f"{s.phase} {s.stmt}[{s.pidx}] client {s.client}: {s.error}")
    return errors


def _layer_metrics(cell: discovery.Cell, obs: dict) -> dict:
    out = {}
    for m in cell.per_layer:
        try:
            if m.reader_path:
                value = discovery.load_module(m.reader_path).read(obs, m.spec)
            else:
                value = generic_read(obs, m.spec["read"])
        except (KeyError, ZeroDivisionError):
            value = None  # nothing to read: the metric is left out
        if value is not None:
            out[m.name] = {"value": float(value), "unit": m.spec["unit"]}
    return out


def generic_read(obs: dict, how: dict) -> Optional[float]:
    """``obs[from][field] * scale``, per statement where ``per`` says so."""
    value = obs[how["from"]][how["field"]] * how.get("scale", 1.0)
    if how.get("per") == "stmt":
        value /= obs["stmts"]
    return value


def run(cell: discovery.Cell, args, t0: float) -> int:
    import jax

    import presto_tpu  # noqa: F401  (x64, the compile cache's directory)
    from presto_tpu import native
    from presto_tpu.connectors.tpch import TpchConnector
    from presto_tpu.utils.telemetry import device_snapshot

    devs = jax.devices()
    device = {"platform": devs[0].platform, "kind": devs[0].device_kind,
              "count": len(devs)}
    on_chip = device["platform"] == "tpu"
    if on_chip and len(devs) < cell.chips:
        print(f"benchmark: cell '{cell.name}' needs {cell.chips} chips, JAX reports "
              f"{len(devs)}", file=sys.stderr)
        return 3
    if on_chip and device["kind"] not in cell.peaks:
        print(f"benchmark: no peaks for device kind '{device['kind']}' in peaks.json",
              file=sys.stderr)
        return 3
    # off the chip the run is a rehearsal of the control flow at the
    # configuration's rehearsal schema: never correct, result on stderr
    schema = cell.config["schema"] if on_chip else cell.config["rehearsal_schema"]
    cache_dir = jax.config.jax_compilation_cache_dir
    log(event="start", workload=cell.name, seed=args.seed, trace=args.trace,
        device=device, schema=schema, rehearsal=not on_chip,
        native=bool(native.available()), compile_cache_dir=cache_dir,
        compile_cache_bytes=_dir_bytes(cache_dir))

    loop = cell.loop()
    coord, worker = _boot()
    trace_dir = None
    try:
        data = HostData(TpchConnector(), cell.config["catalog"], schema)
        ctx = Context(cell, f"{cell.config['catalog']}.{schema}", coord.uri, data,
                      args.seed)
        t_boot = time.monotonic()
        loop.warm(ctx)
        snap0 = device_snapshot()
        t_window = time.monotonic()
        setup_s = t_window - t0
        log(event="setup", setup_s=setup_s, boot_s=t_boot - t0,
            warm_s=t_window - t_boot, statements=len(ctx.samples), counters=snap0)

        if args.trace:
            trace_dir = tempfile.mkdtemp(prefix="bench_trace_")
            opts = jax.profiler.ProfileOptions()
            opts.python_tracer_level = 0
            jax.profiler.start_trace(trace_dir, profiler_options=opts)
            try:
                with jax.profiler.TraceAnnotation(reduce.WINDOW):
                    got = loop.window(ctx, min(args.seconds, TRACE_SLICE_S))
            finally:
                jax.profiler.stop_trace()
        else:
            got = loop.window(ctx, args.seconds)
        counters = _delta(device_snapshot(), snap0)
        window = [s for s in ctx.samples if s.phase == "window"]
        done = [s for s in window if s.error is None]
        log(event="window", asked_s=args.seconds, elapsed_s=got["elapsed_s"],
            statements=len(window), counters=counters, **got.get("info", {}))
        log(event="samples", window=[[s.stmt, s.pidx, s.client, round(s.seconds, 4)]
                                     for s in window][:400])
        by_stmt: Dict[str, List[float]] = {}
        for s in done:
            by_stmt.setdefault(s.stmt, []).append(s.seconds)
        for name, v in by_stmt.items():
            log(event="statement", stmt=name, n=len(v), p50_s=percentile(v, 50),
                min_s=min(v), max_s=max(v))

        t_ref = time.monotonic()
        errors = _check(ctx, data)
        for e in errors[:20]:
            log(event="failed", what=e)
        log(event="references", seconds=time.monotonic() - t_ref,
            distinct=len({(s.stmt, s.pidx) for s in ctx.samples}))

        device["memory_peak_bytes"] = int(max(
            (d.memory_stats() or {}).get("peak_bytes_in_use", 0) for d in devs
        ))
        result = {
            "correct": bool(on_chip and not errors and ctx.samples),
            "attempted": len(ctx.samples),
            "failed": sum(1 for s in ctx.samples if s.error is not None),
            "metrics": {},
            "device": device,
        }
        if args.trace:
            planes = reduce.load_planes(reduce.find_xplane(trace_dir))
            log(event="trace", planes={
                p: {ln: len(evs) for ln, evs in lines.items()}
                for p, lines in planes.items() if not p.startswith("/host:")
            })
            red = reduce.reduce_trace(planes, device["platform"])
            if args.keep_trace:
                shutil.copytree(trace_dir, args.keep_trace, dirs_exist_ok=True)
            peak = cell.peaks[device["kind"]] if on_chip else next(iter(cell.peaks.values()))
            obs = {
                "counters": counters,
                "trace": dict(red, busy_ms=red["busy_s"] * 1e3, idle_share_pct=100.0 * (
                    1.0 - red["busy_s"] / red["window_s"])),
                "stmts": len(done),
                "input_bytes": sum(
                    data.nbytes(ctx.statements[s.stmt].TABLES) for s in done),
                "peak": peak,
            }
            log(event="observed", **{k: v for k, v in obs.items() if k != "trace"},
                trace_events=red["events"], trace_devices=red["devices"])
            result["metrics"] = _layer_metrics(cell, obs)
            device["busy_s"] = red["busy_s"]
            device["window_s"] = red["window_s"]
            result["breakdown"] = {"device_ops": red["device_ops"],
                                   "idle_gaps": red["idle_gaps"]}
        else:
            units = {m["name"]: m["unit"] for m in cell.end_to_end}
            values = dict(got["metrics"], setup_s=setup_s)
            for name, unit in units.items():
                if name not in values:
                    raise KeyError(f"loop '{cell.traffic['loop']}' gave no '{name}'")
                result["metrics"][name] = {"value": float(values[name]), "unit": unit}
    finally:
        worker.shutdown(graceful=False)
        coord.shutdown()
        if trace_dir:
            shutil.rmtree(trace_dir, ignore_errors=True)

    # each number the comparison held, beside its limit: the result's last
    # key and the last lines of standard error. Every answer of the run is
    # compared exactly (``_check``), so the numbers are counts of answers.
    wrong = sum(1 for s in ctx.samples if (s.error or "").startswith("mismatch: "))
    result["compared"] = {
        "answers": {"value": len(ctx.samples), "limit": 1, "holds": "at least"},
        "wrong": {"value": wrong, "limit": 0, "holds": "at most"},
        "unanswered": {"value": result["failed"] - wrong, "limit": 0, "holds": "at most"},
    }
    # no accelerator: no result on standard output, and (never correct) a non-zero exit
    print(json.dumps(result), file=sys.stdout if on_chip else sys.stderr, flush=True)
    for name, c in result["compared"].items():
        print(f"compared {name}: {c['value']} ({c['holds']} {c['limit']})",
              file=sys.stderr, flush=True)
    return 0 if result["correct"] else 1
