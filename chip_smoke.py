#!/usr/bin/env python3
"""Smoke run of the served SQL path on the chip.

One process — the only one that touches JAX, because a chip belongs to
one process — boots a ``CoordinatorServer`` and one ``WorkerServer``
the way ``tests/test_server.py``'s cluster fixture does and drives them
through ``PrestoTpuClient`` over HTTP at a TPC-H schema of the built-in
``tpch`` connector (``--schema``: ``sf1`` by default, ``tiny`` for the
CPU rehearsal, ``sf10`` by hand).

Each phase prints one JSON line as it ends (``phase``, ``ok``,
``seconds``, ``rows`` and the device-plane delta: dispatches,
``compile_ms``, host<->device bytes). Every result is compared: ``q6``
and ``q1`` against numpy over the connector's own host columns, the
rest against the same statement under ``tpu_offload=false`` (the CPU
executor tier-1 holds to the sqlite oracle), after checking that the
reference's staged pages really live on CPU devices. A phase that
raises or mismatches prints ``ok: false`` and the script goes on.

``--chips 4`` runs only the mesh executor
(``DistributedQueryRunner(n_devices=4)``) and the one-device
``LocalQueryRunner`` it is compared with.

The last line of standard output is
``{"ok": ..., "device": {"platform": ..., "kind": ..., "count": ...}}``
as JAX reports the device; ``ok`` is true only when every phase passed
AND the platform is ``tpu``. Exit code 0 only then.
"""

from __future__ import annotations

import argparse
import datetime
import decimal
import json
import os
import sys
import time
import traceback

_Q6 = """
select sum(l_extendedprice * l_discount) as revenue
from tpch.{s}.lineitem
where l_shipdate >= date '1994-01-01' and l_shipdate < date '1995-01-01'
  and l_discount between 0.05 and 0.07 and l_quantity < 24
"""

_Q1 = """
select l_returnflag, l_linestatus, sum(l_quantity) as sum_qty,
  sum(l_extendedprice) as sum_base_price,
  sum(l_extendedprice * (1 - l_discount)) as sum_disc_price,
  sum(l_extendedprice * (1 - l_discount) * (1 + l_tax)) as sum_charge,
  avg(l_quantity) as avg_qty, avg(l_extendedprice) as avg_price,
  avg(l_discount) as avg_disc, count(*) as count_order
from tpch.{s}.lineitem
where l_shipdate <= date '1998-12-01' - interval '90' day
group by l_returnflag, l_linestatus
order by l_returnflag, l_linestatus
"""

_Q3 = """
select l_orderkey, sum(l_extendedprice * (1 - l_discount)) as revenue,
  o_orderdate, o_shippriority
from tpch.{s}.customer, tpch.{s}.orders, tpch.{s}.lineitem
where c_mktsegment = 'BUILDING' and c_custkey = o_custkey
  and l_orderkey = o_orderkey and o_orderdate < date '1995-03-15'
  and l_shipdate > date '1995-03-15'
group by l_orderkey, o_orderdate, o_shippriority
order by revenue desc, o_orderdate
limit 10
"""

_LOOKUP = (
    "select c_custkey, c_name, c_acctbal, c_mktsegment, c_nationkey "
    "from tpch.{s}.customer where c_custkey = "
)

_DOUBLE_KEY = """
select cast(l_discount as double) d, count(*) c, avg(l_quantity) a
from tpch.{s}.lineitem group by 1 order by 3, 1
"""

_N_LOOKUPS = 32


def _day(y: int, m: int, d: int) -> int:
    return (datetime.date(y, m, d) - datetime.date(1970, 1, 1)).days


# ------------------------------------------------------------ references


def _lineitem_columns(catalogs, schema: str, columns):
    """The connector's own host columns for ``lineitem`` (numpy arrays;
    varchar columns as ``(ids, values)``), read split by split through
    the connector SPI — nothing of the engine's executor is involved."""
    import numpy as np

    from presto_tpu.connectors.spi import TableHandle

    conn = catalogs.get("tpch")
    src = conn.get_splits(TableHandle("tpch", schema, "lineitem"))
    parts = {c: [] for c in columns}
    dicts = {}
    while not src.exhausted:
        for split in src.next_batch(64):
            got = conn.create_page_source(split, list(columns))
            for c in columns:
                v = got[c]
                if hasattr(v, "ids"):
                    dicts[c] = v.values
                    v = v.ids
                parts[c].append(np.asarray(v))
    out = {c: np.concatenate(parts[c]) for c in columns}
    return out, dicts


def _numpy_q6(catalogs, schema: str):
    """TPC-H Q6 in numpy: the unscaled int64 sum at scale 4."""
    cols, _ = _lineitem_columns(
        catalogs, schema,
        ("l_extendedprice", "l_discount", "l_quantity", "l_shipdate"),
    )
    keep = (
        (cols["l_shipdate"] >= _day(1994, 1, 1))
        & (cols["l_shipdate"] < _day(1995, 1, 1))
        & (cols["l_discount"] >= 5)
        & (cols["l_discount"] <= 7)
        & (cols["l_quantity"] < 2400)
    )
    return int((cols["l_extendedprice"][keep] * cols["l_discount"][keep]).sum())


def _numpy_q1(catalogs, schema: str):
    """TPC-H Q1 in numpy: per (returnflag, linestatus) the unscaled
    int64 sums, the row count, and the avg numerators' scales."""
    import numpy as np

    cols, dicts = _lineitem_columns(
        catalogs, schema,
        ("l_returnflag", "l_linestatus", "l_quantity", "l_extendedprice",
         "l_discount", "l_tax", "l_shipdate"),
    )
    keep = cols["l_shipdate"] <= _day(1998, 12, 1) - 90
    rf, ls = cols["l_returnflag"][keep], cols["l_linestatus"][keep]
    qty, price = cols["l_quantity"][keep], cols["l_extendedprice"][keep]
    disc, tax = cols["l_discount"][keep], cols["l_tax"][keep]
    disc_price = price * (100 - disc)
    charge = disc_price * (100 + tax)
    n_ls = len(dicts["l_linestatus"])
    gid = rf.astype(np.int64) * n_ls + ls
    out = {}
    for g in np.unique(gid):
        m = gid == g
        key = (
            str(dicts["l_returnflag"][g // n_ls]),
            str(dicts["l_linestatus"][g % n_ls]),
        )
        out[key] = {
            "count": int(m.sum()),
            "sum_qty": int(qty[m].sum()),
            "sum_base_price": int(price[m].sum()),
            "sum_disc_price": int(disc_price[m].sum()),
            "sum_charge": int(charge[m].sum()),
            "sum_disc": int(disc[m].sum()),
        }
    return out


def _same_sum(got, want: int, scale: int) -> bool:
    """Exact equality of a scaled-int64 sum. A float the client
    printed is ``int / 10**scale`` correctly rounded (page.py), so past
    2**53 the comparison is of that same division; anything else (a
    long decimal printed as text) is compared as the unscaled integer."""
    if isinstance(got, float):
        return got == want / 10 ** scale
    return decimal.Decimal(str(got)).scaleb(scale) == want


def _close(a: float, b: float, rel: float = 1e-9) -> bool:
    return abs(a - b) <= rel * max(abs(a), abs(b), 1e-300)


def _check_q6(rows, want: int):
    if len(rows) != 1 or not _same_sum(rows[0][0], want, 4):
        return f"q6 revenue {rows!r} != numpy {want} (scale 4)"
    return None


def _check_q1(rows, want: dict):
    if sorted((r[0], r[1]) for r in rows) != sorted(want):
        return f"q1 groups {[r[:2] for r in rows]} != numpy {sorted(want)}"
    if [(r[0], r[1]) for r in rows] != sorted(want):
        return "q1 rows are not ordered by returnflag, linestatus"
    for r in rows:
        w = want[(r[0], r[1])]
        sums = (
            ("sum_qty", r[2], 2), ("sum_base_price", r[3], 2),
            ("sum_disc_price", r[4], 4), ("sum_charge", r[5], 6),
        )
        for name, got, scale in sums:
            if not _same_sum(got, w[name], scale):
                return f"q1 {r[:2]} {name}: {got!r} != {w[name]} e-{scale}"
        if int(r[9]) != w["count"]:
            return f"q1 {r[:2]} count: {r[9]!r} != {w['count']}"
        avgs = (
            ("avg_qty", r[6], w["sum_qty"]),
            ("avg_price", r[7], w["sum_base_price"]),
            ("avg_disc", r[8], w["sum_disc"]),
        )
        for name, got, num in avgs:
            ref = num / 100 / w["count"]
            if not _close(float(got), ref):
                return f"q1 {r[:2]} {name}: {got!r} != {ref!r}"
    return None


def _norm(v):
    if isinstance(v, (datetime.date, datetime.datetime)):
        return v.isoformat()
    if isinstance(v, decimal.Decimal):
        return str(v)
    return v


def _rows_and_types(res):
    """A ``QueryResult``'s rows and its columns' type names, in order."""
    by_name = {c: t.name for c, t in res.page.schema().items()}
    return res.rows(), [by_name[c] for c in res.columns]


def _diff_rows(got, want, types):
    """``got`` rows against reference ``want`` rows, in order. Exact,
    except DOUBLE/REAL columns (``types`` from the reference's page):
    1e-9 relative, since the two devices sum in different orders."""
    if len(got) != len(want):
        return f"{len(got)} rows != reference {len(want)} rows"
    for i, (g, w) in enumerate(zip(got, want)):
        if len(g) != len(w):
            return f"row {i}: {len(g)} columns != reference {len(w)}"
        for j, (a, b) in enumerate(zip(g, w)):
            a, b = _norm(a), _norm(b)
            if a == b or str(a) == str(b):
                continue
            if (
                types[j] in ("double", "real")
                and a is not None and b is not None
                and _close(float(a), float(b))
            ):
                continue
            return f"row {i} column {j}: {a!r} != reference {b!r}"
    return None


class _CpuReference:
    """The same statements under ``tpu_offload=false``: a
    ``LocalQueryRunner`` of its own (its staged-table cache is its own
    too), whose staged pages are checked to live on CPU devices — a
    cache shared with the served runners would hand it pages that live
    on the chip, and the comparison would be the chip against itself."""

    def __init__(self):
        from presto_tpu.exec.local_runner import LocalQueryRunner
        from presto_tpu.session import Session

        self.runner = LocalQueryRunner(
            session=Session(properties={"tpu_offload": False})
        )

    def run(self, sql: str):
        res = self.runner.execute(sql)
        self._assert_on_cpu()
        return _rows_and_types(res)

    def _assert_on_cpu(self):
        import jax

        cache = self.runner.split_cache
        with cache._lock:
            pages = [e[0] for e in cache._entries.values()]
        if not pages:
            raise AssertionError("the CPU reference staged no table")
        for leaf in jax.tree_util.tree_leaves(pages):
            for d in getattr(leaf, "devices", lambda: ())():
                if d.platform != "cpu":
                    raise AssertionError(
                        f"the reference's staged page lives on {d}: "
                        "it did not run on the CPU"
                    )


# ----------------------------------------------------------------- phases


class _Smoke:
    def __init__(self, schema: str):
        self.schema = schema
        self.failed = []
        self._snap = None

    def sql(self, template: str) -> str:
        return template.format(s=self.schema)

    def _device_delta(self) -> dict:
        from presto_tpu.utils.telemetry import device_snapshot

        snap = device_snapshot()
        prev = self._snap or {}
        self._snap = snap
        return {
            "dispatches": int(snap["dispatches"] - prev.get("dispatches", 0)),
            "compile_ms": round(
                snap["compile_ms"] - prev.get("compile_ms", 0.0), 1
            ),
            "h2d_bytes": int(snap["h2d_bytes"] - prev.get("h2d_bytes", 0)),
            "d2h_bytes": int(snap["d2h_bytes"] - prev.get("d2h_bytes", 0)),
        }

    def phase(self, name: str, fn) -> dict:
        """Run one phase; print its line; never raise."""
        line = {"phase": name, "ok": False, "schema": self.schema}
        t0 = time.monotonic()
        try:
            extra = fn() or {}
            if extra.get("error") is None:
                extra.pop("error", None)
            line["ok"] = "error" not in extra
            line.update(extra)
        except Exception as e:
            line["error"] = f"{type(e).__name__}: {e}"[:600]
            traceback.print_exc(file=sys.stderr)
        line["seconds"] = round(time.monotonic() - t0, 3)
        if not line["ok"]:
            self.failed.append(name)
        print(json.dumps(line), flush=True)
        return line

    def timed_query(self, run):
        """Time ``run()`` alone and take the device delta around it, so
        neither covers the reference computed beside it."""
        self._device_delta()
        t0 = time.monotonic()
        rows = run()
        secs = round(time.monotonic() - t0, 3)
        return rows, {
            "rows": len(rows), "query_seconds": secs,
            "device": self._device_delta(),
        }


def _phase_device() -> dict:
    import jax

    from presto_tpu import native
    from presto_tpu.utils import devicediag

    devs = jax.devices()
    diag = devicediag.probe_backend()
    out = {
        "platform": devs[0].platform,
        "kind": devs[0].device_kind,
        "count": len(devs),
        "jax": jax.__version__,
        "native": bool(native.available()),
        "compile_cache_dir": jax.config.jax_compilation_cache_dir,
        "probe": diag.phase,
    }
    if not diag.ok:
        out["error"] = f"device probe failed in {diag.phase}: {diag.error}"
    return out


def _run_served(smoke: _Smoke) -> None:
    """Boot coordinator + worker in this process and drive the phases
    through the HTTP client."""
    from presto_tpu.server.client import PrestoTpuClient
    from presto_tpu.server.coordinator import CoordinatorServer
    from presto_tpu.server.worker import WorkerServer

    state = {}

    def boot():
        coord = CoordinatorServer().start()
        state["coord"] = coord
        state["worker"] = WorkerServer(coordinator_uri=coord.uri).start()
        deadline = time.monotonic() + 30
        while not coord.active_workers():
            if time.monotonic() > deadline:
                raise RuntimeError("the worker never announced itself")
            time.sleep(0.05)
        state["client"] = PrestoTpuClient(coord.uri, timeout_s=1100)
        return {"workers": len(coord.active_workers())}

    try:
        if not smoke.phase("boot", boot)["ok"]:
            return
        client = state["client"]
        ref = _CpuReference()
        catalogs = ref.runner.catalogs

        def execute(sql):
            return [tuple(r) for r in client.execute(sql).rows()]

        def q6():
            rows, info = smoke.timed_query(lambda: execute(smoke.sql(_Q6)))
            err = _check_q6(rows, _numpy_q6(catalogs, smoke.schema))
            return dict(info, reference="numpy", error=err)

        q1_want = {}

        def q1(warm: bool):
            rows, info = smoke.timed_query(lambda: execute(smoke.sql(_Q1)))
            if not q1_want:
                q1_want.update(_numpy_q1(catalogs, smoke.schema))
            err = _check_q1(rows, q1_want)
            if warm and err is None:
                # host->device alone: the result fetch is the same both
                # times, and the cold run stages only the columns Q6,
                # which ran before it, left out (residency by column)
                moved = info["device"]["h2d_bytes"]
                was = (state.get("q1_cold") or {}).get("h2d_bytes", 0)
                if info["device"]["compile_ms"] != 0:
                    err = (
                        "warm q1 compiled again: compile_ms "
                        f"{info['device']['compile_ms']}"
                    )
                elif was and moved * 4 > was:
                    err = (
                        f"warm q1 staged {moved} bytes, cold staged {was}: "
                        "the resident columns were not reused"
                    )
            elif not warm:
                state["q1_cold"] = info["device"]
            return dict(info, reference="numpy", error=err)

        def against_cpu(template):
            def run():
                sql = smoke.sql(template)
                rows, info = smoke.timed_query(lambda: execute(sql))
                want, types = ref.run(sql)
                err = _diff_rows(rows, want, types)
                return dict(info, reference="tpu_offload=false", error=err)

            return run

        def point_lookup():
            n_cust = int(
                execute(smoke.sql("select count(*) from tpch.{s}.customer"))[0][0]
            )
            keys = [1 + (i * 7919) % n_cust for i in range(_N_LOOKUPS)]
            lookup = smoke.sql(_LOOKUP)

            def run():
                execute(f"prepare smoke_lookup from {lookup}?")
                got = []
                for k in keys:
                    got.extend(execute(f"execute smoke_lookup using {k}"))
                return got

            rows, info = smoke.timed_query(run)
            want, types = [], []
            for k in keys:
                r, types = ref.run(f"{lookup}{k}")
                want.extend(r)
            err = _diff_rows(rows, want, types)
            if err is None and [r[0] for r in rows] != keys:
                err = f"looked up {keys}, got {[r[0] for r in rows]}"
            return dict(
                info, lookups=len(keys), reference="tpu_offload=false",
                error=err,
            )

        smoke.phase("q6", q6)
        smoke.phase("q1", lambda: q1(False))
        smoke.phase("q3", against_cpu(_Q3))
        smoke.phase("q1_warm", lambda: q1(True))
        smoke.phase("point_lookup", point_lookup)
        smoke.phase("double_key", against_cpu(_DOUBLE_KEY))
    finally:
        if "worker" in state:
            state["worker"].shutdown(graceful=False)
        if "coord" in state:
            state["coord"].shutdown()


def _run_mesh(smoke: _Smoke, n: int) -> None:
    """The ``shard_map``/``all_to_all`` mesh executor on ``n`` devices
    against a one-device ``LocalQueryRunner`` in this process — and
    nothing else."""
    import jax

    from presto_tpu.exec import staging
    from presto_tpu.exec.local_runner import LocalQueryRunner
    from presto_tpu.parallel import DistributedQueryRunner

    state = {}

    def mesh():
        devs = jax.devices()
        if len(devs) < n:
            raise RuntimeError(
                f"--chips {n} needs {n} devices, JAX reports {len(devs)}"
            )
        # thresholds low enough that SF1 joins and aggregates take the
        # partitioned all_to_all exchange, not the all_gather broadcast
        runner = DistributedQueryRunner(
            n_devices=n, broadcast_threshold=1 << 11, repl_threshold=1 << 10
        )
        if runner.n != n or len(set(runner.mesh.devices.flat)) != n:
            raise RuntimeError(f"the mesh has {runner.n} devices, wanted {n}")
        state["runner"] = runner
        state["local"] = LocalQueryRunner()
        return {"mesh_devices": [str(d) for d in runner.mesh.devices.flat]}

    if not smoke.phase("mesh", mesh)["ok"]:
        return
    runner, local = state["runner"], state["local"]

    # spy on the two seams the assertions need: what was staged onto
    # the mesh, and which compiled fragment programs ran over it
    staged, frags = [], []
    orig_stage, orig_exec = staging.stage_sharded, runner._execute_fragment

    def stage_spy(tables, sharding):
        out = orig_stage(tables, sharding)
        staged.append(out)
        return out

    def exec_spy(root, scans, tables, balance):
        res = orig_exec(root, scans, tables, balance)
        fn, _ = runner._frag_compiled[(root.fingerprint(), balance, runner.n)]
        frags.append((fn, staged[-1]))
        return res

    def on_mesh(template):
        def run():
            sql = smoke.sql(template)
            del staged[:], frags[:]
            rows, info = smoke.timed_query(lambda: runner.execute(sql).rows())
            err = _diff_rows(rows, *_rows_and_types(local.execute(sql)))
            if not frags:
                raise AssertionError("no fragment ran on the mesh")
            shard_devices = set()
            for pages in staged:
                for leaf in jax.tree_util.tree_leaves(pages):
                    shard_devices |= set(leaf.sharding.device_set)
            if len(shard_devices) != n:
                raise AssertionError(
                    f"sharded inputs sit on {len(shard_devices)} devices, "
                    f"wanted {n}: {sorted(map(str, shard_devices))}"
                )
            collectives = set()
            for fn, pages in frags:
                text = fn.lower(pages).compile().as_text()
                for op in ("all-to-all", "all-gather", "all-reduce"):
                    if op in text:
                        collectives.add(op)
            if "all-to-all" not in collectives:
                raise AssertionError(
                    "no compiled fragment contains an all-to-all: "
                    f"found {sorted(collectives)}"
                )
            return dict(
                info, fragments=len(frags), shard_devices=len(shard_devices),
                collectives=sorted(collectives),
                reference="LocalQueryRunner, one device", error=err,
            )

        return run

    staging.stage_sharded = stage_spy
    runner._execute_fragment = exec_spy
    try:
        smoke.phase("mesh_q3", on_mesh(_Q3))
        smoke.phase("mesh_q1", on_mesh(_Q1))
    finally:
        staging.stage_sharded = orig_stage
        del runner._execute_fragment


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--schema", default="sf1",
                    choices=("tiny", "sf1", "sf10"))
    ap.add_argument("--chips", type=int, default=1, choices=(1, 4))
    args = ap.parse_args(argv)

    # the reference runs on the CPU backend beside the chip: where the
    # environment names platforms, the CPU has to be among them
    plats = os.environ.get("JAX_PLATFORMS", "")
    if plats and "cpu" not in plats.split(","):
        os.environ["JAX_PLATFORMS"] = plats + ",cpu"

    import jax

    import presto_tpu  # noqa: F401  (x64, compile cache)

    devs = jax.devices()  # raises where the named platform cannot start
    device = {
        "platform": devs[0].platform,
        "kind": devs[0].device_kind,
        "count": len(devs),
    }
    t0 = time.monotonic()
    smoke = _Smoke(args.schema)
    if smoke.phase("device", _phase_device)["ok"]:
        if args.chips == 1:
            _run_served(smoke)
        else:
            _run_mesh(smoke, args.chips)
    ok = not smoke.failed and device["platform"] == "tpu"
    print(json.dumps({
        "phase": "total", "ok": not smoke.failed, "failed": smoke.failed,
        "seconds": round(time.monotonic() - t0, 3),
    }), flush=True)
    print(json.dumps({"ok": ok, "device": device}), flush=True)
    return 0 if ok else 1


if __name__ == "__main__":
    sys.exit(main())
