"""A worker's scan task resolves its fragment once and runs every split
batch through the same three steps as a synchronous run
(exec/local_runner.py: _resolve, _dispatch, _collect; server/worker.py:
_execute): counts and results on the CPU, no times."""

import dataclasses

import numpy as np
import pytest

from presto_tpu.exec import staging
from presto_tpu.exec.host_ops import apply_host_ops, peel_host_ops
from presto_tpu.exec.local_runner import ExecutionError
from presto_tpu.exec.staging import bucket_capacity
from presto_tpu.plan import canonical
from presto_tpu.plan import nodes as N
from presto_tpu.plan.optimizer import prune_columns, push_scan_constraints
from presto_tpu.plan.planner import plan_statement
from presto_tpu.server import worker as worker_mod
from presto_tpu.server.protocol import FragmentSpec
from presto_tpu.sql import parse_statement
from presto_tpu.utils.telemetry import device_snapshot

K = 5  # split batches a task
ROWS = 4_096  # rows a batch
END = K * ROWS - 100  # the last batch is short

NARROW = (
    "select count(*) c, sum(l_quantity) q from tpch.tiny.lineitem "
    "where l_quantity < 30"
)
GROUPED = (
    "select l_orderkey, count(*) c from tpch.tiny.lineitem "
    "group by l_orderkey"
)
#: longer than the speculative prefix: read by materialize_page
LONG = (
    "select l_orderkey, l_quantity from tpch.tiny.lineitem "
    "where l_quantity > 0"
)


@pytest.fixture
def worker():
    w = worker_mod.WorkerServer().start()
    yield w
    w.shutdown(graceful=False)


def _fragment(w, sql):
    plan = plan_statement(
        parse_statement(sql), w.runner.catalogs, w.runner.session
    )
    return push_scan_constraints(prune_columns(plan.root))


def _scan_index(root):
    peeled = peel_host_ops(root)[0]
    return next(
        i for i, n in enumerate(N.walk(peeled))
        if isinstance(n, N.TableScanNode)
    )


class _Run:
    """One scan task run on the calling thread, its emits recorded."""

    def __init__(self, w, sql, root=None, end=END, **spec_kw):
        self.w = w
        root = _fragment(w, sql) if root is None else root
        self.spec = FragmentSpec(
            task_id="t.0", query_id="q", fragment=root,
            partition_scan=_scan_index(root), split_start=0,
            split_end=end, split_batch_rows=ROWS, **spec_kw,
        )
        self.task = worker_mod._Task(
            self.spec, pool=w.memory_pool, node_id=w.node_id
        )
        self.pages = []  # what _emit_result was handed, in order
        self.error = None

    def go(self, dispatch=None):
        w, r = self.w, self.w.runner
        if dispatch is not None:
            r._dispatch = dispatch
        w._emit_result = lambda task, out: self.pages.append(out)
        r._qs_local.value = self.task.stats
        before = device_snapshot()
        try:
            w._execute(self.task)
        except ExecutionError as e:
            self.error = e
        finally:
            after = device_snapshot()
            r._qs_local.value = None
            del w._emit_result
            if dispatch is not None:
                del r._dispatch
        self.syncs = after["device_syncs"] - before["device_syncs"]
        self.dispatches = after["dispatches"] - before["dispatches"]
        self.h2d = after["h2d_bytes"] - before["h2d_bytes"]
        # every batch handed its inputs back
        assert not r.split_cache._pins
        assert w.memory_pool.used_bytes("q") == 0
        return self

    def rows(self):
        return [p.to_pylist() for p in self.pages]


def _batch_at_a_time(w, sql, end=END):
    """The synchronous entry over the same batches: stage, run, read."""
    r = w.runner
    root, ops = peel_host_ops(_fragment(w, sql))
    scan = next(n for n in N.walk(root) if isinstance(n, N.TableScanNode))
    out = []
    for lo in range(0, end, ROWS):
        hi = min(lo + ROWS, end)
        page = r.stage_split(scan, lo, hi, bucket_capacity(hi - lo))[0]
        got = r._run_with_pages(root, [scan], [page])
        out.append(apply_host_ops(got, ops).to_pylist())
    return out


@pytest.mark.parametrize(
    "sql, syncs_a_batch",
    [(NARROW, 1), (LONG, 2)],
    ids=["one_row", "filtered_rows"],
)
def test_task_emits_the_synchronous_path_s_pages_in_order(
    worker, sql, syncs_a_batch
):
    """K batches, K pages, those of _run_with_pages over the same
    ranges; a result inside the speculative prefix is one blocking
    fetch a batch, a longer one a second (materialize_page)."""
    _Run(worker, sql).go()  # stages and compiles
    run = _Run(worker, sql).go()
    assert run.error is None and run.task.stats.retries == 0
    assert run.dispatches == K
    assert run.syncs == K * syncs_a_batch
    assert run.rows() == _batch_at_a_time(worker, sql)
    assert len({str(p) for p in run.rows()}) == K  # no batch twice


@pytest.mark.parametrize("batches", [1, K])
def test_fragment_is_resolved_once_a_task(worker, monkeypatch, batches):
    calls = []
    hoist = canonical.hoist_params

    def spy(root, **kw):
        calls.append(root)
        return hoist(root, **kw)

    monkeypatch.setattr(canonical, "hoist_params", spy)
    run = _Run(worker, NARROW, end=batches * ROWS).go()
    assert len(run.pages) == batches and len(calls) == 1


@pytest.mark.parametrize(
    "batches, puts", [(1, 0), (2, 1), (K, 1)],
    ids=["single_run", "two_batches", "task"],
)
def test_parameter_vector_goes_to_the_device_once_a_task(
    worker, monkeypatch, batches, puts
):
    """The hoisted literals of a task's batches are one vector: put on
    the device when the task is resolved, and never for a program
    that is run once."""
    _Run(worker, NARROW, end=batches * ROWS).go()  # stage the columns
    staged = []
    stage_params = staging.stage_params

    def spy(params):
        staged.append(params)
        return stage_params(params)

    monkeypatch.setattr(
        "presto_tpu.exec.local_runner.stage_params", spy
    )
    run = _Run(worker, NARROW, end=batches * ROWS).go()
    assert len(staged) == puts
    want = sum(int(p.nbytes) for v in staged for p in v)
    assert run.h2d == want and (want > 0) == bool(puts)
    assert run.rows() == _batch_at_a_time(worker, NARROW, batches * ROWS)


def test_prepared_statement_values_ride_the_resolved_vector(worker):
    """Two tasks over one fragment shape with different literals: one
    compiled program, each task's own values."""
    other = NARROW.replace("< 30", "< 10")
    a = _Run(worker, NARROW).go().rows()
    compiled = len(worker.runner._compiled)
    b = _Run(worker, other).go().rows()
    assert len(worker.runner._compiled) == compiled
    assert a != b and b == _batch_at_a_time(worker, other)


def _inject(r, at, leaf, resolved_change=None):
    """A ``_dispatch`` whose ``at``-th batch comes back flagged in
    control output ``leaf`` (0 the overflow flags, 1 the error flags)."""
    dispatch = r._dispatch
    seen = []

    def flagged(resolved, pages):
        p = dispatch(resolved, pages)
        seen.append(p)
        if len(seen) - 1 == at:
            control = list(p.control)
            control[leaf] = np.ones((1,), bool)
            p.control = tuple(control)
            if resolved_change:
                p.resolved = dataclasses.replace(
                    p.resolved, **resolved_change
                )
        return p

    return flagged


def test_overflow_in_a_middle_batch_reruns_it_exactly(worker):
    want = _batch_at_a_time(worker, NARROW)
    run = _Run(worker, NARROW).go(_inject(worker.runner, 2, 0))
    assert run.task.stats.retries == 1
    # the flagged batch again, at four times the capacities
    assert (run.dispatches, run.syncs) == (K + 1, K + 1)
    assert run.rows() == want


def test_real_overflow_of_every_batch_is_exact(worker):
    """Group buckets too small for any batch: each is run again until
    it fits, and the later batches start small again."""
    want = _batch_at_a_time(worker, GROUPED)
    root = _fragment(worker, GROUPED)

    def shrink(n):
        if isinstance(n, N.AggregationNode):
            return dataclasses.replace(
                n, source=shrink(n.source), max_groups=256
            )
        kids = n.children()
        if not kids:
            return n
        return dataclasses.replace(n, source=shrink(kids[0]))

    run = _Run(worker, GROUPED, root=shrink(root)).go()
    assert run.task.stats.retries >= K
    assert run.rows() == want


def test_error_flag_raises_the_program_s_message(worker):
    want = _batch_at_a_time(worker, NARROW)
    run = _Run(worker, NARROW).go(
        _inject(
            worker.runner, 2, 1,
            {"msgs_cell": ["cross join build produced more than one row"]},
        )
    )
    assert isinstance(run.error, ExecutionError)
    assert str(run.error) == "cross join build produced more than one row"
    assert run.rows() == want[:2]  # nothing of or after the failed batch


@pytest.mark.parametrize(
    "sql, spec_kw, output",
    [
        (
            "select l_returnflag, count(*) c from tpch.tiny.lineitem "
            "where l_quantity < 3 group by l_returnflag",
            {"n_partitions": 2, "partition_keys": ("l_returnflag",)},
            lambda run: run.task.parts,
        ),
        (
            "select max(l_orderkey) k from tpch.tiny.lineitem",
            {"dynfilter_keys": ("k",)},
            lambda run: run.task.dynfilter,
        ),
    ],
    ids=["partitioned", "dynfilter"],
)
def test_other_emits_are_those_of_a_second_run(worker, sql, spec_kw, output):
    """Partitioned output and dynamic-filter summaries of a task whose
    parameter vector is on the device equal those of a cold first run
    (vector passed from the host for the first batch)."""
    first = output(_Run(worker, sql, **spec_kw).go())
    again = output(_Run(worker, sql, **spec_kw).go())
    assert first and first == again
