"""A join's build side prepared once (ops/join.py: join_build, cut_build;
exec/local_runner.py: prepare_builds): the pages of a join over a build
that a program of its own sorted by key and another cut to the bucket of
its matchable rows are those of the same join building in its own
program, for every join type; and a worker's scan task prepares each
replicated build once, counted from shapes, with no read of the device a
batch; a stated range gone stale costs a re-run on two lanes, in the
batch's program or in the prepared build's. Counts and results on the
CPU, no times."""

import dataclasses

import jax
import numpy as np
import pytest

from presto_tpu.exec.host_ops import apply_host_ops, peel_host_ops
from presto_tpu.exec.staging import bucket_capacity
from presto_tpu.ops import join as J
from presto_tpu.page import pad_capacity
from presto_tpu.plan import nodes as N
from presto_tpu.server import worker as worker_mod

from test_join_probe import JOIN_CASES, _joiner, _pages, _reference
from test_scan_task_batches import END, K, ROWS, _fragment, _Run

#: lineitem probes two replicated builds: orders filtered by a date
#: (the only one a DATE moves), customer filtered by a segment
TWO_BUILDS = (
    "select l_orderkey, l_extendedprice, o_orderdate, c_nationkey "
    "from tpch.tiny.lineitem, tpch.tiny.orders, tpch.tiny.customer "
    "where l_orderkey = o_orderkey and o_custkey = c_custkey "
    "and o_orderdate < date '{date}' and c_mktsegment = 'BUILDING'"
)


def _prepared(build, bkeys, payload, join_type):
    """The build as a task hands it to its batches: sorted by a program
    of its own, then its rows cut to the bucket of those a probe can
    match (all of them for a FULL join) by another."""
    ordered = jax.jit(lambda b: J.join_build(b, bkeys, payload))(build)
    k = build.capacity
    if join_type != "full":
        k = min(bucket_capacity(int(ordered.nb)), k)
    return jax.jit(J.cut_build, static_argnums=1)(ordered, k)


@pytest.mark.parametrize("join_type,unique,two_column", JOIN_CASES)
def test_prepared_build_gives_the_in_program_pages(
    join_type, unique, two_column
):
    probe, build, pkeys, bkeys = _pages(11, two_column, unique)
    # 4,096 slots, a quarter of the live rows dead under a mask: dead
    # rows sort last, and the cut keeps the smallest bucket, 1,024 slots
    build = pad_capacity(build, 4_096)
    keep = np.arange(build.capacity) % 4 != 1
    keep &= np.arange(build.capacity) < int(build.num_valid)
    build = dataclasses.replace(
        build, live=jax.numpy.asarray(keep),
        num_valid=jax.numpy.asarray(int(keep.sum()), jax.numpy.int32),
    )
    payload = [] if join_type in ("semi", "anti") else ["w", "wd"]
    join = jax.jit(_joiner(pkeys, bkeys, join_type, unique))
    whole, whole_overflow = join(probe, build)
    prepared = _prepared(build, bkeys, payload, join_type)
    if join_type != "full":
        assert prepared.capacity != build.capacity
    got, overflow = join(probe, prepared)
    assert got.names == whole.names
    assert bool(overflow) == bool(whole_overflow) is False
    assert int(got.num_valid) == int(whole.num_valid)
    # the same rows in the same order, value for value
    assert got.to_pylist() == whole.to_pylist()
    cols = list(got.names)
    assert sorted(
        (tuple(r[c] for c in cols) for r in got.to_pylist()), key=repr
    ) == _reference(probe, build, pkeys, bkeys, join_type)


def _date_run(worker, date):
    return _Run(worker, TWO_BUILDS.format(date=date))


def _in_program(worker, date):
    """Each batch through one program that builds both sides itself."""
    r = worker.runner
    root, ops = peel_host_ops(_fragment(worker, TWO_BUILDS.format(date=date)))
    scans = [n for n in N.walk(root) if isinstance(n, N.TableScanNode)]
    line = scans[0]
    whole = [r._load_table(s) for s in scans[1:]]
    out = []
    for lo in range(0, END, ROWS):
        hi = min(lo + ROWS, END)
        page = r.stage_split(line, lo, hi, bucket_capacity(hi - lo))[0]
        got = r._run_with_pages(root, scans, [page] + whole)
        out.append(apply_host_ops(got, ops).to_pylist())
    return out


@pytest.fixture
def worker():
    """A worker whose scan task unpins the replicated tables it used,
    as its task thread does when the task ends (``_run_task_body``)."""
    w = worker_mod.WorkerServer().start()
    execute = w._execute

    def task_then_release(task):
        try:
            execute(task)
        finally:
            w.runner.release_pins(task.stats)

    w._execute = task_then_release
    yield w
    w.shutdown(graceful=False)


def test_scan_task_prepares_each_build_once(worker):
    """K batches probe two builds: each built once for the task (its
    subtree one program and one read, join_build one program), counted
    from its shape, and the batches read the device once each, as they
    would without a join; the pages are those of the batches building
    both sides in their own programs."""
    from presto_tpu.utils.telemetry import device_snapshot

    _date_run(worker, "1995-03-15").go()  # stages and compiles
    before = device_snapshot()
    run = _date_run(worker, "1995-03-01").go()  # another DATE
    after = device_snapshot()
    assert run.error is None and run.task.stats.retries == 0
    # two filtered subtrees: one dispatch and one read each, then a
    # join_build dispatch each; then a dispatch and a read a batch
    assert run.dispatches == K + 2 * 2
    assert run.syncs == K + 2
    assert after["join_builds"] - before["join_builds"] == 2
    orders = worker.runner.execute(
        "select count(*) from tpch.tiny.orders "
        "where o_orderdate < date '1995-03-01'"
    ).rows()[0][0]
    customers = worker.runner.execute(
        "select count(*) from tpch.tiny.customer "
        "where c_mktsegment = 'BUILDING'"
    ).rows()[0][0]
    assert after["join_build_slots"] - before["join_build_slots"] == (
        bucket_capacity(orders) + bucket_capacity(customers)
    )
    assert after["xla_compiles"] == before["xla_compiles"]
    assert run.rows() == _in_program(worker, "1995-03-01")
    assert sum(len(p) for p in run.rows()) > 0


@pytest.fixture
def stale_orderkeys(monkeypatch):
    """The connector states ``o_orderkey`` in [1, 20,000] where tiny's
    keys reach 59,976: a build of orders is stale, one of customer not."""
    from presto_tpu.connectors import tpch

    stats = tpch._TpchMetadata.get_table_stats

    def stale(self, handle):
        got = stats(self, handle)
        cols = dict(got.columns)
        if "o_orderkey" in cols:
            cols["o_orderkey"] = dataclasses.replace(
                cols["o_orderkey"], max_value=20_000
            )
        return dataclasses.replace(got, columns=cols)

    return lambda: monkeypatch.setattr(
        tpch._TpchMetadata, "get_table_stats", stale
    )


def _lanes_and_rows(run):
    """What ``run()`` returns, and the key lanes its join probes sorted."""
    from presto_tpu.utils.telemetry import device_snapshot

    before = device_snapshot()["join_probe_lanes"]
    out = run()
    return device_snapshot()["join_probe_lanes"] - before, out


def test_a_stale_range_runs_the_batch_program_again_on_two_lanes(
    worker, stale_orderkeys
):
    """Builds in the batch's program: with the true statistics both
    probes take one lane (2 a batch); with ``o_orderkey`` stated too
    narrow each batch overflows and runs again with nothing stated, on
    two lanes for both (2 + 4 a batch), and answers the same."""
    date = "1995-03-01"
    lanes, want = _lanes_and_rows(lambda: _in_program(worker, date))
    assert lanes == 2 * K
    stale_orderkeys()
    lanes, got = _lanes_and_rows(lambda: _in_program(worker, date))
    assert lanes == (2 + 4) * K
    assert got == want and sum(len(p) for p in got) > 0


def test_a_stale_range_builds_a_prepared_build_again_on_two_lanes(
    worker, stale_orderkeys
):
    """A build prepared once a task: its own program reports the key
    outside the range with the one read it makes anyway, and runs again
    with nothing stated; the batches then probe orders on two lanes and
    customer on one, read the device once each, and answer as the
    batches that build both sides themselves did before."""
    date = "1995-03-01"
    want = _in_program(worker, date)
    _date_run(worker, date).go()  # stages and compiles
    stale_orderkeys()
    lanes, run = _lanes_and_rows(lambda: _date_run(worker, date).go())
    assert run.error is None and run.task.stats.retries == 1
    assert lanes == (2 + 1) * K
    assert run.syncs == K + 2 + 1
    assert run.rows() == want
