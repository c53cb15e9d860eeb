"""Session-property wiring tests: every property must be observable in
engine behavior (VERDICT round-1: no decorative flags). Reference:
SystemSessionProperties, SURVEY.md §5.6."""

import time

import jax
import pytest

from presto_tpu.exec.local_runner import LocalQueryRunner
from presto_tpu.parallel import DistributedQueryRunner
from presto_tpu.session import NodeConfig, Session
from presto_tpu.verifier import SqliteOracle, verify_offload, verify_query

Q_AGG = (
    "select l_returnflag, count(*) as c, sum(l_quantity) as s "
    "from tpch.tiny.lineitem group by l_returnflag order by l_returnflag"
)

Q_JOIN = (
    "select o_orderpriority, count(*) as c from tpch.tiny.orders, "
    "tpch.tiny.customer where o_custkey = c_custkey "
    "group by o_orderpriority order by o_orderpriority"
)


def test_tpu_offload_changes_execution_device():
    """tpu_offload=false pins staging + execution to the first CPU
    device (the BASELINE.json dual-backend session gate)."""
    cpu0 = jax.devices("cpu")[0]
    off = LocalQueryRunner(
        session=Session(properties={"tpu_offload": False})
    )
    res = off.execute(Q_AGG)
    page = res.page
    assert all(
        b.data.devices() == {cpu0} for b in page.blocks
    ), "offload-off result must live on the first CPU device"
    # flag flip mid-session recompiles rather than reusing the cache
    on = LocalQueryRunner(session=Session(properties={"tpu_offload": True}))
    res2 = on.execute(Q_AGG)
    assert [tuple(r) for r in res.rows()] == [
        tuple(r) for r in res2.rows()
    ]


def test_verify_offload_mode():
    assert verify_offload(Q_AGG) is None
    assert verify_offload(Q_JOIN) is None


def test_join_distribution_type_forced_modes(oracle_mod):
    """PARTITIONED and BROADCAST forced modes both produce oracle-exact
    results (AUTOMATIC is covered by the main distributed suite)."""
    for mode in ("PARTITIONED", "BROADCAST"):
        r = DistributedQueryRunner(
            session=Session(properties={"join_distribution_type": mode}),
            broadcast_threshold=1 << 11,
            repl_threshold=1 << 10,
        )
        diff = verify_query(r, oracle_mod, Q_JOIN)
        assert diff is None, f"{mode}: {diff}"


def test_hash_partition_count_sets_mesh_width():
    r = DistributedQueryRunner(
        session=Session(properties={"hash_partition_count": 4})
    )
    assert r.n == 4
    r8 = DistributedQueryRunner()
    assert r8.n == len(jax.devices())


def test_split_batches_over_http(oracle_mod):
    """Small split batches stream many partial pages per task; results
    stay oracle-exact."""
    from presto_tpu.server import (
        CoordinatorServer,
        PrestoTpuClient,
        WorkerServer,
    )

    coord = CoordinatorServer().start()
    coord.local.session.set("page_capacity", 1 << 12)  # 4096-row batches
    w = WorkerServer(coordinator_uri=coord.uri).start()
    try:
        deadline = time.time() + 10
        while time.time() < deadline and not coord.active_workers():
            time.sleep(0.05)
        client = PrestoTpuClient(coord.uri, timeout_s=300)
        diff = verify_query(client, oracle_mod, Q_AGG)
        assert diff is None, diff
    finally:
        w.shutdown(graceful=False)
        coord.shutdown()


@pytest.mark.parametrize(
    "name,tier1",
    [
        ("stream_split_cache", None),
        ("task_concurrency", None),
        ("staging_prefetch_depth", "staging.prefetch-depth"),
    ],
)
def test_removed_scan_options_are_unknown_names(name, tier1):
    """A client or config file still naming an option the scan task
    no longer has fails loudly instead of being silently ignored."""
    with pytest.raises(KeyError, match=name):
        Session().set(name, 1)
    with pytest.raises(KeyError, match=name):
        Session(properties={name: 1})
    with pytest.raises(KeyError, match=name):
        LocalQueryRunner().execute(f"set session {name} = 1")
    for key in filter(None, (name, tier1)):
        with pytest.raises(KeyError, match=key):
            NodeConfig({key: "1"})


@pytest.fixture(scope="module")
def oracle_mod():
    return SqliteOracle("tiny")


def test_speculative_result_rows_single_round_trip(oracle_mod):
    """speculative_result_rows pins the one-round-trip materialization:
    a small aggregate result must need exactly ONE device_get; with the
    property 0, the control+materialize pair (two fetches) returns."""
    import jax

    from presto_tpu.exec import local_runner as LR

    r = LR.LocalQueryRunner()
    sql = (
        "select l_returnflag, count(*) as n from tpch.tiny.lineitem "
        "group by l_returnflag order by l_returnflag"
    )
    r.execute(sql).rows()  # warm: staging + compile out of the count

    calls = []
    orig = jax.device_get

    def spy(x):
        calls.append(1)
        return orig(x)

    jax.device_get, LR.jax.device_get = spy, spy
    try:
        rows1 = r.execute(sql).rows()
        one = len(calls)
        calls.clear()
        r.session.set("speculative_result_rows", 0)
        rows2 = r.execute(sql).rows()
        two = len(calls)
    finally:
        jax.device_get = LR.jax.device_get = orig
        r.session.set("speculative_result_rows", 1024)
    assert rows1 == rows2
    diff = verify_query(r, oracle_mod, sql)
    assert diff is None, diff
    assert one == 1, f"speculative path used {one} fetches"
    assert two == 2, f"fallback path used {two} fetches"
