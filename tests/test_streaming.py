"""Larger-than-HBM streaming execution: the TPC-H corpus with the
device-residency budget forced far below lineitem's size, so every
lineitem query takes the split-stream + bucket-spill path — verified
against the sqlite oracle (reference: spilling/grouped-execution tests;
SURVEY.md §5.7)."""

import jax
import numpy as np
import pytest

from presto_tpu.exec.local_runner import LocalQueryRunner
from presto_tpu.session import Session
from presto_tpu.verifier import SqliteOracle, verify_query

from tpch_queries import QUERIES

#: tiny-SF lineitem is ~60k rows; 16384 forces it (and only it) to
#: stream in ~8 batches of 4096 with >= 16 spill buckets
MAX_DEVICE_ROWS = 16_384
BATCH_ROWS = 4_096


@pytest.fixture(scope="module")
def runner():
    return LocalQueryRunner(
        session=Session(
            properties={
                "max_device_rows": MAX_DEVICE_ROWS,
                "page_capacity": BATCH_ROWS,
                "spill_enabled": True,
            }
        )
    )


@pytest.fixture(scope="module")
def oracle():
    return SqliteOracle("tiny")


#: queries that scan lineitem (stream) — the others stay resident
LINEITEM_QUERIES = [
    q
    for q in sorted(QUERIES)
    if "lineitem" in QUERIES[q]
]


@pytest.mark.parametrize("qnum", LINEITEM_QUERIES)
def test_tpch_streamed(qnum, runner, oracle):
    diff = verify_query(runner, oracle, QUERIES[qnum], rel_tol=1e-6)
    assert diff is None, f"Q{qnum} streamed mismatch: {diff}"


def test_streaming_actually_engaged(runner):
    """The path must really stream: count partial-fragment executions
    by spying on the spill function."""
    from presto_tpu.exec import streaming

    calls = []
    orig = streaming._spill_partial

    def spy(*a, **k):
        calls.append(1)
        return orig(*a, **k)

    streaming._spill_partial = spy
    try:
        fresh = LocalQueryRunner(
            session=Session(
                properties={
                    "max_device_rows": MAX_DEVICE_ROWS,
                    "page_capacity": BATCH_ROWS,
                }
            )
        )
        fresh.execute(
            "select l_returnflag, sum(l_quantity) as s "
            "from tpch.tiny.lineitem group by l_returnflag"
        )
    finally:
        streaming._spill_partial = orig
    assert len(calls) >= 10, f"expected >=10 streamed batches, {len(calls)}"


def test_spill_disabled_fails_cleanly():
    from presto_tpu.exec.streaming import StreamingError

    r = LocalQueryRunner(
        session=Session(
            properties={
                "max_device_rows": MAX_DEVICE_ROWS,
                "spill_enabled": False,
            }
        )
    )
    with pytest.raises(StreamingError):
        r.execute("select count(*) as c from tpch.tiny.lineitem")


#: non-aggregate streamed shapes (VERDICT r2 item 10): big sort and big
#: join-probe plans must stream too, not raise StreamingError
NON_AGG_STREAMED = {
    "sort_topn": """
        select l_orderkey, l_extendedprice from tpch.tiny.lineitem
        order by l_extendedprice desc, l_orderkey, l_linenumber
        limit 20""",
    "sort_full": """
        select l_orderkey, l_linenumber, l_extendedprice
        from tpch.tiny.lineitem
        order by l_extendedprice, l_orderkey, l_linenumber""",
    "join_probe_agg": """
        select o_orderpriority, count(*) as n
        from tpch.tiny.orders, tpch.tiny.lineitem
        where o_orderkey = l_orderkey and l_quantity > 45
        group by o_orderpriority order by o_orderpriority""",
    "join_output_no_agg": """
        select o_orderkey, l_quantity
        from tpch.tiny.orders, tpch.tiny.lineitem
        where o_orderkey = l_orderkey and l_quantity > 49
          and o_totalprice > 400000
        order by o_orderkey, l_quantity limit 30""",
}


@pytest.mark.parametrize("name", sorted(NON_AGG_STREAMED))
def test_non_agg_streamed_shapes(name, runner, oracle):
    """Sort and join-output plans over a scan exceeding the device
    budget stream through the split pipeline (resident build side,
    streamed probe) instead of failing."""
    diff = verify_query(runner, oracle, NON_AGG_STREAMED[name], rel_tol=1e-6)
    assert diff is None, f"{name} streamed mismatch: {diff}"


def test_bucket_hash_stable_across_dictionaries():
    """The same value must land in the same bucket even when two
    batches encode it with different dictionary ids."""
    from presto_tpu.connectors.tpch import DictColumn
    from presto_tpu.exec.streaming import _bucket_of

    p1 = {
        "k": DictColumn(
            ids=np.array([0, 1], np.int32),
            values=np.array(["apple", "banana"], object),
        )
    }
    p2 = {
        "k": DictColumn(
            ids=np.array([1, 0], np.int32),
            values=np.array(["aardvark", "apple"], object),
        )
    }
    b1 = _bucket_of(p1, ["k"], 2, 64)
    b2 = _bucket_of(p2, ["k"], 2, 64)
    assert b1[0] == b2[0]  # "apple" agrees across id spaces


# ------------------------------------------- join build-side spill


@pytest.fixture(scope="module")
def tight_runner():
    """Budget below ORDERS (15k rows): a join building orders must take
    the partitioned build-side spill (no replicated cut exists)."""
    return LocalQueryRunner(
        session=Session(
            properties={
                "max_device_rows": 8_192,
                "page_capacity": 4_096,
                "spill_enabled": True,
            }
        )
    )


def test_join_build_spill_semi(tight_runner, oracle):
    """Semi join with a >budget build side: both sides hash-partition
    to host buckets, per-bucket joins concatenate (reference:
    HashBuilderOperator partitioned spill + unspill replay)."""
    q = (
        "select count(*) as c from tpch.tiny.customer "
        "where c_custkey in (select o_custkey from tpch.tiny.orders "
        "where o_totalprice > 100000)"
    )
    diff = verify_query(tight_runner, oracle, q)
    assert diff is None, diff


def test_join_build_spill_anti(tight_runner, oracle):
    q = (
        "select count(*) as c from tpch.tiny.customer "
        "where c_custkey not in (select o_custkey from tpch.tiny.orders "
        "where o_totalprice > 150000)"
    )
    diff = verify_query(tight_runner, oracle, q)
    assert diff is None, diff


def test_join_build_spill_left_payload(tight_runner, oracle):
    """LEFT join building raw >budget orders with payload columns:
    preserved probe rows and bucket-scattered matches reassemble
    oracle-exact (no agg cut exists, so only the partitioned build
    spill can run this)."""
    q = (
        "select count(*) as c, sum(o_totalprice) as s "
        "from tpch.tiny.customer left join tpch.tiny.orders "
        "on c_custkey = o_custkey"
    )
    diff = verify_query(tight_runner, oracle, q)
    assert diff is None, diff


def test_split_cache_skips_restaging(oracle):
    """The SECOND streamed pass over the same scan must not touch the
    connector for split batches the cache's budget holds (the table
    cache at split granularity — SURVEY.md §5.7)."""
    r = LocalQueryRunner(
        session=Session(
            properties={
                "max_device_rows": MAX_DEVICE_ROWS,
                "page_capacity": BATCH_ROWS,
            }
        )
    )
    conn = r.catalogs.get("tpch")
    calls = []
    orig = conn.create_page_source

    def spy(split, columns):
        calls.append(split)
        return orig(split, columns)

    q = (
        "select l_returnflag, sum(l_quantity) as s, count(*) as c "
        "from tpch.tiny.lineitem group by l_returnflag"
    )
    conn.create_page_source = spy
    try:
        first = r.execute(q)
        n_first = len(calls)
        calls.clear()
        second = r.execute(q)
        n_second = len(calls)
    finally:
        conn.create_page_source = orig
    assert n_first >= 10, f"expected >=10 staged batches, {n_first}"
    assert n_second == 0, (
        f"second pass re-staged {n_second} splits through the cache"
    )
    assert sorted(first.rows()) == sorted(second.rows())


def test_split_cache_zero_budget_restages(oracle):
    """A runner built with a zero cache budget keeps no streamed batch:
    every pass reads and stages every split again (the embedder's
    setting when the scanned set genuinely exceeds HBM)."""
    r = LocalQueryRunner(
        session=Session(
            properties={
                "max_device_rows": MAX_DEVICE_ROWS,
                "page_capacity": BATCH_ROWS,
            }
        ),
        staging_cache_bytes=0,
    )
    conn = r.catalogs.get("tpch")
    calls = []
    orig = conn.create_page_source

    def spy(split, columns):
        calls.append(split)
        return orig(split, columns)

    q = (
        "select count(*) as c from tpch.tiny.lineitem "
        "where l_quantity < 10"
    )
    conn.create_page_source = spy
    try:
        r.execute(q)
        n_first = len(calls)
        calls.clear()
        r.execute(q)
        n_second = len(calls)
    finally:
        conn.create_page_source = orig
    assert n_second == n_first >= 10
