"""The sorted aggregation over keys proved to fit 32 bits
(ops/aggregation.py: _packed_key, _packed_groups) gives the page the
lane-by-lane path gives, and ONE rule sizes every grouped aggregation's
page (_out_capacity): results and shapes on the CPU, no times."""

import dataclasses

import jax.numpy as jnp
import numpy as np
import pytest

from presto_tpu import types as T
from presto_tpu.expr import ColumnRef
from presto_tpu.ops import aggregation
from presto_tpu.ops.aggregation import AggCall, _out_capacity, hash_aggregate
from presto_tpu.page import Page

CAP = 4096
DEC = T.decimal(12, 2)
SCHEMA = {"k": T.BIGINT, "d": T.DATE, "s": T.VARCHAR, "x": DEC, "f": T.DOUBLE}
AGGS = [
    AggCall("sum", ColumnRef("x", DEC), "sum_x"),
    AggCall("count", ColumnRef("x", DEC), "count_x"),
    AggCall("count_star", None, "n"),
    AggCall("min", ColumnRef("x", DEC), "min_x"),
    AggCall("max", ColumnRef("f", T.DOUBLE), "max_f"),
    AggCall("avg", ColumnRef("f", T.DOUBLE), "avg_f"),
]
#: key lists and the ranges stated for them; a dictionary key needs none
SHAPES = {
    "bigint": (("k",), ((-5, 700),)),
    "bigint_date": (("k", "d"), ((-5, 700), (9000, 9030))),
    "dictionary_bigint": (("s", "k"), (None, (-5, 700))),
}


def _page(seed, nulls=True, dead=True):
    """Seeded random rows: NULL keys and arguments, dead rows between
    the live ones, a short live prefix."""
    rng = np.random.default_rng(seed)
    n = CAP - 500

    def some_null(values, share):
        values = list(values)
        if nulls:
            for i in rng.integers(0, n, int(n * share)):
                values[i] = None
        return values

    data = {
        "k": some_null(rng.integers(-5, 701, n).tolist(), 0.05),
        "d": some_null(rng.integers(9000, 9031, n).tolist(), 0.02),
        "s": some_null(rng.choice(list("abcdefghij"), n).tolist(), 0.05),
        "x": some_null((rng.integers(-10**7, 10**7, n) / 100).tolist(), 0.1),
        "f": rng.normal(size=n).tolist(),
    }
    page = Page.from_pydict(data, SCHEMA, capacity=CAP)
    if dead:
        live = np.zeros(CAP, bool)
        live[:n] = rng.random(n) > 0.4
        page = dataclasses.replace(page, live=jnp.asarray(live))
    return page


def _run(page, keys, ranges, max_groups=1 << 20, aggs=AGGS):
    errors = []
    out, overflow = hash_aggregate(
        page, [(k, ColumnRef(k, SCHEMA[k])) for k in keys], aggs, max_groups,
        errors_out=errors, key_ranges=ranges,
    )
    return out, bool(overflow), [m for m, flag in errors if bool(flag)]


@pytest.fixture
def packed_calls(monkeypatch):
    calls = []
    groups = aggregation._packed_groups

    def spy(gid, out_cap, carried=()):
        calls.append(out_cap)
        return groups(gid, out_cap, carried)

    monkeypatch.setattr(aggregation, "_packed_groups", spy)
    return calls


@pytest.mark.parametrize("seed", [1, 2, 3])
@pytest.mark.parametrize("shape", sorted(SHAPES))
def test_packed_sort_gives_the_page_of_the_lane_by_lane_sort(
    shape, seed, packed_calls
):
    keys, ranges = SHAPES[shape]
    page = _page(seed)
    want, w_over, w_err = _run(page, keys, ())
    assert not packed_calls  # no proof: today's path
    got, g_over, g_err = _run(page, keys, ranges)
    assert len(packed_calls) == 1
    assert (g_over, g_err) == (w_over, w_err) == (False, [])
    assert int(got.num_valid) == int(want.num_valid) > 100
    assert got.names == want.names
    assert got.to_pylist() == want.to_pylist()  # order, NULL groups, floats
    for a, b in zip(got.blocks, want.blocks):
        assert (a.dtype, a.data.dtype) == (b.dtype, b.data.dtype)
    # sized by the proved domain where that is under the rows' bucket
    assert got.capacity == min(packed_calls[0], CAP) <= want.capacity == CAP


@pytest.mark.parametrize("what", ["no_nulls", "no_dead_rows", "empty"])
def test_packed_sort_edge_pages(what, packed_calls):
    page = _page(7, nulls=what != "no_nulls", dead=what != "no_dead_rows")
    if what == "empty":
        page = dataclasses.replace(page, live=jnp.zeros(CAP, bool))
    keys, ranges = SHAPES["bigint_date"]
    want, _, _ = _run(page, keys, ())
    got, over, err = _run(page, keys, ranges)
    assert packed_calls and not over and not err
    assert got.to_pylist() == want.to_pylist()
    assert (int(got.num_valid) == 0) == (what == "empty")


@pytest.mark.parametrize("ranges, why", [
    ((), "nothing stated"),
    ((None,), "no range for the key"),
    (((-5, 700), None), "one key of two unproved"),
    (((0, 2**32),), "a range past 32 bits"),
    (((-5, 700), (0, 2**31)), "a composite past 32 bits"),
])
def test_a_key_without_a_proof_takes_today_s_path(ranges, why, packed_calls):
    keys = ("k", "d")[: max(len(ranges), 1)]
    page = _page(5)
    want, _, _ = _run(page, keys, ())
    got, _, err = _run(page, keys, ranges)
    assert not packed_calls and not err, why
    assert got.to_pylist() == want.to_pylist()
    assert got.capacity == want.capacity


def test_order_statistics_keep_today_s_path(packed_calls):
    page = _page(6)
    pct = [AggCall("approx_percentile", ColumnRef("x", DEC), "p", param=0.5)]
    want, _, _ = _run(page, ("k",), (), aggs=pct)
    got, _, _ = _run(page, ("k",), ((-5, 700),), aggs=pct)
    assert not packed_calls
    assert got.to_pylist() == want.to_pylist()


def test_a_value_outside_its_stated_range_reads_as_an_overflow(packed_calls):
    """Statistics that went stale must not change an answer: the value
    would land in another group's slot, so the batch reports an
    overflow, and the host runs it again with nothing stated."""
    page = _page(4)
    _, overflow, err = _run(page, ("k",), ((-5, 600),))
    assert packed_calls and overflow and not err
    # a NULL or a dead row's value is no one's business
    data = np.asarray(page.block("k").data).copy()
    dead_or_null = ~np.asarray(page.row_mask()) | ~np.asarray(page.block("k").valid)
    data[dead_or_null] = 10**12
    blocks = tuple(
        dataclasses.replace(b, data=jnp.asarray(data)) if n == "k" else b
        for n, b in zip(page.names, page.blocks)
    )
    moved = dataclasses.replace(page, blocks=blocks)
    got, overflow, _ = _run(moved, ("k",), ((-5, 700),))
    assert not overflow
    assert got.to_pylist() == _run(page, ("k",), ())[0].to_pylist()


def test_a_statement_over_stale_statistics_runs_again_and_answers_right(
    monkeypatch, packed_calls
):
    """The connector states l_suppkey in [1, 60] where tiny has 100
    suppliers: every program that packs the key overflows, is run again
    with nothing stated, and the answer is the oracle's."""
    from presto_tpu.connectors import tpch
    from presto_tpu.exec.local_runner import LocalQueryRunner
    from presto_tpu.verifier import SqliteOracle, verify_query

    stats = tpch._TpchMetadata.get_table_stats

    def stale(self, handle):
        got = stats(self, handle)
        cols = dict(got.columns)
        if "l_suppkey" in cols:
            cols["l_suppkey"] = dataclasses.replace(cols["l_suppkey"], max_value=60)
        return dataclasses.replace(got, columns=cols)

    monkeypatch.setattr(tpch._TpchMetadata, "get_table_stats", stale)
    runner = LocalQueryRunner()
    sql = ("select l_suppkey, sum(l_quantity) q, count(*) c from lineitem "
           "group by l_suppkey")
    assert verify_query(runner, SqliteOracle("tiny"), sql) is None
    assert packed_calls
    assert runner.history.snapshot()[-1].retries >= 1


@pytest.mark.parametrize("ranges", [(), ((1, 3),)], ids=["lanes", "packed"])
def test_the_bigint_sum_overflow_trap_fires_on_both_paths(ranges, packed_calls):
    big = 2**62
    page = Page.from_pydict(
        {"k": [1, 1, 1, 2, 3], "v": [big, big, big, 5, -7]},
        {"k": T.BIGINT, "v": T.BIGINT}, capacity=1024,
    )
    errors = []
    out, _ = hash_aggregate(
        page, [("k", ColumnRef("k", T.BIGINT))],
        [AggCall("sum", ColumnRef("v", T.BIGINT), "s")], 1024,
        errors_out=errors, key_ranges=ranges,
    )
    assert bool(packed_calls) == bool(ranges)
    fired = [m for m, flag in errors if bool(flag)]
    assert fired == ["bigint sum overflow in s"]
    # and stays silent where every group's sum fits, whatever the page's total
    fits = Page.from_pydict(
        {"k": [1, 2, 3, 3], "v": [big, big, big, 5]},
        {"k": T.BIGINT, "v": T.BIGINT}, capacity=1024,
    )
    errors = []
    out, _ = hash_aggregate(
        fits, [("k", ColumnRef("k", T.BIGINT))],
        [AggCall("sum", ColumnRef("v", T.BIGINT), "s")], 1024,
        errors_out=errors, key_ranges=ranges,
    )
    assert not any(bool(flag) for _, flag in errors)
    assert [r["s"] for r in out.to_pylist()] == [big, big, big + 5]


@pytest.mark.parametrize("max_groups, rows, proved, want", [
    (1 << 24, 1 << 20, None, 1 << 20),    # a split batch: its rows
    (1 << 22, 1 << 20, 100_000, 1 << 17),  # Q15 at SF10: the suppliers
    (1 << 24, 1 << 20, 6, 1024),           # Q1: the smallest bucket
    (1 << 16, 1 << 20, None, 1 << 16),     # the planner's bucket is least
    (1 << 22, 1 << 22, 100_000, 1 << 17),  # the root stage's merge
    (256, 4096, None, 256),                # a caller's own small bound
    (1 << 20, 60, None, 1024),             # rows under the smallest bucket
    (1 << 20, 5000, 5000, 8192),           # buckets, not counts
])
def test_one_rule_sizes_the_page(max_groups, rows, proved, want):
    assert _out_capacity(max_groups, rows, proved) == want


@pytest.mark.parametrize("max_groups", [1024, 2048, 1 << 20])
def test_the_page_has_no_more_slots_than_its_input_has_rows(max_groups):
    """Every row a group of its own: ``max_groups`` under the groups
    overflows and keeps the first ones; over them, the rows' bucket
    bounds the page and nothing can overflow."""
    n = 3000
    page = Page.from_pydict(
        {"k": list(range(n)), "x": [1.0] * n}, {"k": T.BIGINT, "x": DEC},
        capacity=CAP,
    )
    out, overflow = hash_aggregate(
        page, [("k", ColumnRef("k", T.BIGINT))],
        [AggCall("sum", ColumnRef("x", DEC), "s")], max_groups,
    )
    assert out.capacity == min(max_groups, CAP)
    assert bool(overflow) == (n > max_groups)
    assert int(out.num_valid) == min(n, max_groups)
