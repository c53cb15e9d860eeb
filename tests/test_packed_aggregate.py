"""The sorted aggregation moves no row by a permutation
(ops/aggregation.py: _key_lanes, _sorted_aggregate): its page equals a
numpy reference and the lane-by-lane branch's (_ordered_aggregate) over
every key shape, and ONE rule sizes every grouped aggregation's page
(_out_capacity): results and shapes on the CPU, no times."""

import dataclasses
import math

import jax.numpy as jnp
import numpy as np
import pytest

from presto_tpu import types as T
from presto_tpu.expr import ColumnRef, ExprLowerer
from presto_tpu.ops import aggregation
from presto_tpu.ops.aggregation import AggCall, _out_capacity, hash_aggregate
from presto_tpu.page import Page

CAP = 4096
DEC = T.decimal(12, 2)
SCHEMA = {
    "k": T.BIGINT, "d": T.DATE, "s": T.VARCHAR, "x": DEC, "f": T.DOUBLE,
    "o": T.INTEGER, "p": T.INTEGER,
}
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
    # Q3's: a stated 23-bit key, then two int32 keys with no range
    "q3": (("o", "d", "p"), ((1, 6_000_000), None, None)),
    "bigint_unranged": (("k",), ()),
    "double_dictionary": (("f", "s"), ()),
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
        "f": rng.choice(rng.normal(size=40), n).tolist(),
        "o": some_null(
            rng.choice(rng.integers(1, 6_000_001, 300), n).tolist(), 0.03
        ),
        "p": some_null(rng.integers(-2, 3, n).tolist(), 0.05),
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


def _lane_by_lane(page, keys, max_groups=1 << 20, aggs=AGGS):
    """The same aggregation through the lane-by-lane branch, which moves
    keys and arguments by the permutation and reads the groups at their
    spans."""
    lowerer = ExprLowerer(page)
    evald = [(k, *lowerer.eval(ColumnRef(k, SCHEMA[k])), ColumnRef(k, SCHEMA[k]))
             for k in keys]
    errors = []
    out, overflow = aggregation._ordered_aggregate(
        page, evald, aggs, max_groups, page.row_mask(), lowerer, errors
    )
    return out, bool(overflow), [m for m, flag in errors if bool(flag)]


def _column(page, name):
    b = page.block(name)
    data = np.asarray(b.data)
    valid = np.ones(len(data), bool) if b.valid is None else np.asarray(b.valid)
    return [x.item() if ok else None for x, ok in zip(data, valid)]


def _rows(out):
    """The live prefix as tuples of raw values (scaled decimals,
    dictionary ids, days), None for NULL."""
    n = int(out.num_valid)
    return list(zip(*[_column(out, name)[:n] for name in out.names]))


def _reference(page, keys, aggs=AGGS):
    """numpy/Python over the page's raw columns: the groups of its live
    rows in key order, NULL after a key's values."""
    live = np.asarray(page.row_mask())
    cols = {c: _column(page, c) for c in SCHEMA}
    groups = {}
    for i in np.nonzero(live)[0]:
        groups.setdefault(tuple(cols[k][i] for k in keys), []).append(i)
    rows = []
    for key in sorted(groups, key=lambda t: [(v is None, v or 0) for v in t]):
        at = groups[key]
        out = list(key)
        for agg in aggs:
            vals = [] if agg.arg is None else [
                cols[agg.arg.name][i] for i in at
                if cols[agg.arg.name][i] is not None
            ]
            out.append({
                "count_star": lambda: len(at),
                "count": lambda: len(vals),
                "sum": lambda: sum(vals) if vals else None,
                "min": lambda: min(vals) if vals else None,
                "max": lambda: max(vals) if vals else None,
                "avg": lambda: sum(vals) / len(vals) if vals else None,
            }[agg.func]())
        rows.append(tuple(out))
    return rows


def _same(got, want):
    assert len(got) == len(want)
    for g, w in zip(got, want):
        assert len(g) == len(w), (g, w)
        for a, b in zip(g, w):
            if isinstance(b, float) and a is not None:
                assert math.isclose(a, b, rel_tol=1e-9, abs_tol=1e-12), (g, w)
            else:
                assert a == b, (g, w)


@pytest.fixture
def lanes(monkeypatch):
    """The radix of every key-lane layout ``_key_lanes`` builds, one a
    sorted aggregation: per lane its digits' sizes, None for a key's
    own lane."""
    calls = []
    real = aggregation._key_lanes

    def spy(keys, proofs, live):
        out = real(keys, proofs, live)
        calls.append(out[1])
        return out

    monkeypatch.setattr(aggregation, "_key_lanes", spy)
    return calls


@pytest.fixture
def ordered(monkeypatch):
    """Calls of the lane-by-lane branch."""
    calls = []
    real = aggregation._ordered_aggregate

    def spy(*args, **kw):
        calls.append(args[2])
        return real(*args, **kw)

    monkeypatch.setattr(aggregation, "_ordered_aggregate", spy)
    return calls


@pytest.mark.parametrize("seed", [1, 2, 3])
@pytest.mark.parametrize("shape", sorted(SHAPES))
def test_packed_sort_gives_the_page_of_the_lane_by_lane_sort(
    shape, seed, lanes
):
    """With or without the stated ranges, the page of the sort that
    moves no row is the numpy reference's and the lane-by-lane
    branch's: keys, aggregates, row count, order, NULL groups."""
    keys, ranges = SHAPES[shape]
    page = _page(seed)
    want = _reference(page, keys)
    lbl, l_over, l_err = _lane_by_lane(page, keys)
    for stated in (ranges, ()):
        with aggregation.record_row_moves() as moves:
            got, over, err = _run(page, keys, stated)
        assert moves == [0]
        assert (over, err) == (l_over, l_err) == (False, [])
        assert int(got.num_valid) == int(lbl.num_valid) == len(want) > 50
        assert got.names == lbl.names
        _same(_rows(got), want)
        _same(_rows(lbl), want)
        for a, b in zip(got.blocks, lbl.blocks):
            assert (a.dtype, a.data.dtype) == (b.dtype, b.data.dtype)
            assert (a.valid is None) == (b.valid is None)
    # sized by the proved domain where that is under the rows' bucket
    assert got.capacity == lbl.capacity == CAP
    assert len(lanes) == 2


def test_a_stated_key_and_its_neighbours_share_a_lane(lanes):
    """Q3's keys — a stated 1..6,000,000 and two int32 keys with no
    range — take three lanes when none is nullable: 23 bits of the
    first with the dead rows' sentinel, one lane each for the others.
    NULLs add a digit of two to the lane before a key's own."""
    keys, ranges = SHAPES["q3"]
    _run(_page(5, nulls=False), keys, ranges)
    assert lanes[-1] == [(6_000_000,), None, None]
    _run(_page(5), keys, ranges)
    assert lanes[-1] == [(6_000_001, 2), None, (2,), None]
    _run(_page(5), ("k", "d"), SHAPES["bigint_date"][1])
    assert lanes[-1] == [(707, 32)]
    _run(_page(5), ("k",), ())  # an int64 key: two lanes of its own
    assert lanes[-1] == [(2,), None, None]


@pytest.mark.parametrize("what", [
    "no_nulls", "no_dead_rows", "empty", "full_distinct", "max_groups_under",
])
@pytest.mark.parametrize("shape", ["q3", "bigint_unranged"])
def test_sorted_path_edge_pages_match_numpy(shape, what):
    keys, ranges = SHAPES[shape]
    page = _page(8, nulls=what != "no_nulls", dead=what != "no_dead_rows")
    max_groups = 1 << 20
    if what == "empty":
        page = dataclasses.replace(page, live=jnp.zeros(CAP, bool))
    if what == "full_distinct":
        cols = {c: list(range(1, CAP + 1)) if c in keys else [1] * CAP
                for c in SCHEMA if c != "s"}
        cols["s"] = ["a"] * CAP
        cols["f"] = [0.5] * CAP
        page = Page.from_pydict(cols, SCHEMA, capacity=CAP)
    if what == "max_groups_under":
        max_groups = 64
    want = _reference(page, keys)
    with aggregation.record_row_moves() as moves:
        got, over, err = _run(page, keys, ranges, max_groups)
    lbl, l_over, _ = _lane_by_lane(page, keys, max_groups)
    assert moves == [0] and not err
    assert over == l_over == (len(want) > max_groups)
    assert int(got.num_valid) == min(len(want), max_groups)
    _same(_rows(got), want[:max_groups])
    if over:
        # the lane-by-lane branch's last kept group runs to the page's
        # end (its spans end where the next kept group starts): the host
        # runs an overflowed batch again, so only the keys are compared
        _same([r[:len(keys)] for r in _rows(got)],
              [r[:len(keys)] for r in _rows(lbl)])
    else:
        _same(_rows(got), _rows(lbl))
    if what == "full_distinct":
        assert len(want) == CAP
    if what == "empty":
        assert not want


@pytest.mark.parametrize("what", ["no_nulls", "no_dead_rows", "empty"])
def test_packed_sort_edge_pages(what, lanes):
    page = _page(7, nulls=what != "no_nulls", dead=what != "no_dead_rows")
    if what == "empty":
        page = dataclasses.replace(page, live=jnp.zeros(CAP, bool))
    keys, ranges = SHAPES["bigint_date"]
    want, _, _ = _run(page, keys, ())
    got, over, err = _run(page, keys, ranges)
    assert lanes[-1] == [(707 - (what == "no_nulls"), 32 - (what == "no_nulls"))]
    assert not over and not err
    assert got.to_pylist() == want.to_pylist()
    _same(_rows(got), _reference(page, keys))
    assert (int(got.num_valid) == 0) == (what == "empty")


@pytest.mark.parametrize("ranges, why", [
    ((), "nothing stated"),
    ((None,), "no range for the key"),
    (((-5, 700), None), "one key of two unproved"),
    (((0, 2**32),), "a range past 32 bits"),
    (((-5, 700), (0, 2**31)), "a composite past 32 bits"),
])
def test_a_key_without_a_proof_takes_today_s_path(
    ranges, why, lanes, ordered
):
    """A key with no proof, or a composite past 32 bits, no longer takes
    the lane-by-lane branch: it takes lanes of its own (or a second
    packed lane) on the same sorted path, and moves no row."""
    keys = ("k", "d")[: max(len(ranges), 1)]
    page = _page(5)
    want, _, _ = _lane_by_lane(page, keys)
    with aggregation.record_row_moves() as moves:
        got, _, err = _run(page, keys, ranges)
    assert len(ordered) == 1 and not err and moves == [0], why
    assert len(lanes[-1]) > 1, why  # not the one-lane packed key
    _same(_rows(got), _rows(want))
    _same(_rows(got), _reference(page, keys))
    assert got.capacity == want.capacity


def test_order_statistics_keep_today_s_path(lanes, ordered):
    """The order statistics and array_agg need the permutation: they
    keep the lane-by-lane branch, and it moves rows."""
    page = _page(6)
    pct = [AggCall("approx_percentile", ColumnRef("x", DEC), "p", param=0.5)]
    want, _, _ = _run(page, ("k",), (), aggs=pct)
    with aggregation.record_row_moves() as moves:
        got, _, _ = _run(page, ("k",), ((-5, 700),), aggs=pct)
    assert not lanes and len(ordered) == 2
    assert moves[0] > 0
    assert got.to_pylist() == want.to_pylist()
    arr = [AggCall("array_agg", ColumnRef("p", T.INTEGER), "a")] + AGGS[:3]
    got, _, _ = _run(page, ("k",), ((-5, 700),), aggs=arr)
    assert not lanes and len(ordered) == 3
    _same([r[:1] + r[2:] for r in _rows(got)],
          _reference(page, ("k",), AGGS[:3]))


def test_a_value_outside_its_stated_range_reads_as_an_overflow(lanes):
    """Statistics that went stale must not change an answer: the value
    would land in another group's slot, so the batch reports an
    overflow, and the host runs it again with nothing stated."""
    page = _page(4)
    _, overflow, err = _run(page, ("k",), ((-5, 600),))
    assert lanes and overflow and not err
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
    monkeypatch, lanes
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
    assert [(60,)] in lanes  # the stated range packed the key
    assert runner.history.snapshot()[-1].retries >= 1


@pytest.mark.parametrize("ranges, extra", [
    ((), ()),
    (((1, 3),), ()),
    ((), (AggCall("array_agg", ColumnRef("k", T.BIGINT), "a"),)),
], ids=["lanes", "packed", "ordered"])
def test_the_bigint_sum_overflow_trap_fires_on_both_paths(
    ranges, extra, lanes, ordered
):
    big = 2**62
    aggs = [AggCall("sum", ColumnRef("v", T.BIGINT), "s"), *extra]
    page = Page.from_pydict(
        {"k": [1, 1, 1, 2, 3], "v": [big, big, big, 5, -7]},
        {"k": T.BIGINT, "v": T.BIGINT}, capacity=1024,
    )
    errors = []
    out, _ = hash_aggregate(
        page, [("k", ColumnRef("k", T.BIGINT))], aggs, 1024,
        errors_out=errors, key_ranges=ranges,
    )
    assert bool(ordered) == bool(extra)
    if not extra:
        assert (None in lanes[0]) == (not ranges)
    fired = [m for m, flag in errors if bool(flag)]
    assert fired == ["bigint sum overflow in s"]
    # and stays silent where every group's sum fits, whatever the page's total
    fits = Page.from_pydict(
        {"k": [1, 2, 3, 3], "v": [big, big, big, 5]},
        {"k": T.BIGINT, "v": T.BIGINT}, capacity=1024,
    )
    errors = []
    out, _ = hash_aggregate(
        fits, [("k", ColumnRef("k", T.BIGINT))], aggs, 1024,
        errors_out=errors, key_ranges=ranges,
    )
    assert not any(bool(flag) for _, flag in errors)
    assert [r["s"] for r in out.to_pylist()] == [big, big, big + 5]


@pytest.mark.parametrize("ranges", [(), ((0, 40),)], ids=["lanes", "packed"])
def test_sums_near_the_trap_stay_exact(ranges):
    """Groups whose sums fit int64 over a page whose running total wraps
    many times: every sum exact, the trap silent."""
    rng = np.random.default_rng(3)
    k = rng.integers(0, 41, 600)
    k = np.concatenate([k, np.arange(41)])  # every group has a row
    keep = np.zeros_like(k, bool)
    for g in range(41):  # at most six rows a group: 6 x 2^60 < 2^63
        keep[np.nonzero(k == g)[0][:6]] = True
    k = k[keep]
    v = 2**60 + rng.integers(-1000, 1000, len(k))
    v[::7] *= -1
    page = Page.from_pydict(
        {"k": k.tolist(), "v": v.tolist()}, {"k": T.BIGINT, "v": T.BIGINT},
        capacity=1024,
    )
    errors = []
    out, overflow = hash_aggregate(
        page, [("k", ColumnRef("k", T.BIGINT))],
        [AggCall("sum", ColumnRef("v", T.BIGINT), "s")], 1024,
        errors_out=errors, key_ranges=ranges,
    )
    assert not bool(overflow)
    assert not any(bool(flag) for _, flag in errors)
    want = [int(sum(int(x) for x in v[k == g])) for g in range(41)]
    assert [r["s"] for r in out.to_pylist()] == want
    assert abs(sum(int(x) for x in np.abs(v))) >= 2**62  # the shadow ran


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


def test_agg_row_moves_counts_each_dispatch():
    """``device.agg_row_moves`` adds, at every dispatch of a program,
    the row moves its trace counted: 0 for a grouped aggregation on the
    sorted path, the lane-by-lane branch's gathers, ``nonzero_1d`` and
    scatter for an ``array_agg`` — as many again when the compiled
    program runs again, and nothing while telemetry is off."""
    from presto_tpu.exec.local_runner import LocalQueryRunner
    from presto_tpu.utils.telemetry import DEVICE, device_snapshot

    runner = LocalQueryRunner()

    def moves(sql):
        before = device_snapshot()
        runner.execute(sql)
        after = device_snapshot()
        assert after["dispatches"] > before["dispatches"] or not DEVICE.enabled
        return after["agg_row_moves"] - before["agg_row_moves"]

    for sql in (
        "select l_orderkey, sum(l_extendedprice) from lineitem group by 1",
        "select l_shipdate, l_tax, count(*), max(l_discount) from lineitem "
        "group by 1, 2",
    ):
        assert moves(sql) == 0
    arr = ("select l_suppkey, array_agg(l_linenumber), sum(l_quantity) "
           "from lineitem group by 1")
    first = moves(arr)
    assert first > 0
    assert moves(arr) == first  # the compiled program, counted again
    DEVICE.set_enabled(False)
    try:
        assert moves(arr) == 0
    finally:
        DEVICE.set_enabled(True)
