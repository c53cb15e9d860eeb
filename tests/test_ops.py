"""Kernel operator tests: aggregation, sort/topN/limit/distinct, join,
window (SURVEY.md §7 step 3), in the reference's hand-built-page style
(SURVEY.md §4.1)."""

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from presto_tpu import types as T
from presto_tpu.expr import ColumnRef, ExprLowerer, Literal, arith
from presto_tpu.ops import (
    AggCall,
    SortKey,
    WindowCall,
    distinct,
    hash_aggregate,
    hash_join,
    limit,
    order_by,
    window,
)
from presto_tpu.ops.aggregation import _sorted_aggregate
from presto_tpu.page import Page


def make_page(capacity=None, **cols):
    data = {k: v[0] for k, v in cols.items()}
    schema = {k: v[1] for k, v in cols.items()}
    return Page.from_pydict(data, schema, capacity=capacity)


def col(page, name):
    return ColumnRef(name, page.schema()[name])


# ----------------------------------------------------------- aggregation


def test_hash_aggregate_basic():
    p = make_page(
        capacity=8,
        k=(["a", "b", "a", "c", "b", "a"], T.VARCHAR),
        x=([1, 2, 3, 4, 5, None], T.BIGINT),
    )
    out, overflow = jax.jit(
        lambda pg: hash_aggregate(
            pg,
            [("k", col(p, "k"))],
            [
                AggCall("sum", col(p, "x"), "s"),
                AggCall("count", col(p, "x"), "c"),
                AggCall("count_star", None, "cs"),
                AggCall("min", col(p, "x"), "mn"),
                AggCall("max", col(p, "x"), "mx"),
                AggCall("avg", col(p, "x"), "a"),
            ],
            max_groups=8,
        )
    )(p)
    assert not bool(overflow)
    rows = {r["k"]: r for r in out.to_pylist()}
    assert set(rows) == {"a", "b", "c"}
    # group a: x = 1, 3, NULL
    assert rows["a"]["s"] == 4 and rows["a"]["c"] == 2 and rows["a"]["cs"] == 3
    assert rows["a"]["mn"] == 1 and rows["a"]["mx"] == 3
    assert abs(rows["a"]["a"] - 2.0) < 1e-12
    assert rows["b"]["s"] == 7 and rows["c"]["s"] == 4


def test_hash_aggregate_decimal_exact_and_null_group():
    p = make_page(
        capacity=8,
        g=([1, 1, None, None, 2], T.BIGINT),
        d=([10.25, 0.75, 5.00, 1.00, 3.50], T.decimal(10, 2)),
    )
    out, _ = hash_aggregate(
        p, [("g", col(p, "g"))], [AggCall("sum", col(p, "d"), "s")], 8
    )
    rows = {r["g"]: r["s"] for r in out.to_pylist()}
    # nulls form ONE group
    assert rows[1] == 11.0 and rows[None] == 6.0 and rows[2] == 3.5


def test_hash_aggregate_overflow_flag():
    p = make_page(capacity=8, k=([1, 2, 3, 4, 5], T.BIGINT))
    out, overflow = hash_aggregate(
        p, [("k", col(p, "k"))], [AggCall("count_star", None, "c")], 3
    )
    assert bool(overflow)
    assert int(out.num_valid) == 3


def test_global_aggregate_empty_input():
    p = make_page(capacity=4, x=([], T.BIGINT))
    out, _ = hash_aggregate(
        p,
        [],
        [AggCall("count_star", None, "c"), AggCall("sum", col(p, "x"), "s")],
        1,
    )
    rows = out.to_pylist()
    assert rows == [{"c": 0, "s": None}]  # SQL: sum over empty = NULL


# ----------------------------------------------------------------- sort


def test_order_by_multi_key_desc_nulls():
    p = make_page(
        capacity=8,
        a=([2, 1, 2, None, 1], T.BIGINT),
        b=([1.5, 9.9, 0.5, 7.7, 1.1], T.DOUBLE),
    )
    out = order_by(
        p, [SortKey(col(p, "a")), SortKey(col(p, "b"), descending=True)]
    )
    rows = out.to_pylist()
    assert [r["a"] for r in rows] == [1, 1, 2, 2, None]  # nulls last (ASC)
    assert [r["b"] for r in rows][:4] == [9.9, 1.1, 1.5, 0.5]


def test_topn_and_limit():
    p = make_page(capacity=8, x=([5, 3, 9, 1, 7], T.BIGINT))
    out = order_by(p, [SortKey(col(p, "x"))], limit=3)
    assert out.capacity == 3
    assert [r["x"] for r in out.to_pylist()] == [1, 3, 5]
    l = limit(p, 2)
    assert int(l.num_valid) == 2


def test_distinct():
    p = make_page(capacity=8, x=([1, 2, 1, 3, 2], T.BIGINT))
    out, _ = distinct(p)
    assert sorted(r["x"] for r in out.to_pylist()) == [1, 2, 3]


# ----------------------------------------------------------------- join


def _join_pages():
    probe = make_page(
        capacity=8,
        pk=([10, 20, 30, 40, 10], T.BIGINT),
        pv=(["a", "b", "c", "d", "e"], T.VARCHAR),
    )
    build = make_page(
        capacity=4,
        bk=([10, 20, 50], T.BIGINT),
        bv=([100.0, 200.0, 500.0], T.DOUBLE),
    )
    return probe, build


def test_join_inner_unique():
    probe, build = _join_pages()
    out, ov = jax.jit(
        lambda p, b: hash_join(
            p, b, ["pk"], ["bk"],
            join_type="inner", build_payload=["bv"], build_unique=True,
        )
    )(probe, build)
    rows = sorted(out.to_pylist(), key=lambda r: (r["pk"], r["pv"]))
    assert [(r["pk"], r["bv"]) for r in rows] == [
        (10, 100.0), (10, 100.0), (20, 200.0),
    ]


def test_join_left_unique():
    probe, build = _join_pages()
    out, _ = hash_join(
        probe, build, ["pk"], ["bk"],
        join_type="left", build_payload=["bv"], build_unique=True,
    )
    rows = {(r["pk"], r["pv"]): r["bv"] for r in out.to_pylist()}
    assert rows[(30, "c")] is None and rows[(40, "d")] is None
    assert rows[(10, "a")] == 100.0


def test_join_semi_anti():
    probe, build = _join_pages()
    semi, _ = hash_join(probe, build, ["pk"], ["bk"], join_type="semi")
    assert sorted(r["pk"] for r in semi.to_pylist()) == [10, 10, 20]
    anti, _ = hash_join(probe, build, ["pk"], ["bk"], join_type="anti")
    assert sorted(r["pk"] for r in anti.to_pylist()) == [30, 40]


def test_join_duplicates_expansion():
    probe = make_page(capacity=4, k=([1, 2, 3], T.BIGINT))
    build = make_page(
        capacity=8,
        k2=([1, 1, 2, 9, 1], T.BIGINT),
        w=([10, 11, 20, 90, 12], T.BIGINT),
    )
    out, ov = hash_join(
        probe, build, ["k"], ["k2"],
        join_type="inner", build_payload=["w"], out_capacity=8,
    )
    assert not bool(ov)
    got = sorted((r["k"], r["w"]) for r in out.to_pylist())
    assert got == [(1, 10), (1, 11), (1, 12), (2, 20)]
    # overflow: capacity 2 < 4 matches
    out, ov = hash_join(
        probe, build, ["k"], ["k2"],
        join_type="inner", build_payload=["w"], out_capacity=2,
    )
    assert bool(ov) and int(out.num_valid) == 2


def test_join_left_duplicates():
    probe = make_page(capacity=4, k=([1, 7], T.BIGINT))
    build = make_page(capacity=4, k2=([1, 1], T.BIGINT), w=([10, 11], T.BIGINT))
    out, _ = hash_join(
        probe, build, ["k"], ["k2"],
        join_type="left", build_payload=["w"], out_capacity=4,
    )
    got = sorted(
        ((r["k"], r["w"]) for r in out.to_pylist()),
        key=lambda t: (t[0], t[1] if t[1] is not None else -1),
    )
    assert got == [(1, 10), (1, 11), (7, None)]


def test_join_null_keys_never_match():
    probe = make_page(capacity=4, k=([1, None], T.BIGINT))
    build = make_page(capacity=4, k2=([1, None], T.BIGINT), w=([10, 99], T.BIGINT))
    out, _ = hash_join(
        probe, build, ["k"], ["k2"],
        join_type="inner", build_payload=["w"], out_capacity=4,
    )
    assert [(r["k"], r["w"]) for r in out.to_pylist()] == [(1, 10)]
    anti, _ = hash_join(probe, build, ["k"], ["k2"], join_type="anti")
    # NOT EXISTS semantics: the null-key probe row is kept
    assert [r["k"] for r in anti.to_pylist()] == [None]


def test_join_two_column_key():
    probe = make_page(
        capacity=4, a=([1, 1, 2], T.INTEGER), b=([5, 6, 5], T.INTEGER)
    )
    build = make_page(
        capacity=4, a2=([1, 2], T.INTEGER), b2=([5, 5], T.INTEGER),
        w=([100, 200], T.BIGINT),
    )
    out, _ = hash_join(
        probe, build, ["a", "b"], ["a2", "b2"],
        join_type="inner", build_payload=["w"], build_unique=True,
    )
    got = sorted((r["a"], r["b"], r["w"]) for r in out.to_pylist())
    assert got == [(1, 5, 100), (2, 5, 200)]


def test_join_two_column_key_rejects_wide_types():
    import pytest

    probe = make_page(capacity=4, a=([1], T.BIGINT), b=([5], T.BIGINT))
    build = make_page(capacity=4, a2=([1], T.BIGINT), b2=([5], T.BIGINT))
    with pytest.raises(NotImplementedError):
        hash_join(probe, build, ["a", "b"], ["a2", "b2"], join_type="semi")


# --------------------------------------------------------------- window


def test_window_row_number_rank():
    p = make_page(
        capacity=8,
        g=(["x", "x", "x", "y", "y"], T.VARCHAR),
        v=([10, 10, 20, 5, 7], T.BIGINT),
    )
    out = window(
        p,
        [col(p, "g")],
        [SortKey(col(p, "v"))],
        [
            WindowCall("row_number", None, "rn"),
            WindowCall("rank", None, "rk"),
            WindowCall("dense_rank", None, "dr"),
        ],
    )
    rows = out.to_pylist()
    by_g = {}
    for r in rows:
        by_g.setdefault(r["g"], []).append((r["v"], r["rn"], r["rk"], r["dr"]))
    assert by_g["x"] == [(10, 1, 1, 1), (10, 2, 1, 1), (20, 3, 3, 2)]
    assert by_g["y"] == [(5, 1, 1, 1), (7, 2, 2, 2)]


def test_window_partition_aggregate():
    p = make_page(
        capacity=8,
        g=([1, 1, 2], T.BIGINT),
        v=([10.0, 30.0, 5.0], T.DOUBLE),
    )
    out = window(
        p, [col(p, "g")], [], [WindowCall("sum", col(p, "v"), "s")]
    )
    rows = {(r["g"], r["v"]): r["s"] for r in out.to_pylist()}
    assert rows[(1, 10.0)] == 40.0 and rows[(1, 30.0)] == 40.0
    assert rows[(2, 5.0)] == 5.0


def test_window_running_sum_with_peers():
    p = make_page(
        capacity=8,
        g=([1, 1, 1, 1], T.BIGINT),
        o=([1, 2, 2, 3], T.BIGINT),
        v=([10, 20, 30, 40], T.BIGINT),
    )
    out = window(
        p,
        [col(p, "g")],
        [SortKey(col(p, "o"))],
        [WindowCall("sum", col(p, "v"), "s")],
    )
    rows = [(r["o"], r["s"]) for r in out.to_pylist()]
    # RANGE frame: peers (o=2) share the running total including both
    assert rows == [(1, 10), (2, 60), (2, 60), (3, 100)]


def test_window_running_min():
    p = make_page(
        capacity=4,
        g=([1, 1, 2], T.BIGINT),
        o=([1, 2, 1], T.BIGINT),
        v=([5, 3, 9], T.BIGINT),
    )
    out = window(
        p,
        [col(p, "g")],
        [SortKey(col(p, "o"))],
        [WindowCall("min", col(p, "v"), "m")],
    )
    rows = [(r["g"], r["o"], r["m"]) for r in out.to_pylist()]
    assert rows == [(1, 1, 5), (1, 2, 3), (2, 1, 9)]


def test_window_running_min_peer_sharing():
    # RANGE frame: tied ORDER BY rows are peers and share the frame value
    p = make_page(
        capacity=4, g=([1, 1], T.BIGINT), o=([1, 1], T.BIGINT),
        v=([5, 3], T.BIGINT),
    )
    out = window(
        p, [col(p, "g")], [SortKey(col(p, "o"))],
        [WindowCall("min", col(p, "v"), "m")],
    )
    assert [r["m"] for r in out.to_pylist()] == [3, 3]


def test_window_running_min_null_frame():
    # first row's frame contains only NULL -> result NULL
    p = make_page(
        capacity=4, g=([1, 1], T.BIGINT), o=([1, 2], T.BIGINT),
        v=([None, 5], T.BIGINT),
    )
    out = window(
        p, [col(p, "g")], [SortKey(col(p, "o"))],
        [WindowCall("min", col(p, "v"), "m")],
    )
    assert [r["m"] for r in out.to_pylist()] == [None, 5]


def test_sorted_sum_overflow_trap():
    """A group whose TRUE sum exceeds int64 must raise through the error
    channel; groups whose sums fit must stay exact and silent even when
    the page-wide running cumsum wraps (modular arithmetic makes the
    span difference exact in that case)."""
    big = (1 << 62) + 7
    # group 1 sums to 2^63+14 -> real per-group overflow -> trap
    p = make_page(
        capacity=8,
        k=([1, 1, 2], T.BIGINT),
        x=([big, big, 10], T.BIGINT),
    )
    errors = []
    hash_aggregate(
        p,
        [("k", col(p, "k"))],
        [AggCall("sum", col(p, "x"), "s")],
        8,
        errors_out=errors,
    )
    assert errors, "sum must register an overflow trap"
    assert any(bool(flag) for _, flag in errors)

    # page-wide cumsum wraps (4 * (2^62+7) > 2^64) but every per-group
    # sum is representable: exact results, NO trap (the reference only
    # overflows per group)
    p2 = make_page(
        capacity=8,
        k=([1, 2, 3, 4], T.BIGINT),
        x=([big, big, big, big], T.BIGINT),
    )
    errors2 = []
    out2, _ = hash_aggregate(
        p2,
        [("k", col(p2, "k"))],
        [AggCall("sum", col(p2, "x"), "s")],
        8,
        errors_out=errors2,
    )
    assert not any(bool(flag) for _, flag in errors2)
    rows = {r["k"]: r["s"] for r in out2.to_pylist()}
    assert rows == {1: big, 2: big, 3: big, 4: big}

    # and a benign page must NOT trip the trap
    p3 = make_page(
        capacity=8,
        k=([1, 2, 1, 2], T.BIGINT),
        x=([10, 20, 30, 40], T.BIGINT),
    )
    errors3 = []
    out3, _ = hash_aggregate(
        p3,
        [("k", col(p3, "k"))],
        [AggCall("sum", col(p3, "x"), "s")],
        8,
        errors_out=errors3,
    )
    assert not any(bool(flag) for _, flag in errors3)
    rows = {r["k"]: r["s"] for r in out3.to_pylist()}
    assert rows == {1: 40, 2: 60}


# ---------------------------------------- one-hot path: output capacity


def _onehot_case(shape):
    """A Q1-shaped page: two dictionary keys (3 x 2 values), a decimal
    and a nullable bigint. ``plain`` has five of the six groups,
    ``nullable`` adds NULLs in the second key (seven groups), ``empty``
    filters every row out."""
    k1 = ["A", "N", "R", "A", "N", "R", "A", "N", "N", "A", "R", "N"]
    k2 = ["F", "O", "F", "F", "O", "F", "O", "F", "O", "F", "F", "O"]
    if shape == "nullable":
        k2 = [None if i in (2, 6, 9) else v for i, v in enumerate(k2)]
    d = [1.25, 2.50, 3.75, 10.00, 0.05, 7.10, 8.20, 9.30, 4.40, 5.55,
         6.65, 0.95]
    x = [3, None, 5, 7, 11, None, 13, 17, 19, 23, None, 29]
    p = make_page(
        capacity=16,
        k1=(k1, T.VARCHAR), k2=(k2, T.VARCHAR),
        d=(d, T.decimal(12, 2)), x=(x, T.BIGINT),
    )
    rows = list(zip(k1, k2, d, x))
    if shape == "empty":
        p = dataclasses.replace(
            p, live=jnp.zeros((16,), jnp.bool_),
            num_valid=jnp.asarray(0, jnp.int32),
        )
        rows = []
    return p, rows


def _onehot_reference(rows):
    """The same group-by in numpy, in the kernel's order: ascending
    keys, a key's NULL last."""
    groups = {}
    for k1, k2, d, x in rows:
        groups.setdefault((k1, k2), []).append((d, x))
    out = []
    for key in sorted(groups, key=lambda k: (k[0], k[1] is None, k[1] or "")):
        ds = np.array([round(d * 100) for d, _ in groups[key]], np.int64)
        xs = [x for _, x in groups[key] if x is not None]
        out.append({
            "k1": key[0], "k2": key[1],
            "sd": int(ds.sum()) / 100, "ad": float(ds.sum()) / 100 / len(ds),
            "cx": len(xs), "n": len(ds),
            "mn": min(xs) if xs else None, "mx": max(xs) if xs else None,
        })
    return out


def _onehot_aggs(p):
    return [
        AggCall("sum", col(p, "d"), "sd"),
        AggCall("avg", col(p, "d"), "ad"),
        AggCall("count", col(p, "x"), "cx"),
        AggCall("count_star", None, "n"),
        AggCall("min", col(p, "x"), "mn"),
        AggCall("max", col(p, "x"), "mx"),
    ]


def _same_rows(got, want):
    assert len(got) == len(want)
    for g, w in zip(got, want):
        assert g.keys() == w.keys()
        for name in w:
            if isinstance(w[name], float):
                assert abs(g[name] - w[name]) < 1e-9, (name, g, w)
            else:
                assert g[name] == w[name], (name, g, w)


@pytest.mark.parametrize("shape", ["plain", "nullable", "empty"])
@pytest.mark.parametrize("max_groups", [4, 1024, 1 << 20, 1 << 24])
def test_onehot_output_sized_by_proved_domain(max_groups, shape):
    """The one-hot path's page is ``min(max_groups, 1024)`` long whatever
    bucket the planner estimated, holds the rows of a plain group-by,
    and overflows exactly where ``max_groups`` is under the groups."""
    p, rows = _onehot_case(shape)
    keys = [("k1", col(p, "k1")), ("k2", col(p, "k2"))]
    out, overflow = jax.jit(
        lambda pg: hash_aggregate(pg, keys, _onehot_aggs(p), max_groups)
    )(p)
    want = _onehot_reference(rows)
    assert out.capacity == min(max_groups, 1024)
    assert all(b.capacity == out.capacity for b in out.blocks)
    assert bool(overflow) == (len(want) > max_groups)
    assert int(out.num_valid) == min(len(want), max_groups)
    _same_rows(out.to_pylist(), want[:max_groups])
    if max_groups == 1024:
        lowerer = ExprLowerer(p)
        evald = [(n, *lowerer.eval(e), e) for n, e in keys]
        srt, s_over = _sorted_aggregate(
            p, evald, _onehot_aggs(p), max_groups, p.row_mask(), lowerer
        )
        assert not bool(s_over) and srt.capacity == out.capacity
        _same_rows(out.to_pylist(), srt.to_pylist())


def test_onehot_program_holds_nothing_max_groups_long():
    """Trace only: at a 2^16-row batch and the planner's 2^24 bucket no
    value of the program is longer than the one-hot matrix itself."""
    p, _ = _onehot_case("nullable")
    cap, nseg = 1 << 16, 3 * (2 + 1)
    big = jax.tree_util.tree_map(
        lambda a: jax.ShapeDtypeStruct((cap,) + a.shape[1:], a.dtype)
        if getattr(a, "ndim", 0) else a,
        p,
    )
    jaxpr = jax.make_jaxpr(
        lambda pg: hash_aggregate(
            pg, [("k1", col(p, "k1")), ("k2", col(p, "k2"))],
            _onehot_aggs(p), 1 << 24,
        )
    )(big)

    def sizes(jp):
        for eqn in jp.eqns:
            for v in eqn.outvars:
                yield int(np.prod(v.aval.shape, dtype=np.int64)), eqn.primitive.name
            for sub in jax.core.jaxprs_in_params(eqn.params):
                yield from sizes(sub)

    worst = max(sizes(jaxpr.jaxpr))
    assert worst[0] <= cap * nseg, worst
    assert jaxpr.out_avals[0].shape == (1024,)
