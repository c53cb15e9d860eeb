"""Operator programs compiled for the described chip (v5e), no chip
attached: what the TPU compiler refuses, it refuses here.

The ONE file that loads the TPU compiler (``on-chip-measurement`` guide
§2): the topology and everything built from it live in module-scoped
fixtures of this file — never at import, in a ``skipif``, in
``parametrize`` or in ``conftest.py`` — because only one process may
hold libtpu and every xdist worker imports every test file. Compiles
run in this process (threads, not children: a child could not load the
library this process holds) with the persistent cache off around them.

A compile that passes is not a chip run: nothing here says anything
about results or times on the device.
"""

import concurrent.futures
import dataclasses
import decimal
import re
import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P
from jax.sharding import SingleDeviceSharding

from presto_tpu import types as T
from presto_tpu.expr import ColumnRef, Compare, Literal, arith
from presto_tpu.ops import (
    AggCall,
    SortKey,
    filter_project,
    hash_aggregate,
    hash_join,
    order_by,
)
from presto_tpu.ops.common import orderable_i64
from presto_tpu.ops.join import join_build
from presto_tpu.page import Page
from presto_tpu.parallel import exchange as X
from presto_tpu.session import Session

#: the real page capacity (session default ``page_capacity``) for the
#: elementwise programs; the sort-bearing ones take a smaller bucket,
#: because what the compiler refuses does not depend on the row count
#: and its time does (at 1 << 20 rows these four cases alone compile
#: for three and a half minutes here, side by side)
CAP = int(Session().get("page_capacity"))
SORT_CAP = 1 << 16
BUILD_CAP = 1 << 12

#: one column per physical type the engine stores: int64, scaled-int64
#: decimal, DOUBLE, dictionary ids, DATE, the (cap, 2) long-decimal
#: limb pair, BOOLEAN
TYPES = {
    "i": T.BIGINT, "dec": T.decimal(12, 2), "dbl": T.DOUBLE,
    "s": T.VARCHAR, "d": T.DATE, "ld": T.decimal(38, 2), "b": T.BOOLEAN,
}
_VALUES = {
    "i": [1, 2, None, 4], "dec": [1.25, None, 3.5, 4.0],
    "dbl": [0.5, -1.5, None, 2.0], "s": ["a", "b", None, "a"],
    "d": [9131, None, 9496, 9862],
    "ld": [decimal.Decimal("1.25"), None, decimal.Decimal("3.50"),
           decimal.Decimal("4.00")],
    "b": [True, False, None, True],
}


def _page(cap, cols=tuple(TYPES)):
    return Page.from_pydict(
        {c: _VALUES[c] for c in cols}, {c: TYPES[c] for c in cols},
        capacity=cap,
    )


def _col(name):
    return ColumnRef(name, TYPES[name])


def _spec(tree, sharding):
    return jax.tree_util.tree_map(
        lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype, sharding=sharding),
        tree,
    )


# ---------------------------------------------------------------- cases


def _case_orderable(one_chip, mesh):
    x = _page(CAP, ("dbl",)).block("dbl").data
    return jax.jit(lambda v: orderable_i64(v, T.DOUBLE)), (_spec(x, one_chip),)


def _case_filter_project(one_chip, mesh):
    def fn(p):
        return filter_project(
            p,
            Compare(">", _col("dbl"), Literal(0.0, T.DOUBLE)),
            [(c, _col(c)) for c in TYPES]
            + [("x", arith("*", _col("dec"), _col("dec"))),
               ("y", arith("+", _col("dbl"), _col("dbl")))],
        )

    return jax.jit(fn), (_spec(_page(CAP), one_chip),)


def _case_sort(one_chip, mesh):
    keys = [SortKey(_col("dbl"), descending=True), SortKey(_col("s"))]
    return (
        jax.jit(lambda p: order_by(p, keys)),
        (_spec(_page(SORT_CAP, ("dbl", "s", "ld")), one_chip),),
    )


def _case_aggregate(one_chip, mesh):
    """The sorted path over keys with lanes of their own (a DOUBLE key
    has no static domain, a DATE no stated range): the float64 and
    int64 arguments riding the lane sorts, the int64 cumsum with its
    float64 overflow shadow, the segmented float64 scan riding the
    compacting sort, the DOUBLE key put back from its lanes by
    bitcast."""
    aggs = [
        AggCall("sum", _col("dec"), "s1"), AggCall("avg", _col("dbl"), "a1"),
        AggCall("count_star", None, "c"),
    ]
    keys = [(c, _col(c)) for c in ("dbl", "d")]
    return (
        jax.jit(lambda p: hash_aggregate(p, keys, aggs, max_groups=1 << 12)),
        (_spec(_page(SORT_CAP, ("dbl", "d", "dec")), one_chip),),
    )


def _case_aggregate_packed(one_chip, mesh):
    """The packed sort (bigint and date keys with stated ranges): one
    uint32 lane, its sort and the compacting one, the keys unpacked, the
    same accumulators, and the range check among the error flags."""
    aggs = [AggCall("sum", _col("dec"), "s1"), AggCall("count_star", None, "c")]
    keys = [(c, _col(c)) for c in ("i", "d")]

    def fn(p):
        errors = []
        out, overflow = hash_aggregate(
            p, keys, aggs, max_groups=1 << 22, errors_out=errors,
            key_ranges=((1, 100_000), (9_000, 10_000)),
        )
        return out, overflow, [flag for _, flag in errors]

    return jax.jit(fn), (_spec(_page(SORT_CAP, ("i", "d", "dec")), one_chip),)


#: Q3's partial aggregation at a split batch's real size
Q3_CAP = 1 << 20


def _case_aggregate_q3(one_chip, mesh):
    """Q3's grouping at a split batch's 2^20 rows: a key with a stated
    23-bit range (``l_orderkey``) and two int32 keys with none, three
    lanes sorted one a pass with the decimal argument riding, then the
    compacting sort."""
    dec = T.decimal(18, 4)
    schema = {"ok": T.INTEGER, "od": T.DATE, "sp": T.INTEGER, "rev": dec}
    page = Page.from_pydict(
        {"ok": [1, 2], "od": [9000, 9001], "sp": [0, 0], "rev": [1.0, 2.0]},
        schema, capacity=Q3_CAP,
    )
    page = dataclasses.replace(page, live=jnp.ones(Q3_CAP, bool))
    keys = [(c, ColumnRef(c, schema[c])) for c in ("ok", "od", "sp")]
    aggs = [AggCall("sum", ColumnRef("rev", dec), "revenue")]

    def fn(p):
        errors = []
        out, overflow = hash_aggregate(
            p, keys, aggs, max_groups=1 << 22, errors_out=errors,
            key_ranges=((1, 6_000_000), None, None),
        )
        return out, overflow, [flag for _, flag in errors]

    return jax.jit(fn), (_spec(page, one_chip),)


def _case_join(one_chip, mesh):
    def fn(probe, build):
        return hash_join(
            probe, build, ["dbl"], ["dbl"], "inner",
            build_payload=["i", "b"],
            payload_rename={"i": "b_i", "b": "b_b"},
            out_capacity=SORT_CAP,
        )

    return jax.jit(fn), (
        _spec(_page(SORT_CAP, ("dbl", "dec")), one_chip),
        _spec(_page(BUILD_CAP, ("dbl", "i", "b")), one_chip),
    )


def _case_join_ranked(join_type, unique):
    """The probe that ranks by sort, on an int64 key: the three-operand
    sorts of ``_match_ranges`` (two lanes and the origin; the origin
    with lo and hi) at ``BUILD_CAP + SORT_CAP`` rows, and for a
    duplicate build once more over the output slots, for FULL once more
    from the build's side."""

    def case(one_chip, mesh):
        def fn(probe, build):
            return hash_join(
                probe, build, ["i"], ["i"], join_type,
                build_payload=["dec", "b"],
                payload_rename={"dec": "b_dec", "b": "b_b"},
                build_unique=unique,
                out_capacity=None if unique else SORT_CAP,
            )

        return jax.jit(fn), (
            _spec(_page(SORT_CAP, ("i", "dbl")), one_chip),
            _spec(_page(BUILD_CAP, ("i", "dec", "b")), one_chip),
        )

    return case


#: a stated range for the build's BIGINT key: one uint32 lane
ONE_LANE = ((1, 100_000),)


def _case_join_build(key_ranges=()):
    """A build side put in key order by a program of its own (what a
    task prepares once for its batches): the key's lanes sorted with the
    row mask and a payload of every stored type riding (DOUBLE, the
    long decimal's limbs, BOOLEAN, dictionary ids); on two lanes the
    int64 key put back from them by bitcast, on one lane (``key_ranges``)
    one sort pass and the range check."""

    def case(one_chip, mesh):
        def fn(build):
            return join_build(
                build, ["i"], ["dec", "b", "dbl", "ld", "s"], key_ranges
            )

        return jax.jit(fn), (
            _spec(
                _page(BUILD_CAP, ("i", "dec", "b", "dbl", "ld", "s")),
                one_chip,
            ),
        )

    return case


def _case_join_prepared(key_ranges=()):
    """A batch's join over a prepared build: the probe and the output
    only — on one lane (``key_ranges``) the probe keys packed into the
    build's lane and two sort passes, not three."""

    def case(one_chip, mesh):
        build = jax.eval_shape(
            lambda b: join_build(b, ["i"], ["dec", "b"], key_ranges),
            _page(BUILD_CAP, ("i", "dec", "b")),
        )

        def fn(probe, prepared):
            return hash_join(
                probe, prepared, ["i"], ["i"], "inner",
                build_payload=["dec", "b"],
                payload_rename={"dec": "b_dec", "b": "b_b"},
                build_unique=True,
            )

        return jax.jit(fn), (
            _spec(_page(SORT_CAP, ("i", "dbl")), one_chip),
            _spec(build, one_chip),
        )

    return case


def _case_partition_exchange(one_chip, mesh):
    """The mesh executor's REPARTITION and REPLICATE under shard_map on
    the 2x2 topology: hash over a DOUBLE, a dictionary and an int64
    key, ``all_to_all`` the buckets."""
    n = mesh.devices.size
    shard_cap = SORT_CAP // n
    base = _page(shard_cap, ("i", "dbl", "s"))
    stacked = jax.tree_util.tree_map(
        lambda x: jnp.concatenate([jnp.atleast_1d(x)] * n), base
    )

    def prog(p):
        local = dataclasses.replace(p, num_valid=p.num_valid[0])
        dest = (
            X.partition_hash(local, ["dbl", "s", "i"]) % jnp.uint64(n)
        ).astype(jnp.int32)
        out, overflow = X.partition_exchange(
            local, dest, n, "workers", shard_cap // 2
        )
        rep = X.replicate(local, n, "workers")
        return (
            dataclasses.replace(out, num_valid=out.num_valid.reshape(1)),
            overflow.reshape(1),
            rep.num_valid.reshape(1),
        )

    fn = jax.jit(
        jax.shard_map(
            prog, mesh=mesh, in_specs=(P("workers"),),
            out_specs=P("workers"),
        )
    )
    return fn, (_spec(stacked, NamedSharding(mesh, P("workers"))),)


CASES = {
    "orderable_i64[double]": _case_orderable,
    "filter_project[every type]": _case_filter_project,
    "sort[double desc, dictionary keys; long-decimal payload]": _case_sort,
    "hash_aggregate[double, date keys]": _case_aggregate,
    "hash_aggregate[packed bigint, date keys]": _case_aggregate_packed,
    "hash_aggregate[Q3's keys, 2^20 rows]": _case_aggregate_q3,
    "hash_join[double key]": _case_join,
    "hash_join[unique build]": _case_join_ranked("inner", True),
    "hash_join[duplicate build, left]": _case_join_ranked("left", False),
    "hash_join[duplicate build, full]": _case_join_ranked("full", False),
    "join_build[prepared once a task]": _case_join_build(),
    "join_build[one lane]": _case_join_build(ONE_LANE),
    "hash_join[prepared build]": _case_join_prepared(),
    "join_ranked[one lane]": _case_join_prepared(ONE_LANE),
    "partition_exchange[2x2 mesh]": _case_partition_exchange,
}

#: cases compiled one at a time ahead of the others: side by side with
#: them the compiler takes this one four times as long as alone, most of
#: it in one thread (1,112 s against 279 s on an 8-core x86 host, the
#: whole file 643 s where it was 1,115 s), and it bounded the file's time
ALONE = ("hash_aggregate[double, date keys]",)


# ------------------------------------------------------------- fixtures


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies

    try:
        return topologies.get_topology_desc(
            platform="tpu", topology_name="v5e:2x2"
        )
    except Exception as e:
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture(scope="module")
def mesh(topo):
    return Mesh(np.array(topo.devices), ("workers",))


@pytest.fixture(scope="module")
def compiled(one_chip, mesh):
    """Every case lowered and compiled once — those of ``ALONE`` one at
    a time first, then the rest side by side (the compiler releases the
    GIL; a sort costs it tens of seconds whatever the row count) —
    persistent cache off so nothing is read from or written to it. Maps
    case name -> (compiled | exception, seconds)."""
    from jax.experimental.compilation_cache import compilation_cache

    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()

    def one(name):
        t0 = time.monotonic()
        try:
            fn, args = CASES[name](one_chip, mesh)
            out = fn.lower(*args).compile()
        except Exception as e:  # reported by the case's own test
            out = e
        return name, (out, time.monotonic() - t0)

    try:
        out = dict(one(name) for name in ALONE)
        rest = [name for name in CASES if name not in ALONE]
        with concurrent.futures.ThreadPoolExecutor(len(rest)) as pool:
            out.update(pool.map(one, rest))
        yield out
    finally:
        jax.config.update("jax_enable_compilation_cache", was)
        compilation_cache.reset_cache()


# ---------------------------------------------------------------- tests


@pytest.mark.parametrize("name", list(CASES))
def test_compiles_for_v5e(compiled, name):
    out, seconds = compiled[name]
    print(f"{name}: {seconds:.1f}s")
    if isinstance(out, Exception):
        raise AssertionError(
            f"the v5e compiler refused {name}: {out}"
        ) from out
    assert out.as_text()


@pytest.mark.parametrize("name", [
    "hash_aggregate[double, date keys]",
    "hash_aggregate[packed bigint, date keys]",
    "hash_aggregate[Q3's keys, 2^20 rows]",
])
def test_sorted_aggregation_moves_no_row(compiled, name):
    """No gather and no scatter: the keys and arguments ride the lane
    sorts, the groups are read by the compacting sort."""
    out, _ = compiled[name]
    assert not isinstance(out, Exception), out
    ops = re.findall(r"\s(gather|scatter|sort)\(", out.as_text())
    assert "gather" not in ops and "scatter" not in ops
    assert "sort" in ops


@pytest.mark.parametrize("one_lane,two_lanes,sorts", [
    ("join_ranked[one lane]", "hash_join[prepared build]", (2, 3)),
    ("join_build[one lane]", "join_build[prepared once a task]", (1, 2)),
])
def test_one_lane_join_sorts_one_pass_fewer(
    compiled, one_lane, two_lanes, sorts
):
    """A key with a stated range rides one uint32 lane: the unique probe
    ranks in two sorts where the int64 key takes three (the lane with
    the origin; the origin with lo and hi), the build sorts once where
    it sorts twice, and neither gathers more."""
    ops = {}
    for name in (one_lane, two_lanes):
        out, _ = compiled[name]
        assert not isinstance(out, Exception), out
        ops[name] = re.findall(r"\s(gather|scatter|sort)\(", out.as_text())
    one, two = ops[one_lane], ops[two_lanes]
    assert (one.count("sort"), two.count("sort")) == sorts
    assert one.count("gather") <= two.count("gather")
    assert one.count("scatter") <= two.count("scatter")


def test_partition_exchange_program_holds_the_collective(compiled):
    out, _ = compiled["partition_exchange[2x2 mesh]"]
    assert not isinstance(out, Exception), out
    # (REPLICATE's all_gather feeds only a count here and the compiler
    # turns it into an all-reduce; the routed buckets cannot be)
    assert "all-to-all" in out.as_text()
