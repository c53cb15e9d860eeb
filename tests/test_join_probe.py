"""The join's probe ranks by sort (``ops/join.py: _match_ranges``): the
ranges it finds against ``np.searchsorted``, ``hash_join``'s pages
against a plain reference and, leaf for leaf, against the pages the
binary-search probe it replaced builds, and the shape of the traced
program — no loop, and no gather of a probe column where the build is
unique. Keys with stated ranges on one uint32 lane against their two
lanes and the reference, and a key outside its range."""

import dataclasses
import itertools

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.extend.core import Var

from presto_tpu import types as T
from presto_tpu.ops import hash_join
from presto_tpu.ops import join as J
from presto_tpu.page import Page

MAX = int(J._I64_MAX)


# ------------------------------------------------------- _match_ranges


def _keys(rng, n, kind):
    if kind == "dups":  # a handful of values: long runs on both sides
        return rng.integers(-4, 5, n)
    if kind == "wide":  # beyond 32 bits, both signs: both uint32 lanes
        return rng.integers(-(2 ** 62), 2 ** 62, n)
    if kind == "lanes":  # equal low words under different high words
        return (rng.integers(-3, 4, n) << 32) | rng.integers(0, 3, n)
    raise AssertionError(kind)


def _with_sentinels(rng, keys, share):
    keys = np.asarray(keys, np.int64).copy()
    keys[rng.random(keys.shape[0]) < share] = MAX
    return keys


RANGE_CASES = {
    # name: (build slots, probe slots, key kind, dead share of the
    # build, dead share of the probe)
    "duplicates on both sides": (64, 64, "dups", 0.0, 0.0),
    "keys beyond 32 bits and negative": (128, 96, "wide", 0.0, 0.0),
    "low words tie under different high words": (96, 128, "lanes", 0.0, 0.0),
    "sentinels in the build's tail": (64, 64, "dups", 0.4, 0.0),
    "sentinels among the probe rows": (64, 64, "dups", 0.0, 0.4),
    "sentinels on both sides": (128, 128, "lanes", 0.3, 0.3),
    "one-slot build": (1, 32, "dups", 0.0, 0.0),
    "one-slot build, dead": (1, 32, "dups", 1.0, 0.2),
    "all-dead build": (32, 48, "dups", 1.0, 0.0),
    "all-dead probe": (32, 48, "wide", 0.0, 1.0),
    "probe far longer than build": (8, 4096, "dups", 0.2, 0.1),
    "build far longer than probe": (4096, 8, "lanes", 0.1, 0.2),
    "one-slot probe": (256, 1, "dups", 0.0, 0.0),
}


@pytest.mark.parametrize("seed", [0, 1])
@pytest.mark.parametrize("name", list(RANGE_CASES))
def test_match_ranges_equal_searchsorted(name, seed):
    nb, n_p, kind, dead_b, dead_p = RANGE_CASES[name]
    rng = np.random.default_rng([seed, nb, n_p])
    build = _with_sentinels(rng, _keys(rng, nb, kind), dead_b)
    probe = _with_sentinels(rng, _keys(rng, n_p, kind), dead_p)
    lo, hi = jax.jit(J._match_ranges)(jnp.asarray(build), jnp.asarray(probe))
    assert lo.dtype == hi.dtype == jnp.int32
    # the build need not arrive sorted (hash_join hands it over as it
    # is); the ranges are those in its sorted order
    sorted_build = np.sort(build)
    np.testing.assert_array_equal(
        np.asarray(lo), np.searchsorted(sorted_build, probe, side="left")
    )
    np.testing.assert_array_equal(
        np.asarray(hi), np.searchsorted(sorted_build, probe, side="right")
    )


# ----------------------------------------------------------- hash_join


def _searchsorted_ranges(build_keys, probe_keys):
    """The probe this PR replaced: two binary searches in the sorted
    build keys."""
    s = jnp.sort(build_keys)
    return (
        jnp.searchsorted(s, probe_keys, side="left"),
        jnp.searchsorted(s, probe_keys, side="right"),
    )


def _pages(seed, two_column, unique):
    """A probe of 48 slots (40 rows, some keys NULL) against a build of
    32 slots (20 rows, some keys NULL), keys drawn so that most probe
    rows match, some several times unless ``unique``."""
    rng = np.random.default_rng([seed, two_column, unique])
    kt = T.INTEGER if two_column else T.BIGINT
    scale = 1 if two_column else 2 ** 33  # wide keys for the one-column

    def key(vals, null_share):
        return [
            None if rng.random() < null_share else int(v) * scale
            for v in vals
        ]

    bvals = rng.permutation(24)[:20] if unique else rng.integers(0, 8, 20)
    build = {"bk": key(bvals - 4, 0.15), "w": list(range(100, 120)),
             "wd": [None if i % 5 == 0 else i / 4 for i in range(20)]}
    probe = {"pk": key(rng.integers(-6, 22 if unique else 10, 40), 0.15),
             "v": [None if i % 7 == 0 else i for i in range(40)]}
    pschema = {"pk": kt, "v": T.BIGINT}
    bschema = {"bk": kt, "w": T.BIGINT, "wd": T.DOUBLE}
    pkeys, bkeys = ["pk"], ["bk"]
    if two_column:
        build["bk2"] = [int(x) for x in rng.integers(0, 2, 20)]
        probe["pk2"] = [int(x) for x in rng.integers(0, 2, 40)]
        pschema["pk2"] = bschema["bk2"] = kt
        pkeys, bkeys = ["pk", "pk2"], ["bk", "bk2"]
        if unique:  # the PAIR has to be unique
            build["bk2"] = [0] * 20
    return (
        Page.from_pydict(probe, pschema, capacity=48),
        Page.from_pydict(build, bschema, capacity=32),
        pkeys, bkeys,
    )


def _reference(probe, build, pkeys, bkeys, join_type):
    """Nested loops over the rows: a multiset of output tuples."""
    prow, brow = probe.to_pylist(), build.to_pylist()
    pcols = list(probe.names)

    def k(row, cols):
        vals = tuple(row[c] for c in cols)
        return None if None in vals else vals

    out, hit_b = [], set()
    for p in prow:
        ms = [
            i for i, b in enumerate(brow)
            if k(p, pkeys) is not None and k(p, pkeys) == k(b, bkeys)
        ]
        hit_b.update(ms)
        base = tuple(p[c] for c in pcols)
        if join_type == "semi":
            out += [base] if ms else []
        elif join_type == "anti":
            out += [] if ms else [base]
        else:
            out += [base + (brow[i]["w"], brow[i]["wd"]) for i in ms]
            if not ms and join_type in ("left", "full"):
                out.append(base + (None, None))
    if join_type == "full":
        out += [
            (None,) * len(pcols) + (b["w"], b["wd"])
            for i, b in enumerate(brow) if i not in hit_b
        ]
    return sorted(out, key=repr)


def _joiner(pkeys, bkeys, join_type, unique):
    payload = [] if join_type in ("semi", "anti") else ["w", "wd"]
    return lambda p, b: hash_join(
        p, b, pkeys, bkeys, join_type=join_type, build_payload=payload,
        build_unique=unique, out_capacity=None if unique else 256,
    )


def _join(probe, build, pkeys, bkeys, join_type, unique):
    return jax.jit(_joiner(pkeys, bkeys, join_type, unique))(probe, build)


JOIN_CASES = [
    pytest.param(jt, unique, two, id=(
        f"{jt}-{'unique' if unique else 'duplicate'} build-"
        f"{'two-column' if two else 'wide'} key"
    ))
    for jt, unique, two in itertools.product(
        ("inner", "left", "full", "semi", "anti"), (True, False),
        (False, True),
    )
]


@pytest.mark.parametrize("join_type,unique,two_column", JOIN_CASES)
def test_hash_join_pages(join_type, unique, two_column, monkeypatch):
    probe, build, pkeys, bkeys = _pages(7, two_column, unique)
    out, overflow = _join(probe, build, pkeys, bkeys, join_type, unique)
    assert not bool(overflow)
    cols = list(out.names)
    got = sorted(
        (tuple(r[c] for c in cols) for r in out.to_pylist()), key=repr
    )
    assert got == _reference(probe, build, pkeys, bkeys, join_type)
    assert int(out.num_valid) == len(got)

    # and bit for bit the pages of the binary-search probe, dead slots
    # included: same lo, hi by construction
    monkeypatch.setattr(J, "_match_ranges", _searchsorted_ranges)
    was, was_overflow = _join(probe, build, pkeys, bkeys, join_type, unique)
    assert out.names == was.names and bool(was_overflow) == bool(overflow)
    new_leaves, new_tree = jax.tree_util.tree_flatten(out)
    old_leaves, old_tree = jax.tree_util.tree_flatten(was)
    assert new_tree == old_tree
    for a, b in zip(new_leaves, old_leaves):
        assert a.dtype == b.dtype
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


# ------------------------------------------------------ traced program


def _walk(jaxpr, marked, found):
    """Every equation of ``jaxpr`` and of the programs it calls:
    collects primitive names in ``found["prims"]`` and the gathers whose
    operand is a ``marked`` variable (carried into called programs by
    position) in ``found["gathers"]``."""
    for eqn in jaxpr.eqns:
        found["prims"].add(eqn.primitive.name)
        if eqn.primitive.name == "gather" and eqn.invars[0] in marked:
            found["gathers"].append(eqn)
        for sub in jax.core.jaxprs_in_params(eqn.params):
            inner = {
                iv for iv, ov in zip(sub.invars, eqn.invars[-len(sub.invars):])
                if isinstance(ov, Var) and ov in marked
            } if sub.invars else set()
            _walk(sub, inner, found)


def _trace(join_type, unique):
    probe, build, pkeys, bkeys = _pages(3, False, unique)
    closed = jax.make_jaxpr(_joiner(pkeys, bkeys, join_type, unique))(
        probe, build
    )
    n_probe = len(jax.tree_util.tree_leaves(probe))
    found = {"prims": set(), "gathers": []}
    _walk(closed.jaxpr, set(closed.jaxpr.invars[:n_probe]), found)
    return found


@pytest.mark.parametrize("unique", [True, False], ids=["unique", "duplicate"])
@pytest.mark.parametrize(
    "join_type", ["inner", "left", "full", "semi", "anti"]
)
def test_traced_join_has_no_loop(join_type, unique):
    found = _trace(join_type, unique)
    assert "sort" in found["prims"]
    assert "while" not in found["prims"], sorted(found["prims"])
    if join_type in ("semi", "anti"):
        # the probe page itself under a new mask: nothing is gathered
        assert not found["gathers"]
    elif unique:
        # output row i is probe row i: only the build's payload is
        # gathered
        assert "gather" in found["prims"] and not found["gathers"]
    else:
        # (the walk does see a probe column's gather where there is one)
        assert found["gathers"]


# ------------------------------------------------------------ one lane


#: the build keys' stated ranges: one BIGINT key, or two INTEGER keys
#: packed as mixed-radix digits
LANE_RANGES = {
    "one key with a range": ((-3, 12),),
    "two packed keys": ((-3, 12), (5, 7)),
}


def _ranged_pages(seed, shape, unique):
    """A probe of 64 slots against a build of 32, both with NULL keys
    and dead rows under a mask (and a dead tail): the build's keys in
    their stated ranges, both ends among them; the probe's a few steps
    beyond them on either side too."""
    rng = np.random.default_rng([seed, len(shape), unique])
    # without a range: the two INTEGER keys, nothing stated
    (lo, hi), (lo2, hi2) = LANE_RANGES["two packed keys"]
    two = shape != "one key with a range"
    kt = T.INTEGER if two else T.BIGINT

    def with_nulls(vals, share):
        return [None if rng.random() < share else int(v) for v in vals]

    if unique:
        if two:
            combos = [(a, b) for a in range(lo, hi + 1)
                      for b in range(lo2, hi2 + 1)]
            picked = [combos[i] for i in rng.permutation(len(combos))[:12]]
        else:
            picked = [
                (int(a), lo2) for a in rng.permutation(hi - lo + 1)[:12] + lo
            ]
        ends = [(lo, lo2), (hi, hi2 if two else lo2)]
        pairs = list(dict.fromkeys(ends + picked))
        a_vals, b_vals = [p[0] for p in pairs], [p[1] for p in pairs]
    else:
        a_vals = list(rng.integers(lo, hi + 1, 22)) + [lo, hi]
        b_vals = list(rng.integers(lo2, hi2 + 1, 22)) + [lo2, hi2]
    n_b = len(a_vals)
    build = {"bk": with_nulls(a_vals, 0.15),
             "w": list(range(100, 100 + n_b)),
             "wd": [None if i % 5 == 0 else i / 4 for i in range(n_b)]}
    probe = {"pk": with_nulls(
        list(rng.integers(lo - 3, hi + 4, 44)) + [lo, hi, lo - 1, hi + 1],
        0.15,
    ), "v": [None if i % 7 == 0 else i for i in range(48)]}
    pschema = {"pk": kt, "v": T.BIGINT}
    bschema = {"bk": kt, "w": T.BIGINT, "wd": T.DOUBLE}
    pkeys, bkeys = ["pk"], ["bk"]
    if two:
        build["bk2"] = [int(x) for x in b_vals]
        probe["pk2"] = [int(x) for x in rng.integers(lo2 - 1, hi2 + 2, 48)]
        pschema["pk2"] = bschema["bk2"] = kt
        pkeys, bkeys = ["pk", "pk2"], ["bk", "bk2"]
    probe_page = Page.from_pydict(probe, pschema, capacity=64)
    build_page = Page.from_pydict(build, bschema, capacity=32)

    def dead_under_mask(page, every):
        keep = np.arange(page.capacity) < int(page.num_valid)
        keep &= np.arange(page.capacity) % every != 2
        return dataclasses.replace(
            page, live=jnp.asarray(keep),
            num_valid=jnp.asarray(int(keep.sum()), jnp.int32),
        )

    return (
        dead_under_mask(probe_page, 6), dead_under_mask(build_page, 7),
        pkeys, bkeys, LANE_RANGES.get(shape, ()),
    )


def _ranged_join(probe, build, pkeys, bkeys, join_type, unique, ranges):
    payload = [] if join_type in ("semi", "anti") else ["w", "wd"]
    with J.record_probe_lanes() as lanes:
        out, overflow = jax.jit(lambda p, b: hash_join(
            p, b, pkeys, bkeys, join_type=join_type, build_payload=payload,
            build_unique=unique, out_capacity=None if unique else 256,
            key_ranges=ranges,
        ))(probe, build)
    return out, overflow, lanes[0]


@pytest.mark.parametrize("shape", ["one key with a range", "two packed keys",
                                   "no range"])
@pytest.mark.parametrize("unique", [True, False], ids=["unique", "duplicate"])
@pytest.mark.parametrize(
    "join_type", ["inner", "left", "full", "semi", "anti"]
)
def test_one_lane_join_equals_two_lanes_and_the_reference(
    join_type, unique, shape
):
    """Keys with stated ranges ride one uint32 lane: every join type
    gives the rows of the two-lane path, in the same order, and of the
    nested-loop reference — NULL keys and dead rows on both sides, keys
    at both ends of the ranges, probe keys beyond them (which match
    nothing). Without a range the key keeps its two lanes."""
    probe, build, pkeys, bkeys, ranges = _ranged_pages(5, shape, unique)
    got, overflow, lanes = _ranged_join(
        probe, build, pkeys, bkeys, join_type, unique, ranges
    )
    was, was_overflow, was_lanes = _ranged_join(
        probe, build, pkeys, bkeys, join_type, unique, ()
    )
    assert (lanes, was_lanes) == ((1 if ranges else 2), 2)
    assert not bool(overflow) and not bool(was_overflow)
    assert got.names == was.names
    assert got.to_pylist() == was.to_pylist()
    cols = list(got.names)
    assert sorted(
        (tuple(r[c] for c in cols) for r in got.to_pylist()), key=repr
    ) == _reference(probe, build, pkeys, bkeys, join_type)


def test_a_build_key_outside_its_stated_range_reads_as_an_overflow():
    """A live build key beyond the stated range would match nothing: the
    join reports an overflow, whatever its type, so the host runs it
    again with nothing stated. NULL keys and dead rows beyond it are no
    one's business."""
    probe, build, pkeys, bkeys, _ = _ranged_pages(5, "one key with a range",
                                                  True)
    for join_type in ("inner", "left", "semi", "anti"):
        _, overflow, lanes = _ranged_join(
            probe, build, pkeys, bkeys, join_type, True, ((-3, 11),)
        )
        assert lanes == 1 and bool(overflow), join_type
    bk = build.block("bk")
    outside = (np.asarray(bk.data) == 12) & np.asarray(build.row_mask())
    valid = np.ones(build.capacity, bool) if bk.valid is None else np.asarray(
        bk.valid
    )
    nulled = dataclasses.replace(build, blocks=tuple(
        dataclasses.replace(b, valid=jnp.asarray(valid & ~outside))
        if n == "bk" else b
        for n, b in zip(build.names, build.blocks)
    ))
    _, overflow, _ = _ranged_join(
        probe, nulled, pkeys, bkeys, "inner", True, ((-3, 11),)
    )
    assert not bool(overflow)
