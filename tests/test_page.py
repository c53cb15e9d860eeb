"""Unit tests for the columnar Block/Page core (SURVEY.md §7 step 1).

Modeled on the reference's per-class operator tests with hand-built Pages
(SURVEY.md §4.1).
"""

import datetime

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from presto_tpu import types as T
from presto_tpu.page import Block, Dictionary, Page, encode_strings, pad_capacity


def test_type_parse_roundtrip():
    assert T.parse_type("bigint") is T.BIGINT
    d = T.parse_type("decimal(12,2)")
    assert d.precision == 12 and d.scale == 2
    assert T.parse_type("varchar(25)").length == 25
    with pytest.raises(ValueError):
        T.parse_type("blob")


def test_common_super_type():
    assert T.common_super_type(T.INTEGER, T.BIGINT) is T.BIGINT
    assert T.common_super_type(T.BIGINT, T.DOUBLE) is T.DOUBLE
    d = T.common_super_type(T.decimal(12, 2), T.decimal(10, 4))
    assert d.scale == 4
    assert T.common_super_type(T.decimal(12, 2), T.INTEGER).is_decimal


def test_dictionary_order_preserving():
    ids, valid, d = encode_strings(["pear", "apple", None, "mango", "apple"])
    assert list(d.values) == ["apple", "mango", "pear"]
    assert list(ids) == [2, 0, -1, 1, 0]
    assert list(valid) == [True, True, False, True, True]
    # order preservation: id comparison == string comparison
    assert d.id_of("apple") < d.id_of("mango") < d.id_of("pear")
    assert d.id_of("absent") == -1
    assert d.searchsorted("b") == 1  # between apple and mango


def test_dictionary_hashable_and_lut():
    d1 = Dictionary.build(["a", "b", "c"])
    d2 = Dictionary.build(["c", "b", "a", "a"])
    assert d1 == d2 and hash(d1) == hash(d2)
    lut = d1.predicate_lut(lambda s: s >= "b")
    assert list(lut) == [False, True, True]


def test_page_from_pydict_roundtrip():
    schema = {
        "k": T.BIGINT,
        "price": T.decimal(12, 2),
        "name": T.VARCHAR,
        "d": T.DATE,
        "x": T.DOUBLE,
    }
    day = (datetime.date(1995, 3, 15) - datetime.date(1970, 1, 1)).days
    page = Page.from_pydict(
        {
            "k": [1, 2, None],
            "price": [10.25, 99.99, 0.01],
            "name": ["alice", None, "bob"],
            "d": [day, day + 1, day + 2],
            "x": [1.5, 2.5, 3.5],
        },
        schema,
        capacity=8,
    )
    assert page.capacity == 8
    assert int(page.num_valid) == 3
    rows = page.to_pylist()
    assert rows[0]["k"] == 1 and rows[2]["k"] is None
    assert rows[0]["price"] == 10.25 and rows[1]["price"] == 99.99
    assert rows[0]["name"] == "alice" and rows[1]["name"] is None
    assert rows[0]["d"] == datetime.date(1995, 3, 15)
    # decimal exactness: stored as scaled int64
    assert np.asarray(page.block("price").data)[:3].tolist() == [1025, 9999, 1]


def test_page_is_pytree():
    page = Page.from_pydict({"a": [1, 2, 3]}, {"a": T.BIGINT}, capacity=4)
    leaves = jax.tree_util.tree_leaves(page)
    # data + num_valid (no null masks here)
    assert len(leaves) == 2

    @jax.jit
    def double(p: Page) -> Page:
        blk = p.blocks[0]
        import dataclasses

        return dataclasses.replace(
            p, blocks=(dataclasses.replace(blk, data=blk.data * 2),)
        )

    out = double(page)
    assert [r["a"] for r in out.to_pylist()] == [2, 4, 6]


def test_row_mask_and_pad_capacity():
    page = Page.from_pydict({"a": [1, 2, 3]}, {"a": T.BIGINT}, capacity=4)
    assert list(np.asarray(page.row_mask())) == [True, True, True, False]
    bigger = pad_capacity(page, 16)
    assert bigger.capacity == 16 and int(bigger.num_valid) == 3
    smaller = pad_capacity(bigger, 4)
    assert smaller.capacity == 4
    assert [r["a"] for r in smaller.to_pylist()] == [1, 2, 3]


def test_block_null_mask_static_none():
    b = Block.from_pylist([1, 2, 3], T.BIGINT)
    assert b.valid is None  # null-free => no mask materialised
    b2 = Block.from_pylist([1, None, 3], T.BIGINT)
    assert b2.valid is not None
    assert list(np.asarray(b2.valid)) == [True, False, True]


@pytest.mark.parametrize("k", [8, 9, 1 << 20])
def test_prefix_leaves_returns_a_covered_leaf_unsliced(k):
    """A prefix that covers the page cuts nothing: the leaves are the
    page's own arrays, not slices of them (a slice of a device array
    is Python work even when it is the whole array)."""
    page = Page.from_pydict(
        {"k": [1, 2, None], "name": ["a", None, "b"]},
        {"k": T.BIGINT, "name": T.VARCHAR},
        capacity=8,
    )
    own = []
    for b in page.blocks:
        own.append(b.data)
        if b.valid is not None:
            own.append(b.valid)
    leaves = page.prefix_leaves(k)
    assert len(leaves) == len(own)
    assert all(a is b for a, b in zip(leaves, own))
    # a shorter prefix still cuts
    assert [x.shape[0] for x in page.prefix_leaves(3)] == [3] * len(own)
