"""TPC-H Q18 (large volume customer): the customers who placed an order
of more than QUANTITY units, with the order, its date, its total price
and its quantity, the 100 largest orders by total price. The IN
subquery aggregates all of ``lineitem`` by order key and keeps the few
orders above QUANTITY; the outer query joins ``lineitem``, ``orders``
and ``customer`` and groups by five keys. QUANTITY is an integer of
312..315, as qgen draws it.

At ``tpch.tiny`` (a hundredth of SF1) no order holds more than 293
units, so the spec's range would keep no order there: the rehearsal
schema draws QUANTITY from 200..250 instead (72 to 857 orders).

No float is in the statement: the total price and the quantities are
scaled-int64 decimals, so the comparison below has no tolerance."""

import numpy as np

from benchmark.data import iso, same_sum

TABLES = {
    "lineitem": ("l_orderkey", "l_quantity"),
    "orders": ("o_orderkey", "o_custkey", "o_orderdate", "o_totalprice"),
    "customer": ("c_custkey", "c_name"),
}
LIMIT = 100
QUANTITY = (312, 315)  # qgen's range, both ends included
REHEARSAL = {"tiny": (200, 250)}

SQL = """
select c_name, c_custkey, o_orderkey, o_orderdate, o_totalprice,
  sum(l_quantity)
from {s}.customer, {s}.orders, {s}.lineitem
where o_orderkey in (
    select l_orderkey from {s}.lineitem
    group by l_orderkey having sum(l_quantity) > {quantity})
  and c_custkey = o_custkey and o_orderkey = l_orderkey
group by c_name, c_custkey, o_orderkey, o_orderdate, o_totalprice
order by o_totalprice desc, o_orderdate
limit 100
"""


def _require_program() -> None:
    """Q18 at one chip's share needs a program that merges a stage's
    partial groups on the device (the counter ``agg_merge_slots``). One
    without it computes the IN subquery once a task over ``lineitem``
    staged whole, and on the chip crashed (signal 11) 264 s into its
    set-up, before its first window (PERF.md section 6). Refuse at once
    instead."""
    from presto_tpu.utils.telemetry import device_snapshot

    if "agg_merge_slots" not in device_snapshot():
        raise RuntimeError(
            "q18 needs a program with the counter agg_merge_slots: this one "
            "has none, and at SF10 crashed (signal 11) 264 s into its set-up "
            "(PERF.md section 6)"
        )


def params(rng, data) -> dict:
    _require_program()
    lo, hi = REHEARSAL.get(data.schema, QUANTITY)
    return {"quantity": int(rng.integers(lo, hi + 1))}


def sql(schema: str, p: dict, tag: str) -> str:
    return SQL.format(s=schema, quantity=p["quantity"])


def reference(data, p: dict) -> list:
    """The specification's text over the host columns: every order's
    quantity summed by order key, the orders above QUANTITY, each
    joined to its customer by key index, ordered by total price
    descending and date; the first 100 rows and whatever ties with the
    hundredth on (total price, date). Rows are ``(c_name, c_custkey,
    o_orderkey, o_orderdate, o_totalprice e-2, quantity e-2)``."""
    li, _ = data.columns("lineitem", TABLES["lineitem"])
    orders, _ = data.columns("orders", TABLES["orders"])
    cust, cdict = data.columns("customer", TABLES["customer"])
    keys = li["l_orderkey"].astype(np.int64)
    qty = li["l_quantity"].astype(np.int64)  # e-2
    # an int64 sum by key: the float64 bincount is exact while every
    # partial sum stays below 2**53, which 7 items of at most 5,000 do
    assert int(qty.max()) * 7 < 2 ** 53
    total = np.bincount(keys, weights=qty).astype(np.int64)
    big = np.nonzero(total > p["quantity"] * 100)[0]
    order_at = np.full(int(orders["o_orderkey"].max()) + 1, -1, dtype=np.int64)
    order_at[orders["o_orderkey"]] = np.arange(len(orders["o_orderkey"]))
    big = big[(big < len(order_at))]
    at = order_at[big]
    big, at = big[at >= 0], at[at >= 0]
    cust_at = np.full(int(cust["c_custkey"].max()) + 1, -1, dtype=np.int64)
    cust_at[cust["c_custkey"]] = np.arange(len(cust["c_custkey"]))
    ckey = orders["o_custkey"][at].astype(np.int64)
    crow = cust_at[ckey]
    price = orders["o_totalprice"][at].astype(np.int64)
    odate = orders["o_orderdate"][at]
    order = np.lexsort((odate, -price))
    out = []
    for i in order:
        if len(out) >= LIMIT and (price[i], odate[i]) != out[LIMIT - 1][6]:
            break
        name = str(cdict["c_name"][cust["c_name"][crow[i]]])
        out.append((name, int(ckey[i]), int(big[i]), iso(odate[i]),
                    int(price[i]), int(total[big[i]]), (price[i], odate[i])))
    return [w[:6] for w in out]


def compare(rows, want: list):
    n = min(LIMIT, len(want))
    if len(rows) != n:
        return f"q18 returned {len(rows)} rows, reference has {n}"
    by_key = {w[2]: w for w in want}
    for i, r in enumerate(rows):
        w = by_key.get(int(r[2]))
        if w is None:
            return f"q18 row {i}: order {r[2]!r} is not among the reference's rows"
        if (str(r[0]), int(r[1]), str(r[3])) != (w[0], w[1], w[3]):
            return f"q18 row {i}: {r!r} != reference {w!r}"
        if not same_sum(r[4], w[4], 2) or not same_sum(r[5], w[5], 2):
            return f"q18 row {i}: {r!r} != reference {w!r} (price, quantity e-2)"
        # rows tied on (total price, date) may come in either order, and
        # a tie across the hundredth row is compared as a set
        if (w[4], w[3]) != (want[i][4], want[i][3]):
            return f"q18 row {i}: {r!r} is out of order, reference has {want[i]!r} there"
    if len({int(r[2]) for r in rows}) != len(rows):
        return "q18 returned an order twice"
    return None
