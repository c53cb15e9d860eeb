"""TPC-H Q3 (shipping priority): customer |x| orders |x| lineitem, an
aggregate over ~1 group per qualifying order, top 10 by revenue. DATE is
a day of March 1995 as qgen draws it; SEGMENT is held at the
specification's validation value (the configuration lists it under
``assumed``: every new segment compiles two of Q3's three programs again)."""

import numpy as np

from benchmark.data import day, iso, same_sum

TABLES = {
    "customer": ("c_custkey", "c_mktsegment"),
    "orders": ("o_orderkey", "o_custkey", "o_orderdate", "o_shippriority"),
    "lineitem": ("l_orderkey", "l_extendedprice", "l_discount", "l_shipdate"),
}
SEGMENT = "BUILDING"

SQL = """
select l_orderkey, sum(l_extendedprice * (1 - l_discount)) as revenue,
  o_orderdate, o_shippriority
from {s}.customer, {s}.orders, {s}.lineitem
where c_mktsegment = '{segment}' and c_custkey = o_custkey
  and l_orderkey = o_orderkey and o_orderdate < date '{date}'
  and l_shipdate > date '{date}'
group by l_orderkey, o_orderdate, o_shippriority
order by revenue desc, o_orderdate
limit 10
"""


def params(rng, data) -> dict:
    return {"day": int(rng.integers(1, 32))}  # 1995-03-01 .. 1995-03-31


def sql(schema: str, p: dict, tag: str) -> str:
    return SQL.format(s=schema, segment=SEGMENT, date=f"1995-03-{p['day']:02d}")


def reference(data, p: dict) -> list:
    """Every qualifying group as ``(orderkey, revenue e-4, orderdate,
    shippriority)``, ordered by revenue desc, orderdate: the top 10 and
    whatever ties with the tenth."""
    cust, cdict = data.columns("customer", TABLES["customer"])
    orders, _ = data.columns("orders", TABLES["orders"])
    li, _ = data.columns("lineitem", TABLES["lineitem"])
    date = day(1995, 3, p["day"])
    seg = int(np.nonzero(cdict["c_mktsegment"] == SEGMENT)[0][0])
    cust_ok = np.zeros(int(cust["c_custkey"].max()) + 1, dtype=bool)
    cust_ok[cust["c_custkey"][cust["c_mktsegment"] == seg]] = True
    okeep = cust_ok[orders["o_custkey"]] & (orders["o_orderdate"] < date)
    n_keys = int(orders["o_orderkey"].max()) + 1
    order_at = np.full(n_keys, -1, dtype=np.int64)  # orderkey -> row of orders
    order_at[orders["o_orderkey"][okeep]] = np.nonzero(okeep)[0]
    lkeep = (order_at[li["l_orderkey"]] >= 0) & (li["l_shipdate"] > date)
    revenue = np.zeros(n_keys, dtype=np.int64)
    np.add.at(revenue, li["l_orderkey"][lkeep],
              li["l_extendedprice"][lkeep] * (100 - li["l_discount"][lkeep]))
    keys = np.unique(li["l_orderkey"][lkeep])
    rev, at = revenue[keys], order_at[keys]
    odate, prio = orders["o_orderdate"][at], orders["o_shippriority"][at]
    order = np.lexsort((odate, -rev))
    last = order[min(10, len(order)) - 1] if len(order) else None
    out = []
    for i in order:
        if len(out) >= 10 and (rev[i], odate[i]) != (rev[last], odate[last]):
            break
        out.append((int(keys[i]), int(rev[i]), iso(odate[i]), int(prio[i])))
    return out


def compare(rows, want: list):
    n = min(10, len(want))
    if len(rows) != n:
        return f"q3 returned {len(rows)} rows, reference has {n}"
    by_key = {w[0]: w for w in want}
    for i, r in enumerate(rows):
        w = by_key.get(int(r[0]))
        if w is None:
            return f"q3 row {i}: order {r[0]!r} is not among the reference's top rows"
        if not same_sum(r[1], w[1], 4) or str(r[2]) != w[2] or int(r[3]) != w[3]:
            return f"q3 row {i}: {r!r} != reference {w!r} (revenue e-4)"
        # position: equal (revenue, date) with the reference's i-th row
        if (w[1], w[2]) != (want[i][1], want[i][2]):
            return f"q3 row {i}: {r!r} is out of order, reference has {want[i]!r} there"
    if len({int(r[0]) for r in rows}) != len(rows):
        return "q3 returned an order twice"
    return None
