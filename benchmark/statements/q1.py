"""TPC-H Q1 (pricing summary report): scan + filter + a four-group
aggregate over ``lineitem``. DELTA as the specification's qgen draws it."""

import numpy as np

from benchmark.data import close, day, same_sum

TABLES = {"lineitem": ("l_returnflag", "l_linestatus", "l_quantity", "l_extendedprice",
                       "l_discount", "l_tax", "l_shipdate")}

SQL = """
select l_returnflag, l_linestatus, sum(l_quantity) as sum_qty,
  sum(l_extendedprice) as sum_base_price,
  sum(l_extendedprice * (1 - l_discount)) as sum_disc_price,
  sum(l_extendedprice * (1 - l_discount) * (1 + l_tax)) as sum_charge,
  avg(l_quantity) as avg_qty, avg(l_extendedprice) as avg_price,
  avg(l_discount) as avg_disc, count(*) as count_order
from {s}.lineitem
where l_shipdate <= date '1998-12-01' - interval '{delta}' day
group by l_returnflag, l_linestatus
order by l_returnflag, l_linestatus
"""


def params(rng, data) -> dict:
    return {"delta": int(rng.integers(60, 121))}  # 60..120 days


def sql(schema: str, p: dict, tag: str) -> str:
    return SQL.format(s=schema, delta=p["delta"])


def reference(data, p: dict) -> dict:
    """Per (returnflag, linestatus): the unscaled int64 sums and the count."""
    cols, dicts = data.columns("lineitem", TABLES["lineitem"])
    keep = cols["l_shipdate"] <= day(1998, 12, 1) - p["delta"]
    qty, price = cols["l_quantity"], cols["l_extendedprice"]
    disc, tax = cols["l_discount"], cols["l_tax"]
    disc_price = price * (100 - disc)
    charge = disc_price * (100 + tax)
    n_ls = len(dicts["l_linestatus"])
    gid = cols["l_returnflag"] * np.int32(n_ls) + cols["l_linestatus"]
    out = {}
    for g in range(len(dicts["l_returnflag"]) * n_ls):
        m = keep & (gid == g)
        count = int(np.count_nonzero(m))
        if not count:
            continue
        key = (str(dicts["l_returnflag"][g // n_ls]), str(dicts["l_linestatus"][g % n_ls]))
        out[key] = {"count": count}
        for name, values in (
            ("sum_qty", qty), ("sum_base_price", price), ("sum_disc_price", disc_price),
            ("sum_charge", charge), ("sum_disc", disc),
        ):
            out[key][name] = int(values[m].sum())
    return out


def compare(rows, want: dict):
    if [(r[0], r[1]) for r in rows] != sorted(want):
        return f"q1 groups {[r[:2] for r in rows]} != numpy {sorted(want)}, in order"
    for r in rows:
        w = want[(r[0], r[1])]
        for name, got, scale in (
            ("sum_qty", r[2], 2), ("sum_base_price", r[3], 2),
            ("sum_disc_price", r[4], 4), ("sum_charge", r[5], 6),
        ):
            if not same_sum(got, w[name], scale):
                return f"q1 {r[:2]} {name}: {got!r} != {w[name]} e-{scale}"
        if int(r[9]) != w["count"]:
            return f"q1 {r[:2]} count: {r[9]!r} != {w['count']}"
        for name, got, num in (
            ("avg_qty", r[6], w["sum_qty"]), ("avg_price", r[7], w["sum_base_price"]),
            ("avg_disc", r[8], w["sum_disc"]),
        ):
            ref = num / 100 / w["count"]
            if got is None or not close(float(got), ref):
                return f"q1 {r[:2]} {name}: {got!r} != {ref!r}"
    return None
