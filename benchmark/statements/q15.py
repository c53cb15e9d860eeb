"""TPC-H Q15 (top supplier): the revenue a supplier shipped in one
quarter — a group-by over ``lineitem`` with one group a supplier —, its
maximum as a scalar subquery, and the suppliers at that maximum joined
to ``supplier``. The view ``revenue0`` is written as a common table
expression (the specification's approved variant, Appendix B). DATE is
the first day of a month from 1993-01 to 1997-10, as qgen draws it; the
interval is the specification's three months.

No float is in the statement: the revenue is a scaled-int64 sum, so the
comparison below has no tolerance anywhere."""

import numpy as np

from benchmark.data import day, same_sum

TABLES = {
    "lineitem": ("l_suppkey", "l_extendedprice", "l_discount", "l_shipdate"),
    "supplier": ("s_suppkey", "s_name", "s_address", "s_phone"),
}

SQL = """
with revenue0 as (
  select l_suppkey as supplier_no,
    sum(l_extendedprice * (1 - l_discount)) as total_revenue
  from {s}.lineitem
  where l_shipdate >= date '{date}'
    and l_shipdate < date '{date}' + interval '3' month
  group by l_suppkey
)
select s_suppkey, s_name, s_address, s_phone, total_revenue
from {s}.supplier, revenue0
where s_suppkey = supplier_no
  and total_revenue = (select max(total_revenue) from revenue0)
order by s_suppkey
"""

MONTHS = 58  # 1993-01 .. 1997-10


def _require_program() -> None:
    """Q15 at one chip's share needs the program of PR 35 or later. One
    without the counter ``agg_partial_rows`` sizes the worker's partial
    pages by the planner's bucket (2^24 slots a 2^20-row batch): 101 s a
    statement after 814 s of set-up on the chip (PERF.md section 6, PR
    33), more than a run is given. Refuse at once instead."""
    from presto_tpu.utils.telemetry import device_snapshot

    if "agg_partial_rows" not in device_snapshot():
        raise RuntimeError(
            "q15 needs the program of PR 35 or later: this one has no counter "
            "agg_partial_rows, and at SF10 takes 101 s a statement after 814 s "
            "of set-up (PERF.md section 6, PR 33), more than a run is given"
        )


def params(rng, data) -> dict:
    _require_program()
    m = int(rng.integers(0, MONTHS))
    return {"year": 1993 + m // 12, "month": 1 + m % 12}


def sql(schema: str, p: dict, tag: str) -> str:
    return SQL.format(s=schema, date=f"{p['year']:04d}-{p['month']:02d}-01")


def reference(data, p: dict) -> list:
    """Every supplier at the quarter's maximum revenue, ordered by key:
    ``(suppkey, name, address, phone, revenue e-4)``."""
    li, _ = data.columns("lineitem", TABLES["lineitem"])
    sup, sdict = data.columns("supplier", TABLES["supplier"])
    end = p["year"] * 12 + p["month"] - 1 + 3
    lo, hi = day(p["year"], p["month"], 1), day(end // 12, 1 + end % 12, 1)
    keep = (li["l_shipdate"] >= lo) & (li["l_shipdate"] < hi)
    key = li["l_suppkey"][keep]
    if not len(key):
        return []
    revenue = np.zeros(int(sup["s_suppkey"].max()) + 1, dtype=np.int64)
    np.add.at(revenue, key,
              li["l_extendedprice"][keep] * (100 - li["l_discount"][keep]))
    shipped = np.unique(key)  # a supplier with no row is not in the view
    top = int(revenue[shipped].max())
    out = []
    for k in shipped[revenue[shipped] == top]:
        i = int(np.nonzero(sup["s_suppkey"] == k)[0][0])
        out.append((int(k),) + tuple(
            str(sdict[c][sup[c][i]]) for c in ("s_name", "s_address", "s_phone")
        ) + (top,))
    return out


def compare(rows, want: list):
    if len(rows) != len(want):
        return f"q15 returned {len(rows)} rows, reference has {len(want)}"
    for i, (r, w) in enumerate(zip(rows, want)):
        if int(r[0]) != w[0] or tuple(str(x) for x in r[1:4]) != w[1:4]:
            return f"q15 row {i}: {r!r} != reference {w!r}, in order"
        if not same_sum(r[4], w[4], 4):
            return f"q15 row {i}: total_revenue {r[4]!r} != {w[4]} e-4"
    return None
