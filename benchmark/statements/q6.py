"""TPC-H Q6 (forecasting revenue change): one filtered sum over
``lineitem``. Parameters as the specification's qgen draws them."""

from benchmark.data import day, same_sum

TABLES = {"lineitem": ("l_extendedprice", "l_discount", "l_quantity", "l_shipdate")}

SQL = """
select sum(l_extendedprice * l_discount) as revenue
from {s}.lineitem
where l_shipdate >= date '{year}-01-01' and l_shipdate < date '{next_year}-01-01'
  and l_discount between {lo} and {hi} and l_quantity < {quantity}
"""


def params(rng, data) -> dict:
    return {
        "year": int(rng.integers(1993, 1998)),  # 1993..1997
        "discount": int(rng.integers(2, 10)),  # 0.02..0.09
        "quantity": int(rng.integers(24, 26)),  # 24 or 25
    }


def sql(schema: str, p: dict, tag: str) -> str:
    return SQL.format(
        s=schema, year=p["year"], next_year=p["year"] + 1,
        lo=f"0.{p['discount'] - 1:02d}", hi=f"0.{p['discount'] + 1:02d}",
        quantity=p["quantity"],
    )


def reference(data, p: dict) -> int:
    """The unscaled int64 sum at scale 4."""
    cols, _ = data.columns("lineitem", TABLES["lineitem"])
    keep = (
        (cols["l_shipdate"] >= day(p["year"], 1, 1))
        & (cols["l_shipdate"] < day(p["year"] + 1, 1, 1))
        & (cols["l_discount"] >= p["discount"] - 1)
        & (cols["l_discount"] <= p["discount"] + 1)
        & (cols["l_quantity"] < p["quantity"] * 100)
    )
    return int((cols["l_extendedprice"][keep] * cols["l_discount"][keep]).sum())


def compare(rows, want: int):
    if len(rows) != 1 or not same_sum(rows[0][0], want, 4):
        return f"q6 revenue {rows!r} != numpy {want} (scale 4)"
    return None
