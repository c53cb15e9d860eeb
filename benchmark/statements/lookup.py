"""A served point lookup: one customer by key through a prepared
statement (``PREPARE`` once a client session, ``EXECUTE ... USING``).
Keys are Zipfian over all ``c_custkey`` values, theta 0.99: YCSB
workload C's read-only shape and default skew."""

import numpy as np

from benchmark.data import same_sum

TABLES = {"customer": ("c_custkey", "c_name", "c_acctbal", "c_mktsegment", "c_nationkey")}
THETA = 0.99

SELECT = (
    "select c_custkey, c_name, c_acctbal, c_mktsegment, c_nationkey "
    "from {s}.customer where c_custkey = ?"
)


def _zipf_rank(rng, n: int) -> int:
    """One draw of a rank in ``[0, n)`` with P(r) ~ 1/(r+1)**theta, by
    inverting the continuous approximation of the cumulative weight."""
    u = rng.random()
    a = 1.0 - THETA
    return min(int((((n + 1) ** a - 1.0) * u + 1.0) ** (1.0 / a)) - 1, n - 1)


def params(rng, data) -> dict:
    n = data.rows("customer")
    rank = _zipf_rank(rng, n)
    # ranks are scattered over the key space, as YCSB hashes them
    return {"key": 1 + (rank * 2654435761) % n}


def prepare(schema: str, tag: str):
    return [f"prepare bench_lookup_{tag} from " + SELECT.format(s=schema)]


def sql(schema: str, p: dict, tag: str) -> str:
    return f"execute bench_lookup_{tag} using {p['key']}"


def reference(data, p: dict) -> tuple:
    cols, dicts = data.columns("customer", TABLES["customer"])
    i = int(np.searchsorted(cols["c_custkey"], p["key"]))
    if cols["c_custkey"][i] != p["key"]:
        raise AssertionError(f"customer {p['key']} does not exist")
    return (
        p["key"], str(dicts["c_name"][cols["c_name"][i]]), int(cols["c_acctbal"][i]),
        str(dicts["c_mktsegment"][cols["c_mktsegment"][i]]), int(cols["c_nationkey"][i]),
    )


def compare(rows, want: tuple):
    if len(rows) != 1:
        return f"lookup of {want[0]} returned {len(rows)} rows"
    r = rows[0]
    if (int(r[0]), r[1], r[3], int(r[4])) != (want[0], want[1], want[3], want[4]) \
            or not same_sum(r[2], want[2], 2):
        return f"lookup {r!r} != reference {want!r} (acctbal e-2)"
    return None
