"""Each statement's numpy reference against the engine
(``LocalQueryRunner``, which tier-1 holds to sqlite) at ``tpch.tiny``,
for all parameter sets of seeds 1 and 2: the yardstick is checked once,
on the CPU."""

import glob
import os
import zlib

import numpy as np
import pytest

from benchmark import discovery
from benchmark.data import HostData

HERE = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
STATEMENTS = sorted(
    os.path.splitext(os.path.basename(p))[0]
    for p in glob.glob(os.path.join(HERE, "statements", "*.py"))
)


@pytest.fixture(scope="module")
def runner():
    import presto_tpu  # noqa: F401
    from presto_tpu.exec.local_runner import LocalQueryRunner

    return LocalQueryRunner()


@pytest.fixture(scope="module")
def data():
    from presto_tpu.connectors.tpch import TpchConnector

    return HostData(TpchConnector(), "tpch", "tiny")


@pytest.mark.parametrize("seed", [1, 2])
@pytest.mark.parametrize("name", STATEMENTS)
def test_reference_agrees_with_the_engine(name, seed, runner, data):
    mod = discovery.load_module(os.path.join(HERE, "statements", name + ".py"))
    rng = np.random.default_rng([seed, zlib.crc32(name.encode())])
    for sql in discovery.prepare_sql(mod, "tpch.tiny", "t"):
        runner.execute(sql)
    for _ in range(4):
        p = mod.params(rng, data)
        rows = runner.execute(mod.sql("tpch.tiny", p, "t")).rows()
        assert rows, f"{name} {p} returned nothing: the parameters select no row"
        want = mod.reference(data, p)
        assert mod.compare([tuple(r) for r in rows], want) is None, (name, p)


@pytest.mark.parametrize("name", STATEMENTS)
def test_compare_refuses_a_wrong_result(name, runner, data):
    mod = discovery.load_module(os.path.join(HERE, "statements", name + ".py"))
    rng = np.random.default_rng([7, zlib.crc32(name.encode())])
    p = mod.params(rng, data)
    for sql in discovery.prepare_sql(mod, "tpch.tiny", "t"):
        runner.execute(sql)
    rows = [tuple(r) for r in runner.execute(mod.sql("tpch.tiny", p, "t")).rows()]
    want = mod.reference(data, p)
    assert mod.compare(rows, want) is None
    assert mod.compare(rows[:-1], want) is not None  # a row short
    # the first numeric, non-key cell off by one unit of its last place
    r = list(rows[0])
    j = next(i for i in range(len(r) - 1, -1, -1) if isinstance(r[i], (int, float))
             and not isinstance(r[i], bool))
    r[j] = r[j] + 1
    assert mod.compare([tuple(r)] + rows[1:], want) is not None
