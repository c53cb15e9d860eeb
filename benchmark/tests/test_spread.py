"""The bound of ``pass_s.p50`` stands on the runs it was set from:
``data/spreads.json`` holds the builder's sets (two a cell, six runs
each, one process a run on the chip), ``benchmark/spread.py`` the
driver's rules. A ``benchmark`` PR that measures anew replaces the
sets, and this file then says whether the bound still holds."""

import json
import os
import statistics

import pytest

from benchmark import spread

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(os.path.dirname(HERE))
CANDIDATES = (0.03, 0.04, 0.05, 0.06, 0.08, 0.10)  # ISSUE 34; 0.10 is the ceiling
METRIC = "pass_s.p50"


def _load():
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        bench = json.load(f)
    with open(os.path.join(HERE, "data", "spreads.json")) as f:
        sets = json.load(f)["sets"]
    return bench, sets


def _metric(bench, name):
    return next(m for m in bench["end_to_end"] if m["name"] == name)


def _values(sets, cell, metric):
    return [[r[metric] for r in s["runs"]] for s in sets if s["cell"] == cell]


def _cells():
    bench, _ = _load()
    return _metric(bench, METRIC)["workloads"]


def _recorded(sets, cell):
    """The cell's sets; a cell that a later PR added has none recorded
    here and is the driver's to judge, which measures new cells itself."""
    mine = [s for s in sets if s["cell"] == cell]
    if not mine:
        pytest.skip(f"no sets of '{cell}' in data/spreads.json: the driver judges a new cell")
    return mine


@pytest.mark.parametrize("values,rest", [
    ([1.00, 1.01, 1.02, 1.03, 1.04, 1.50], [1.00, 1.01, 1.02, 1.03, 1.04]),  # one far above
    ([0.50, 1.01, 1.02, 1.03, 1.04, 1.05], [1.01, 1.02, 1.03, 1.04, 1.05]),  # one far below
    ([1.0, 1.0, 1.0, 1.0], [1.0, 1.0, 1.0, 1.0]),  # leaving one out narrows nothing
    ([2.0, 1.0], [1.0, 2.0]),  # under three runs there is no farthest
], ids=["above", "below", "flat", "two"])
def test_a_spread_leaves_out_the_farthest_run_where_that_narrows_it(values, rest):
    assert spread.without_farthest(values) == rest
    assert spread.spread(values) == pytest.approx(rest[-1] - rest[0])
    assert spread.spread_share(values) == pytest.approx(
        (rest[-1] - rest[0]) / statistics.median(values))


def test_the_quartile_distance_is_the_contracts():
    v = [6.45, 6.40, 6.52, 6.47, 6.44, 6.61]
    q1, _, q3 = statistics.quantiles(v, n=4)
    assert spread.iqr(v) == pytest.approx(q3 - q1)
    assert spread.iqr_share(v) == pytest.approx((q3 - q1) / statistics.median(v))
    # the range is never the narrower reading of the same runs
    rest = spread.without_farthest(v)
    assert rest[-1] - rest[0] >= spread.iqr(rest)


def test_the_smallest_bound_is_the_first_candidate_that_holds_every_cell():
    steady = [[1.000, 1.002, 1.004, 1.006, 1.008, 1.010]] * 2  # 0.8 % (one left out)
    noisy = [[1.00, 1.01, 1.02, 1.03, 1.04, 1.05]] * 2  # 3.9 %
    assert spread.smallest_bound({"a": steady}, CANDIDATES) == 0.03
    assert spread.smallest_bound({"a": steady, "b": noisy}, CANDIDATES) == 0.10
    with pytest.raises(ValueError):
        spread.smallest_bound({"b": [[1.0, 1.1, 1.2, 1.3]] * 2}, CANDIDATES)


def test_the_bound_is_a_listed_one_and_the_other_limits_are_unmoved():
    bench, _ = _load()
    assert _metric(bench, METRIC)["bound"] in CANDIDATES
    assert _metric(bench, "setup_s")["bound"] == 0.25
    assert bench["run_seconds"] == 51


@pytest.mark.parametrize("cell", _cells())
def test_every_listed_cell_has_two_sets_of_sound_runs(cell):
    _, sets = _load()
    mine = _recorded(sets, cell)
    assert len(mine) == 2 and {s["set"] for s in mine} == {"A", "B"}
    for s in mine:
        assert s["machine"] and s["date"] and s["seconds"] == 51
        assert len(s["runs"]) >= 6
        assert len({r["seed"] for r in s["runs"]}) == len(s["runs"])
        for r in s["runs"]:
            assert r["correct"] is True and r["failed"] == 0
            assert r["window_compiles"] == 0 and r["xla_compiles"] == 0
            assert r["passes"] >= 3 and r["pass_min_s"] <= r[METRIC] <= r["pass_max_s"]


@pytest.mark.parametrize("cell", _cells())
def test_the_bound_is_at_least_two_and_a_half_mean_spreads_in_every_listed_cell(cell):
    bench, sets = _load()
    bound = _metric(bench, METRIC)["bound"]
    _recorded(sets, cell)
    got = spread.cell_summary(_values(sets, cell, METRIC))
    assert got["mean_spread_share"] <= spread.MARGIN * bound, (cell, got)


def test_the_bound_is_the_smallest_that_holds_and_not_too_loose():
    bench, sets = _load()
    bound = _metric(bench, METRIC)["bound"]
    cells = {c: _values(sets, c, METRIC) for c in _cells() if _values(sets, c, METRIC)}
    assert bound == spread.smallest_bound(cells, CANDIDATES)
    # the driver refuses a bound over eight times the widest spread it reads
    widest = max(spread.iqr_share([x for s in v for x in s]) for v in cells.values())
    assert bound <= spread.LOOSE * widest, widest


@pytest.mark.parametrize("cell", _cells())
def test_set_up_is_steady_between_the_sets(cell):
    bench, sets = _load()
    _recorded(sets, cell)
    first, second = (statistics.median(v) for v in _values(sets, cell, "setup_s"))
    assert second <= first * (1.0 + _metric(bench, "setup_s")["bound"]), (first, second)
