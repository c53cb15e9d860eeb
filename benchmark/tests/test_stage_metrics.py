"""The cell ``sf10_q1_resident`` resolves by name, and the two layer
metrics of the staging cache's residency by column read the counters
they name — or are left out where there is nothing to read."""

import dataclasses
import json
import os

import pytest

from benchmark import discovery, harness

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
CELL = "sf10_q1_resident"
NEW = ["stage_hit_share.pass", "stage_evictions_per_stmt.pass"]


@pytest.fixture(scope="module")
def cell():
    return discovery.load_cell(ROOT, CELL)


def _obs(**counters):
    return {"counters": counters, "stmts": 4}


def test_the_cell_its_configuration_and_traffic_load(cell):
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        bench = json.load(f)
    entry = next(c for c in bench["configs"] if c["name"] == cell.config_name)
    assert cell.config_name == "tpch_sf10_1chip_resident" and cell.chips == 1
    assert (cell.config["catalog"], cell.config["schema"]) == ("tpch", "sf10")
    assert entry["reduced"] == cell.config["reduced"] == ["scale"]
    assert set(cell.config["reduced_how"]) == {"scale"}
    assert cell.traffic_name == "power_q1"
    assert cell.traffic == dict(cell.traffic, loop="closed_pass", clients=1,
                                statements=["q1"], param_sets=4, warm_passes=2)
    assert list(cell.statement_paths) == ["q1"]
    assert [m["name"] for m in cell.end_to_end] == ["setup_s", "pass_s.p50"]
    names = [m.name for m in cell.per_layer]
    assert names[-2:] == NEW and len(names) == 19
    # every metric of a whole pass lists the cell
    assert all(CELL in m["workloads"] for m in bench["per_layer"])


@pytest.mark.parametrize("counters,want", [
    ({"stage_col_hits": 434, "stage_col_misses": 0, "stage_evictions": 0},
     {NEW[0]: 100.0, NEW[1]: 0.0}),
    ({"stage_col_hits": 248, "stage_col_misses": 186, "stage_evictions": 8},
     {NEW[0]: 100.0 * 248 / 434, NEW[1]: 2.0}),
    # no batch looked a column up: the share is left out, not 0 or 100
    ({"stage_col_hits": 0, "stage_col_misses": 0, "stage_evictions": 0},
     {NEW[1]: 0.0}),
    # a program without the counters (the parent of this PR): both left out
    ({"h2d_bytes": 1}, {}),
], ids=["resident", "partial", "no-lookup", "no-counters"])
def test_the_stage_metrics_read_a_hand_made_observation(cell, counters, want):
    new = [m for m in cell.per_layer if m.name in NEW]
    one = dataclasses.replace(cell, per_layer=new)
    got = harness._layer_metrics(one, _obs(**counters))
    assert {k: v["value"] for k, v in got.items()} == pytest.approx(want)
    assert {v["unit"] for v in got.values()} <= {"%", "1/stmt"}
