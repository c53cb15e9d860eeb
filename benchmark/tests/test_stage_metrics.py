"""Every cell of ``BENCHMARK.json`` resolves by name, with every metric
that lists it, and the two layer metrics of the staging cache's
residency by column read the counters they name — or are left out
where there is nothing to read."""

import dataclasses
import json
import os

import pytest

from benchmark import discovery, harness

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
CELL = "sf10_q1_resident"
NEW = ["stage_hit_share.pass", "stage_evictions_per_stmt.pass"]


def _bench():
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        return json.load(f)


def _file(*parts):
    with open(os.path.join(ROOT, *parts)) as f:
        return json.load(f)


@pytest.fixture(scope="module")
def cell():
    return discovery.load_cell(ROOT, CELL)


def _obs(**counters):
    return {"counters": counters, "stmts": 4}


@pytest.mark.parametrize("name", [w["name"] for w in _bench()["workloads"]])
def test_a_cell_its_configuration_traffic_loop_and_statements_load(name):
    """Every expectation comes from the cell's own entry and files, so a
    PR that adds a cell adds a case here and edits nothing."""
    bench = _bench()
    entry = next(w for w in bench["workloads"] if w["name"] == name)
    config = next(c for c in bench["configs"] if c["name"] == entry["config"])
    cell = discovery.load_cell(ROOT, name)
    assert (cell.config_name, cell.chips) == (entry["config"], entry["chips"])
    assert cell.config == _file(config["file"])
    assert config["reduced"] == cell.config["reduced"]
    assert set(cell.config.get("reduced_how", {})) == set(config["reduced"])
    assert cell.traffic_name == entry["traffic"]
    assert cell.traffic == _file(bench["paths"][0], "traffic", entry["traffic"] + ".json")
    # the loop and every statement of a pass are files that load
    statements = cell.traffic["statements"]
    assert list(cell.statement_paths) == statements and statements
    assert set(cell.statements()) == set(statements)
    assert all(hasattr(m, "sql") and hasattr(m, "params") for m in cell.statements().values())
    loop = cell.traffic["loop"]
    assert os.path.basename(cell.loop_path) == loop + ".py" and cell.loop().VARIANT
    # set-up warms at least one whole pass, and the pools hold a set or more
    assert cell.traffic.get("warm_passes", 1) >= 1 and cell.traffic["param_sets"] >= 1
    # the cell reports setup_s and another end-to-end metric
    e2e = [m["name"] for m in bench["end_to_end"]
           if "workloads" not in m or name in m["workloads"]]
    assert [m["name"] for m in cell.end_to_end] == e2e
    assert "setup_s" in e2e and len(e2e) >= 2
    # every metric that lists the cell resolved to a file and a reader, and no other did
    listed = [m for m in bench["per_layer"] if "workloads" not in m or name in m["workloads"]]
    assert [m.name for m in cell.per_layer] == [m["name"] for m in listed] and listed
    for got, m in zip(cell.per_layer, listed):
        assert got.spec["moves"][loop] == m["moves"] and m["moves"] in e2e
        assert got.reader_path or "read" in got.spec
    # the stage_* pair is found by name, and listed together or not at all
    assert [m["name"] for m in listed if m["name"] in NEW] in (NEW, [])


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
