"""A later PR adds a cell with files and ``BENCHMARK.json`` entries
only: everything is found by name, nothing that exists is edited, and
a name with no file fails before JAX is imported."""

import json
import os
import shutil
import subprocess
import sys

import pytest

from benchmark import discovery

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

STATEMENT = '''
TABLES = {"nation": ("n_nationkey",)}

def params(rng, data):
    return {"below": int(rng.integers(5, 20))}

def sql(schema, p, tag):
    return f"select count(*) from {schema}.nation where n_nationkey < {p['below']}"

def reference(data, p):
    cols, _ = data.columns("nation", TABLES["nation"])
    return int((cols["n_nationkey"] < p["below"]).sum())

def compare(rows, want):
    return None if [tuple(r) for r in rows] == [(want,)] else f"{rows!r} != {want}"
'''


@pytest.fixture()
def copy(tmp_path):
    """A copy of BENCHMARK.json and benchmark/, with a new cell dropped in."""
    root = str(tmp_path)
    shutil.copy(os.path.join(ROOT, "BENCHMARK.json"), root)
    shutil.copytree(os.path.join(ROOT, "benchmark"), os.path.join(root, "benchmark"),
                    ignore=shutil.ignore_patterns("__pycache__", "tests"))
    before = {}
    for base, _, files in os.walk(os.path.join(root, "benchmark")):
        for f in files:
            with open(os.path.join(base, f), "rb") as fh:
                before[os.path.join(base, f)] = fh.read()
    b = os.path.join(root, "benchmark")
    with open(os.path.join(b, "configs", "tpch_new.json"), "w") as f:
        json.dump({"source": "test", "catalog": "tpch", "schema": "tiny",
                   "rehearsal_schema": "tiny", "chips": 1}, f)
    with open(os.path.join(b, "traffic", "count_nations.json"), "w") as f:
        json.dump({"loop": "closed_pass", "clients": 1, "statements": ["nations"],
                   "param_sets": 2, "warm_passes": 1, "who": "test"}, f)
    with open(os.path.join(b, "statements", "nations.py"), "w") as f:
        f.write(STATEMENT)
    with open(os.path.join(b, "layer_metrics", "live_rows_per_stmt.json"), "w") as f:
        json.dump({"layer": "staging", "unit": "rows/stmt", "better": "lower",
                   "source": "program_counter",
                   "read": {"from": "counters", "field": "live_rows", "per": "stmt"},
                   "moves": {"closed_pass": "pass_s.p50"}}, f)
    with open(os.path.join(root, "BENCHMARK.json")) as f:
        bench = json.load(f)
    bench["configs"].append({"name": "tpch_new", "source": "test",
                             "file": "benchmark/configs/tpch_new.json",
                             "reduced": [], "why": "test"})
    bench["workloads"].append({"name": "new_cell", "config": "tpch_new",
                               "traffic": "count_nations", "chips": 1, "why": "test"})
    for m in bench["end_to_end"]:
        if m["name"] == "pass_s.p50":
            m["workloads"].append("new_cell")
    bench["per_layer"].append({"name": "live_rows_per_stmt.pass", "unit": "rows/stmt",
                               "better": "lower", "source": "program_counter",
                               "layer": "staging", "moves": "pass_s.p50",
                               "workloads": ["new_cell"]})
    with open(os.path.join(root, "BENCHMARK.json"), "w") as f:
        json.dump(bench, f)
    return root, before


def test_a_dropped_in_cell_is_found_by_name(copy):
    root, before = copy
    cell = discovery.load_cell(root, "new_cell")
    assert cell.config["schema"] == "tiny" and cell.traffic["statements"] == ["nations"]
    assert list(cell.statement_paths) == ["nations"]
    assert [m.name for m in cell.per_layer] == ["live_rows_per_stmt.pass"]
    assert [m["name"] for m in cell.end_to_end] == ["setup_s", "pass_s.p50"]
    for path, content in before.items():  # no file that existed was edited
        with open(path, "rb") as fh:
            assert fh.read() == content, path


@pytest.mark.parametrize("trace", [0, 1])
def test_the_dropped_in_cell_runs(copy, trace):
    root, _ = copy
    env = dict(os.environ, JAX_PLATFORMS="cpu", PYTHONPATH=ROOT)
    got = subprocess.run(
        [sys.executable, os.path.join(root, "benchmark", "run.py"), "--workload",
         "new_cell", "--seed", "3000000019", "--seconds", "1", "--trace", str(trace)],
        env=env, capture_output=True, text=True, timeout=300,
    )
    assert got.returncode == 1, got.stderr[-2000:]  # a CPU run is never correct
    for line in got.stdout.splitlines():  # and prints no result on standard output
        assert "metrics" not in json.loads(line)
    # standard error ends with the result and, last, each compared number beside its limit
    *_, last, answers, wrong, unanswered = got.stderr.strip().splitlines()
    assert (answers.split(":")[0], wrong, unanswered) == (
        "compared answers", "compared wrong: 0 (at most 0)",
        "compared unanswered: 0 (at most 0)")
    line = json.loads(last)
    assert list(line)[-1] == "compared"
    assert line["correct"] is False and line["failed"] == 0 and line["attempted"] > 1
    assert line["device"]["platform"] == "cpu"
    if trace:
        assert line["metrics"]["live_rows_per_stmt.pass"]["unit"] == "rows/stmt"
        assert "breakdown" in line and line["device"]["window_s"] > 0
    else:
        assert set(line["metrics"]) == {"setup_s", "pass_s.p50"}


@pytest.mark.parametrize("kind,path", [
    ("configuration", "configs/tpch_new.json"),
    ("traffic", "traffic/count_nations.json"),
    ("statement", "statements/nations.py"),
    ("layer metric", "layer_metrics/live_rows_per_stmt.json"),
    ("loop", "loops/closed_pass.py"),
])
def test_a_missing_file_fails_before_jax_with_its_name(copy, kind, path):
    root, _ = copy
    os.remove(os.path.join(root, "benchmark", path))
    name = os.path.splitext(os.path.basename(path))[0]
    # a jax that cannot be imported: discovery must fail first
    os.makedirs(os.path.join(root, "jax"))
    with open(os.path.join(root, "jax", "__init__.py"), "w") as f:
        f.write("raise ImportError('jax was imported before discovery failed')\n")
    got = subprocess.run(
        [sys.executable, os.path.join(root, "benchmark", "run.py"), "--workload",
         "new_cell", "--seed", "1", "--seconds", "1", "--trace", "0"],
        env=dict(os.environ, PYTHONPATH=root), capture_output=True, text=True, timeout=60,
    )
    assert got.returncode == 2 and got.stdout == ""
    assert kind in got.stderr and f"'{name}" in got.stderr, got.stderr


def test_an_unknown_workload_names_itself(copy):
    root, _ = copy
    with pytest.raises(discovery.Missing, match="nope"):
        discovery.load_cell(root, "nope")


def test_every_cell_of_the_benchmark_resolves():
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        bench = json.load(f)
    for w in bench["workloads"]:
        cell = discovery.load_cell(ROOT, w["name"])
        assert cell.per_layer and len(cell.end_to_end) >= 2
        names = {m["name"] for m in cell.end_to_end}
        assert "setup_s" in names
        for m in bench["per_layer"]:
            if w["name"] in m.get("workloads", [w["name"]]):
                assert m["moves"] in names, (w["name"], m["name"])
