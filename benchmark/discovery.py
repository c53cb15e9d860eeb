"""Finds everything a cell needs by the names in ``BENCHMARK.json``.

Nothing here imports JAX or the engine: a cell that names a missing
file fails before either is loaded, with the name in the message. No
name is listed in code — a later PR adds a cell by adding files and
entries, never by editing one of these modules.
"""

from __future__ import annotations

import dataclasses
import importlib.util
import json
import os
from typing import Any, Dict, List


class Missing(Exception):
    """A name in ``BENCHMARK.json`` that no file answers to."""


def _need(path: str, what: str, name: str) -> str:
    if not os.path.isfile(path):
        raise Missing(f"{what} '{name}' not found: no file {path}")
    return path


def _json(path: str, what: str, name: str) -> Any:
    with open(_need(path, what, name)) as f:
        return json.load(f)


def prepare_sql(statement, schema: str, tag: str):
    """What a client session sends once before ``statement``: nothing,
    unless the statement's file has a ``prepare``."""
    return statement.prepare(schema, tag) if hasattr(statement, "prepare") else ()


def load_module(path: str):
    """Import one file of the benchmark by its path."""
    rel = os.path.splitext(path)[0].split(os.sep)[-2:]
    spec = importlib.util.spec_from_file_location("bench_" + "_".join(rel), path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def _applies(metric: dict, cell: str) -> bool:
    return "workloads" not in metric or cell in metric["workloads"]


@dataclasses.dataclass
class LayerMetric:
    name: str  # as BENCHMARK.json has it, variant suffix included
    spec: dict  # the metric's own file
    reader_path: str  # "" = the generic reader of the harness


@dataclasses.dataclass
class Cell:
    root: str
    name: str
    chips: int
    config_name: str
    config: dict
    traffic_name: str
    traffic: dict
    loop_path: str
    statement_paths: Dict[str, str]
    end_to_end: List[dict]
    per_layer: List[LayerMetric]
    peaks: dict

    def loop(self):
        return load_module(self.loop_path)

    def statements(self) -> dict:
        return {n: load_module(p) for n, p in self.statement_paths.items()}


def load_cell(root: str, workload: str) -> Cell:
    bench = _json(os.path.join(root, "BENCHMARK.json"), "benchmark", "BENCHMARK.json")
    here = os.path.join(root, bench["paths"][0])
    cells = {w["name"]: w for w in bench["workloads"]}
    if workload not in cells:
        raise Missing(
            f"workload '{workload}' is not in BENCHMARK.json (has: {sorted(cells)})"
        )
    w = cells[workload]
    entry = {c["name"]: c for c in bench["configs"]}.get(w["config"])
    if entry is None:
        raise Missing(f"configuration '{w['config']}' is not in BENCHMARK.json")
    config = _json(os.path.join(root, entry["file"]), "configuration", w["config"])
    traffic = _json(
        os.path.join(here, "traffic", w["traffic"] + ".json"), "traffic", w["traffic"]
    )
    loop = traffic["loop"]
    loop_path = _need(os.path.join(here, "loops", loop + ".py"), "loop", loop)
    statement_paths = {
        s: _need(os.path.join(here, "statements", s + ".py"), "statement", s)
        for s in traffic["statements"]
    }
    per_layer = []
    for m in bench["per_layer"]:
        if not _applies(m, workload):
            continue
        # `<metric>.<variant>` shares the file of `<metric>`
        bases = [m["name"]]
        if "." in m["name"]:
            bases.append(m["name"].rsplit(".", 1)[0])
        base = next(
            (b for b in bases
             if os.path.isfile(os.path.join(here, "layer_metrics", b + ".json"))),
            None,
        )
        if base is None:
            raise Missing(
                f"layer metric '{m['name']}' not found: no file "
                + os.path.join(here, "layer_metrics", bases[-1] + ".json")
            )
        spec = _json(os.path.join(here, "layer_metrics", base + ".json"),
                     "layer metric", base)
        moves = spec["moves"].get(loop)
        if moves != m["moves"]:
            raise Missing(
                f"layer metric '{m['name']}' moves '{m['moves']}' in BENCHMARK.json "
                f"but its file says '{moves}' for loop '{loop}'"
            )
        reader = os.path.join(here, "layer_metrics", base + ".py")
        per_layer.append(
            LayerMetric(m["name"], spec, reader if os.path.isfile(reader) else "")
        )
    return Cell(
        root=root, name=workload, chips=int(w["chips"]),
        config_name=w["config"], config=config,
        traffic_name=w["traffic"], traffic=traffic,
        loop_path=loop_path, statement_paths=statement_paths,
        end_to_end=[m for m in bench["end_to_end"] if _applies(m, workload)],
        per_layer=per_layer,
        peaks=_json(os.path.join(here, "peaks.json"), "table of peaks", "peaks.json"),
    )
