"""Finding a cell's files by name.

``BENCHMARK.json`` names a cell's configuration and traffic mix; a
per-layer metric is named by its entry there.  Each is ONE file:

    benchmarks/configs/<config>.json          the deployment as it is run
    benchmarks/traffic/<traffic>.json         the mix's parameters
    benchmarks/traffic_kinds/<kind>.py        the one generator of its kind
    benchmarks/layer_metrics/<metric>.json    layer, unit, moves, reader
    benchmarks/readers/<reader>.py            a generic reader

A later PR adds a cell, a mix or a metric by adding files and entries; a
name that has no file is an error, never a default.
"""

from __future__ import annotations

import importlib.util
import json
import os
from typing import List

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)


def _load(kind_dir: str, name: str) -> dict:
    path = os.path.join(HERE, kind_dir, f"{name}.json")
    if not os.path.isfile(path):
        raise FileNotFoundError(f"no benchmarks/{kind_dir}/{name}.json")
    with open(path) as f:
        return json.load(f)


def benchmark(path: str = None) -> dict:
    with open(path or os.path.join(ROOT, "BENCHMARK.json")) as f:
        return json.load(f)


def cell(name: str, bench: dict = None) -> dict:
    bench = bench or benchmark()
    for w in bench["workloads"]:
        if w["name"] == name:
            cfg = _load("configs", w["config"])
            mix = _load("traffic", w["traffic"])
            if cfg.get("name") != w["config"]:
                raise ValueError(f"configs/{w['config']}.json names itself {cfg.get('name')!r}")
            return {"name": name, "chips": w["chips"], "config": cfg, "traffic": mix,
                    "kind": traffic_kind(mix["kind"])}
    raise KeyError(f"no workload {name!r} in BENCHMARK.json")


def _module(kind_dir: str, name: str):
    """The one module ``benchmarks/<kind_dir>/<name>.py``, loaded by its
    path (so a file dropped in is found with nothing registered)."""
    path = os.path.join(HERE, kind_dir, f"{name}.py")
    if not os.path.isfile(path):
        raise FileNotFoundError(f"no benchmarks/{kind_dir}/{name}.py")
    spec = importlib.util.spec_from_file_location(f"benchmarks.{kind_dir}.{name}", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def cut(cell_: dict, nodes: int, pods: int, init: int) -> dict:
    """The cell with its configuration's counts cut down: for rehearsals
    and tests on the CPU, never for a result."""
    cfg = cell_["config"]
    cfg["nodes"]["count"], cfg["measure_pods"]["count"], cfg["init_pods"]["count"] = nodes, pods, init
    return cell_


def traffic_kind(kind: str):
    return _module("traffic_kinds", kind)


def end_to_end_names(cell_name: str, bench: dict) -> List[str]:
    return [
        m["name"] for m in bench["end_to_end"]
        if "workloads" not in m or cell_name in m["workloads"]
    ]


def layer_metrics(cell_name: str, bench: dict) -> List[dict]:
    """The per-layer metrics this cell reports, each with its reader."""
    out = []
    for m in bench["per_layer"]:
        if "workloads" in m and cell_name not in m["workloads"]:
            continue
        spec = _load("layer_metrics", m["name"])
        spec["read"] = _module("readers", spec["reader"]).read
        out.append(spec)
    return out
