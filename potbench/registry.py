"""Everything a cell is made of, found by the names in ``BENCHMARK.json``.

- a configuration: the ``file`` its entry in ``configs`` names;
- a traffic mix: ``potbench/mixes/<traffic>.json``;
- a key law: ``potbench/keylaws/<law>.py`` (loaded by ``traffic.Stream``);
- a per-layer metric: ``potbench/metrics/<name>.py``, whose
  ``read(record)`` returns the number or ``None`` when it finds nothing.
"""

from __future__ import annotations

import importlib.util
import json
import os

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)


def _json(path: str) -> dict:
    with open(path) as f:
        return json.load(f)


def benchmark(root: str = ROOT) -> dict:
    return _json(os.path.join(root, "BENCHMARK.json"))


def cell(bench: dict, name: str) -> dict:
    for w in bench["workloads"]:
        if w["name"] == name:
            return w
    raise KeyError(f"no workload {name!r} in BENCHMARK.json")


def config(bench: dict, name: str, root: str = ROOT) -> dict:
    for c in bench["configs"]:
        if c["name"] == name:
            return _json(os.path.join(root, c["file"]))
    raise KeyError(f"no config {name!r} in BENCHMARK.json")


def mix(name: str) -> dict:
    return _json(os.path.join(HERE, "mixes", f"{name}.json"))


def metric_reader(name: str):
    """The ``read`` function of ``metrics/<name>.py``."""
    path = os.path.join(HERE, "metrics", f"{name}.py")
    spec = importlib.util.spec_from_file_location(f"potbench_metric_{name}",
                                                  path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read


def per_layer_for(bench: dict, cell_name: str) -> list[dict]:
    """The per-layer metrics reported in ``cell_name``."""
    return [m for m in bench["per_layer"]
            if cell_name in m.get("workloads", [cell_name])]


def end_to_end_for(bench: dict, cell_name: str) -> list[dict]:
    return [m for m in bench["end_to_end"]
            if cell_name in m.get("workloads", [cell_name])]
