"""Find everything a cell needs by the names in BENCHMARK.json.

A later PR adds a configuration, a traffic mix, a cell or a per-layer
metric by adding files and one entry; nothing here names one."""

from __future__ import annotations

import importlib
import importlib.util
import json
import os
from types import ModuleType

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)


def load_benchmark(path: str = None) -> dict:
    with open(path or os.path.join(ROOT, "BENCHMARK.json")) as f:
        return json.load(f)


def _load_json(kind: str, name: str, base: str = None) -> dict:
    """``<kind>/<name>.json`` under ``base`` (a test's fixtures) or here."""
    for root in filter(None, (base, HERE)):
        path = os.path.join(root, kind, f"{name}.json")
        if os.path.isfile(path):
            with open(path) as f:
                return json.load(f)
    raise FileNotFoundError(f"{kind}/{name}.json")


def load_module(kind: str, name: str) -> ModuleType:
    """``benchmark/<kind>/<name>.py`` by file (names may hold ``-``/``.``)."""
    path = os.path.join(HERE, kind, f"{name}.py")
    if not os.path.isfile(path):
        raise FileNotFoundError(path)
    mod_name = f"benchmark.{kind}._{name.replace('-', '_').replace('.', '_')}"
    spec = importlib.util.spec_from_file_location(mod_name, path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def resolve_callable(dotted: str):
    """``package.module:function``."""
    mod, _, fn = dotted.partition(":")
    return getattr(importlib.import_module(mod), fn)


def find_cell(bench: dict, workload: str) -> dict:
    for w in bench["workloads"]:
        if w["name"] == workload:
            return w
    raise KeyError(f"no workload {workload!r} in BENCHMARK.json "
                   f"(have {[w['name'] for w in bench['workloads']]})")


def reports(metric: dict, cell_name: str, bench: dict) -> bool:
    """Whether ``cell_name`` reports ``metric``.  A metric that lists
    ``workloads`` is reported there; an end-to-end metric without the key
    everywhere; a per-layer metric without it wherever the end-to-end
    metric it moves is reported."""
    if "workloads" in metric:
        return cell_name in metric["workloads"]
    if "moves" not in metric:
        return True
    moved = next(m for m in bench["end_to_end"]
                 if m["name"] == metric["moves"])
    return reports(moved, cell_name, bench)


def resolve(bench: dict, workload: str, base: str = None) -> dict:
    """The cell's entry, configuration, traffic, flops and reference, and
    the metrics it reports."""
    cell = find_cell(bench, workload)
    name = cell["name"]
    config = _load_json("configs", cell["config"], base)
    return {
        "cell": cell,
        "config": config,
        "traffic": _load_json("traffic", cell["traffic"], base),
        "flops": load_module("flops", config.get("flops", cell["config"])),
        "ref": load_module("refs", config.get("ref", cell["config"])),
        "end_to_end": [m for m in bench["end_to_end"]
                       if reports(m, name, bench)],
        "per_layer": [m for m in bench["per_layer"]
                      if reports(m, name, bench)],
    }
