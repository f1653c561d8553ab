"""BENCHMARK.json against the files: every name resolves, every name and
unit is spelt as the contract allows, every per-layer reader declares what
the entry says, and a new cell needs new files and one entry only."""

import json
import os
import re
import shutil

import pytest

from benchmark import cells

NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.\-]{1,16}$")
PATH = re.compile(r"^[A-Za-z0-9_.\-/]+$")
SOURCES = {"device_trace", "program_span", "program_counter", "host_clock"}

BENCH = cells.load_benchmark()


def test_top_level_keys_and_limits():
    assert set(BENCH) == {"command", "paths", "run_seconds", "configs",
                          "workloads", "end_to_end", "per_layer"}
    assert BENCH["paths"] == ["benchmark"]
    assert 1 <= BENCH["run_seconds"] <= 51
    assert len(json.dumps(BENCH)) < 64 * 1024
    four = [w for w in BENCH["workloads"] if w["chips"] == 4]
    assert len(four) <= max(1, len(BENCH["workloads"]) // 4)
    assert any(m["name"] == "setup_s" for m in BENCH["end_to_end"])
    for m in BENCH["end_to_end"]:
        assert 0 < m["bound"] <= 0.1
        assert m["source"] in {"host_clock", "device_trace"}
        assert set(m) <= {"name", "unit", "better", "bound", "source",
                          "workloads"}
    for m in BENCH["per_layer"]:
        assert set(m) <= {"name", "unit", "better", "source", "layer",
                          "moves", "workloads"}


def test_names_units_and_lines():
    entries = (BENCH["configs"] + BENCH["workloads"] + BENCH["end_to_end"]
               + BENCH["per_layer"])
    for e in entries:
        assert NAME.match(e["name"]), e["name"]
    for group in ("configs", "workloads", "end_to_end", "per_layer"):
        names = [e["name"] for e in BENCH[group]]
        assert len(names) == len(set(names))
    metric_names = [m["name"] for m in BENCH["end_to_end"] + BENCH["per_layer"]]
    assert len(metric_names) == len(set(metric_names))
    for m in BENCH["end_to_end"] + BENCH["per_layer"]:
        assert UNIT.match(m["unit"]), m
        assert m["better"] in ("lower", "higher")
        assert m["source"] in SOURCES
    for w in BENCH["workloads"]:
        assert NAME.match(w["config"]) and NAME.match(w["traffic"])
        assert w["chips"] in (1, 4)
        assert 1 <= len(w["why"]) <= 200 and "\n" not in w["why"]
    for c in BENCH["configs"]:
        assert 1 <= len(c["source"]) <= 200 and 1 <= len(c["why"]) <= 200
        assert all(NAME.match(k) for k in c["reduced"])
    pairs = [(w["config"], w["traffic"]) for w in BENCH["workloads"]]
    assert len(pairs) == len(set(pairs))
    for root, _dirs, files in os.walk(cells.HERE):
        if "__pycache__" in root:
            continue
        for f in files:
            rel = os.path.relpath(os.path.join(root, f), cells.ROOT)
            assert PATH.match(rel), rel


@pytest.mark.parametrize("workload", [w["name"] for w in BENCH["workloads"]])
def test_every_cell_resolves_to_files(workload):
    r = cells.resolve(BENCH, workload)
    assert r["config"]["name"] == r["cell"]["config"]
    runner = cells.resolve_callable(r["traffic"]["runner"])
    assert runner.__name__ == "run_cell"
    assert callable(r["ref"].init_params)
    assert hasattr(r["flops"], "train_flops_per_item")
    cells.resolve_callable(r["config"]["builder"])
    if runner.__module__ == "benchmark.serve":
        cells.resolve_callable(r["config"]["serve_builder"])
        assert callable(r["ref"].served_logits)
    names = {m["name"] for m in r["end_to_end"]}
    assert "setup_s" in names and len(names) >= 2
    assert r["per_layer"]
    for c in BENCH["configs"]:
        if c["name"] == r["cell"]["config"]:
            assert c["file"] == f"benchmark/configs/{c['name']}.json"
            assert c["reduced"] == r["config"]["reduced"]


@pytest.mark.parametrize("metric", BENCH["per_layer"],
                         ids=lambda m: m["name"])
def test_every_per_layer_metric_has_its_reader(metric):
    reader = cells.load_module("metrics", metric["name"])
    assert callable(reader.read)
    assert (reader.LAYER, reader.UNIT, reader.SOURCE, reader.MOVES,
            reader.BETTER) == (metric["layer"], metric["unit"],
                               metric["source"], metric["moves"],
                               metric["better"])
    moved = [m for m in BENCH["end_to_end"] if m["name"] == metric["moves"]]
    assert moved, "moves names an end-to-end metric"
    for w in metric.get("workloads", []):
        assert cells.reports(moved[0], w, BENCH)


def test_every_config_is_used_and_every_metric_is_reported():
    used = {w["config"] for w in BENCH["workloads"]}
    assert used == {c["name"] for c in BENCH["configs"]}
    cell_names = {w["name"] for w in BENCH["workloads"]}
    for m in BENCH["end_to_end"] + BENCH["per_layer"]:
        assert set(m.get("workloads", [])) <= cell_names


@pytest.mark.parametrize("config", [c["name"] for c in BENCH["configs"]])
def test_every_limit_is_a_number_with_its_readings(config):
    check = cells._load_json("configs", config)["check"]
    assert check["steps"] == 3
    for name, limit in check["limits"].items():
        assert isinstance(limit, float) and 0 < limit < 1, (name, limit)
        assert name in check["readings"], f"{name}: no readings recorded"


def test_flops_counts_are_the_published_ones():
    r = cells.resolve(BENCH, "resnet50-1chip")
    fwd = r["flops"].forward_flops_per_item(r["config"]["model"])
    assert 8.0e9 < fwd < 8.4e9            # 4.1 GMAC an image, He et al.
    g = cells.resolve(BENCH, "gpt2m-train-1k")
    per_token = g["flops"].forward_flops_per_item(g["config"]["model"], 1024)
    # 2 x (302M block weights + 51.5M output matrix) + attention
    assert 0.75e9 < per_token < 0.80e9


def test_a_new_cell_needs_new_files_and_one_entry_only(tmp_path):
    """Add a configuration, a traffic mix, a per-layer metric and a cell
    beside the existing ones without editing a file that is there."""
    base = tmp_path / "extra"
    (base / "configs").mkdir(parents=True)
    (base / "traffic").mkdir()
    cfg = cells._load_json("configs", "gpt2-medium")
    cfg["name"] = "gpt2-wide"
    (base / "configs" / "gpt2-wide.json").write_text(json.dumps(cfg))
    mix = cells._load_json("traffic", "serve-chat")
    mix["rate_per_s"], mix["order_seed"] = 2.5, 7
    (base / "traffic" / "serve-slow.json").write_text(json.dumps(mix))
    bench = json.loads(json.dumps(BENCH))
    bench["workloads"].append(
        {"name": "gpt2w-serve-slow", "config": "gpt2-wide",
         "traffic": "serve-slow", "chips": 1, "why": "slower"})
    for m in bench["end_to_end"]:
        if m["name"].startswith(("tpot", "ttft")):
            m["workloads"].append("gpt2w-serve-slow")
    r = cells.resolve(bench, "gpt2w-serve-slow", str(base))
    assert r["traffic"]["rate_per_s"] == 2.5
    assert r["ref"].__name__.endswith("gpt2_medium")
    assert {m["name"] for m in r["per_layer"]} >= {
        "decode_iter_ms", "gen_lag_p99_ms", "cache_misses"}
    # A new per-layer metric is one reader file, found by its name.
    src = os.path.join(cells.HERE, "metrics", "gen_lag_p99_ms.py")
    dst = os.path.join(cells.HERE, "metrics", "zz_test_only_metric.py")
    shutil.copy(src, dst)
    try:
        assert cells.load_module("metrics", "zz_test_only_metric").read
    finally:
        os.remove(dst)
