"""What ISSUE 41 adds to the benchmark: the configuration file against the
catalog's keys, the counts of ``flops/trinity-large-ep8.py`` at the
published widths, the five readers on hand-made run records (and ``None``
where the program has no such counter, gauge or op, as the parent commit
has not), the cell and its files found by ``cells.py`` with no edit, and
the toy fixture of the family driven through the harness on the CPU."""

import json
import os
import time
import types

import pytest

from benchmark import cells, device, run
from benchmark.tests.test_span_metrics import _hist, _reader, _run
import conftest
from conftest import FIXTURES

# As test_granite4h.py and its forerunners do: conftest's rename table ends
# at PR 23's cells and is an accepted file this PR may not edit.
conftest._RENAME.setdefault("trinity-serve-mixed", "tiny-trinity-serve")

BENCH = cells.load_benchmark()
CELL = "trinity-serve-mixed"
FLOPS = cells.load_module("flops", "trinity-large-ep8")
with open(os.path.join(cells.HERE, "configs",
                       "trinity-large-ep8.json")) as f:
    CONFIG = json.load(f)
MODEL = CONFIG["model"]
NEW = {  # name -> (unit, better, source, layer)
    "window_kv_tokens_per_iter": ("tokens", "lower", "program_counter",
                                  "serving"),
    "window_pages_peak_pct": ("%", "lower", "program_counter", "serving"),
    "full_pages_peak_pct": ("%", "lower", "program_counter", "serving"),
    "gqa_attn_time_pct": ("%", "lower", "device_trace",
                          "grouped-query attention"),
    "mixed_decode_hbm_roofline": ("%", "higher", "program_counter",
                                  "serving")}
APPENDED = ["warm_start_s", "moe_ffn_time_pct", "moe_ffn_roofline",
            "expert_tokens_per_iter", "expert_load_max_over_mean",
            "prefill_ms_per_ktok", "shared_kv_tokens_per_iter",
            "prefill_ahead_pct", "steady_pass_ms", "admission_pass_ms",
            "admission_time_pct", "tables_after_admission_ms",
            "tables_h2d_kb_per_pass", "tpot_admission_p90_ms"]
CATALOG = "/opt/skills/guides/model-configs/architectures.jsonl"
REDUCED = ["num_hidden_layers", "num_dense_layers", "layer_types",
           "num_experts", "vocab_size"]


def test_the_file_holds_the_published_keys_but_the_five_reduced():
    assert CONFIG["reduced"] == REDUCED
    (entry,) = [c for c in BENCH["configs"]
                if c["name"] == "trinity-large-ep8"]
    assert entry["reduced"] == REDUCED and entry["source"] == CONFIG["source"]
    assert entry["file"] == "benchmark/configs/trinity-large-ep8.json"
    assert BENCH["configs"][-1] is entry
    assert (MODEL["num_hidden_layers"], MODEL["num_dense_layers"],
            MODEL["num_experts"], MODEL["vocab_size"]) == (5, 1, 32, 25024)
    assert (MODEL["num_experts_published"], MODEL["n_routed_experts"],
            MODEL["n_routed_experts_published"]) == (256, 32, 256)
    assert MODEL["layer_types"] == ["sliding_attention"] * 4 + [
        "full_attention"]
    # No width is cut.
    assert (MODEL["hidden_size"], MODEL["num_attention_heads"],
            MODEL["num_key_value_heads"], MODEL["head_dim"],
            MODEL["sliding_window"], MODEL["intermediate_size"],
            MODEL["moe_intermediate_size"], MODEL["num_experts_per_tok"],
            MODEL["route_scale"]) == (3072, 48, 8, 128, 4096, 12288, 3072,
                                      4, 2.448)
    assert set(CONFIG["assumed"]) >= {
        "rope", "gate", "qk_norm", "norms", "router", "window", "embedding",
        "weights", "dtype", "bytes"}
    assert "EIGHT v5e chips" in CONFIG["deployment"]
    assert any("prefix_cache is off" in d for d in CONFIG["departures"])
    assert CONFIG["control_precision"] == "fp8"
    assert 0 < CONFIG["check"]["limits"]["served_gap_max"]
    if not os.path.isfile(CATALOG):
        pytest.skip("the model-configs guide's catalog is not here")
    with open(CATALOG) as f:
        (row,) = [r for r in map(json.loads, filter(str.strip, f))
                  if r["name"] == "Trinity-Large-Preview"]
    assert CONFIG["source"] == row["source_url"]
    for key, value in row["config"].items():
        if key in REDUCED:
            continue
        assert CONFIG[key] == value, key         # the top level, as run
        assert MODEL[key] == value, key          # what builder and ref read
    # The five kept layers are published layers 0 and 8-11.
    assert MODEL["layer_types"] == [row["config"]["layer_types"][l]
                                    for l in MODEL["layers_kept"]]
    assert CONFIG["reduced_from"]["num_experts"] == row["config"][
        "num_experts"] == MODEL["num_experts_published"]


def test_the_counts_are_the_issues_figures():
    p = FLOPS.param_counts(MODEL)
    assert p["attention"] == 62_914_560
    assert (p["attention"] + p["shared"] + p["router"] + 32 * p["expert"]
            == 997_982_208)
    assert p["attention"] + p["dense_ffn"] == 176_160_768
    assert p["embed"] + p["head"] == 153_747_456
    total = FLOPS.total_params(MODEL)
    assert total == 4_321_837_056            # 4.32B, 8.64 GB in bfloat16
    assert round(2 * total / 1e9, 2) == 8.64
    assert "4,321,837,056 parameters" in CONFIG["assumed"]["bytes"]
    assert "8.64 GB" in CONFIG["assumed"]["bytes"]
    assert FLOPS.layer_counts(MODEL) == (1, 4)
    assert FLOPS.kind_counts(MODEL) == (1, 4)
    assert FLOPS.token_bytes(MODEL) == 4096
    # The Motivation's pass at 32 alive and a mean length near 4000 (3300
    # of them inside the window): 1.24 GB of matrices outside the routed
    # experts, 2.9 GB of touched experts (12.7 of 32 a layer), 2.2 GB of
    # cache, 1.7 of it in the window group.
    bare = FLOPS.decode_pass_bytes(MODEL, 0, 0, 0)
    assert round(bare / 1e9, 2) == 1.24
    touched = FLOPS.expected_touched(MODEL, 32)
    assert round(touched, 1) == 12.7
    experts = FLOPS.decode_pass_bytes(MODEL, 4 * touched, 0, 0) - bare
    assert round(experts / 1e9, 1) == 2.9
    cache = FLOPS.decode_pass_bytes(MODEL, 0, 32 * 4000, 32 * 3300) - bare
    assert round(cache / 1e9, 1) == 2.3
    assert round(FLOPS.token_bytes(MODEL) * 4 * 32 * 3300 / 1e9, 1) == 1.7
    work = FLOPS.moe_ffn_work(MODEL, 100, 10)
    assert work["flops"] == 2 * 100 * p["expert"]
    assert work["bytes"] == 2 * (10 * p["expert"] + 100 * 2 * 3072)
    assert [k["name"] for k in FLOPS.KERNELS] == ["moe_ffn", "gqa_flash",
                                                  "gqa_attn"]
    # The prompt's attention: a head scores n (n + 1) / 2 pairs in the full
    # layer and at most 4096 keys a query in the four window layers.
    assert FLOPS.prompt_attention_pairs(MODEL, 100) == 5 * 5050
    assert FLOPS.prompt_attention_pairs(MODEL, 8192) == (
        8192 * 8193 // 2 + 4 * (4096 * 4097 // 2 + 4096 * 4096))
    flash = FLOPS.gqa_flash_work(MODEL, 1000, 10)
    assert flash["flops"] == 2 * 2 * 128 * 48 * 1000
    assert flash["bytes"] == 2 * 10 * 128 * (2 * 48 + 2 * 8)
    traffic = cells._load_json("traffic", "serve-mixed-64")
    assert 3600 < FLOPS.mean_prompt_tokens(traffic) < 3800


@pytest.mark.parametrize("name", sorted(NEW))
def test_it_is_declared_for_the_one_cell_with_the_files_own_words(name):
    unit, better, source, layer = NEW[name]
    (entry,) = [m for m in BENCH["per_layer"] if m["name"] == name]
    mod = _reader(name)
    assert entry == {"name": name, "unit": mod.UNIT, "better": mod.BETTER,
                     "source": mod.SOURCE, "layer": mod.LAYER,
                     "moves": mod.MOVES, "workloads": [CELL]}
    assert (unit, better, source, layer, "tpot_p90_ms") == (
        mod.UNIT, mod.BETTER, mod.SOURCE, mod.LAYER, mod.MOVES)


def test_the_cell_and_its_files_are_found_with_no_edit():
    resolved = cells.resolve(BENCH, CELL)
    assert resolved["config"] == CONFIG and resolved["cell"] == {
        "name": CELL, "config": "trinity-large-ep8",
        "traffic": "serve-mixed-64", "chips": 1,
        "why": resolved["cell"]["why"]}
    assert len(resolved["cell"]["why"]) <= 200
    assert sum(w["chips"] == 4 for w in BENCH["workloads"]) == 1
    assert hasattr(resolved["ref"], "served_logits")
    assert hasattr(resolved["ref"], "init_params")
    assert cells.resolve_callable(CONFIG["serve_builder"]).__name__ \
        == "build_serve"
    listed = [m["name"] for m in resolved["per_layer"]]
    assert sorted(listed) == sorted(
        set(NEW) | set(APPENDED) | {"cache_misses", "window_compiles"})
    # The cell is LAST in each list it was appended to.
    for name in APPENDED:
        (entry,) = [m for m in BENCH["per_layer"] if m["name"] == name]
        assert entry["workloads"][-1] == CELL, name
    (tpot,) = [m for m in BENCH["end_to_end"] if m["name"] == "tpot_p90_ms"]
    assert tpot["workloads"][-1] == CELL
    for name in ("decode_hbm_roofline", "hybrid_decode_hbm_roofline",
                 "steady_decode_hbm_roofline"):
        (entry,) = [m for m in BENCH["per_layer"] if m["name"] == name]
        assert CELL not in entry["workloads"]
    assert [m["name"] for m in resolved["end_to_end"]] == ["tpot_p90_ms",
                                                           "setup_s"]
    traffic = resolved["traffic"]
    assert traffic["runner"] == "benchmark.serve:run_cell"
    assert {k: traffic["engine"][k] for k in ("slots", "page_size",
                                              "capacity")} == {
        "slots": 64, "page_size": 16, "capacity": 9216}
    assert traffic["prompt_tokens"] == {"median": 3072, "sigma": 0.8,
                                        "min": 256, "max": 8192}
    assert traffic["answer_tokens"] == {"median": 256, "sigma": 0.7,
                                        "min": 32, "max": 768}
    assert traffic["warmup_prompt_tokens"] == [256, 512, 1024, 2048, 4096,
                                               8192]
    assert traffic["check_requests"] == 4 and traffic["trace_seconds"] == 3.0
    assert traffic["order_seed"] == 41 and traffic["rate_per_s"] > 0


PEAKS = {"bf16_flops": 197e12, "hbm_bytes_per_s": 819e9}


def _window(iters=2000, alive=20, prefills=140, steady=1600,
            steady_seconds=32.0):
    after = {"serving.decode_iterations": {"value": 5 + iters},
             "serving.shared_kv_tokens": {"value": alive * 4000 * iters},
             "serving.window_tokens": {"value": alive * 3300 * iters},
             "serving.moe_experts_touched": {"value": 35 * iters},
             "serving.moe_assignments": {"value": 40 * iters},
             "serving.tokens_generated":
                 {"value": alive * iters + prefills},
             "serving.prefills": {"value": prefills},
             "serving.pass_seconds.steady": _hist(steady, steady_seconds),
             "serving.kv_group_pages_peak.window": {"value": 6300},
             "serving.kv_group_pages_total.window": {"value": 11053},
             "serving.kv_group_pages_peak.full": {"value": 7500},
             "serving.kv_group_pages_total.full": {"value": 13763},
             "serving.token_seconds": _hist(iters, 46.0)}
    before = {"serving.decode_iterations": {"value": 5},
              "serving.pass_seconds.steady": _hist(0, 0.0),
              "serving.token_seconds": _hist(0, 0.0)}
    return _run(before=before, after=after, config=CONFIG, flops=FLOPS,
                peaks=PEAKS, notes={}, trace=None, requests=[],
                traffic=cells._load_json("traffic", "serve-mixed-64"))


def test_the_counter_readers_on_a_hand_made_window():
    r = _window()
    assert _reader("window_kv_tokens_per_iter").read(r) == 20 * 3300
    assert _reader("shared_kv_tokens_per_iter").read(r) == 20 * 4000
    assert _reader("window_pages_peak_pct").read(r) == pytest.approx(
        100 * 6300 / 11053)
    assert _reader("full_pages_peak_pct").read(r) == pytest.approx(
        100 * 7500 / 13763)
    # 1.24 GB of matrices, 35 touched experts of 56.6 MB, 20 slots of 4000
    # and 3300 cached positions: 4.6 GB over 819 GB/s over a 20 ms pass.
    least = FLOPS.decode_pass_bytes(MODEL, 35, 20 * 4000, 20 * 3300, 20.0)
    share = _reader("mixed_decode_hbm_roofline").read(r)
    assert share == pytest.approx(100 * least / 819e9 / 0.020)
    assert round(least / 1e9, 1) == 4.6 and 25 < share < 32
    # The accepted expert readers take this file's names.
    assert _reader("expert_tokens_per_iter").read(r) == 40 / 4
    # The older rooflines do not fit this model and read nothing.
    assert _reader("hybrid_decode_hbm_roofline").read(
        _run(before=r.counters_before, after=r.counters_after,
             config=CONFIG, flops=types.SimpleNamespace(), peaks=None,
             notes={})) is None


def test_the_roofline_counts_what_must_move_and_stays_under_100():
    """At the peak's own rate the share is 100: a pass cannot be shorter
    than its bytes over the peak, and the gather's extra traffic is not in
    the count."""
    least = FLOPS.decode_pass_bytes(MODEL, 35, 20 * 4000, 20 * 3300, 20.0)
    at_peak = least / 819e9
    r = _window(steady=1000, steady_seconds=1000 * at_peak)
    assert _reader("mixed_decode_hbm_roofline").read(r) == pytest.approx(100)
    r = _window(steady=1000, steady_seconds=3000 * at_peak)
    assert _reader("mixed_decode_hbm_roofline").read(r) == pytest.approx(
        100 / 3)


def test_the_trace_reader_on_hand_made_ops():
    r = _window()
    sample = [k for k in FLOPS.KERNELS if k["name"] == "gqa_attn"][0]["sample"]
    r.trace = {"busy_s": 2.9, "window_s": 3.0,
               "ops": {"fusion": 1.6, sample: 0.4, "ragged-dot-none": 0.5,
                       "copy": 0.3}}
    assert _reader("gqa_attn_time_pct").read(r) == pytest.approx(
        100 * 0.4 / 2.9)
    assert _reader("moe_ffn_time_pct").read(r) == pytest.approx(
        100 * 0.5 / 2.9)
    # The prompt's kernel counts in the layer's share.
    r.trace["ops"]["gqa_flash_fwd"] = 0.3
    assert _reader("gqa_attn_time_pct").read(r) == pytest.approx(
        100 * 0.7 / 2.9)
    r.trace["ops"] = {"fusion": 2.0}
    assert _reader("gqa_attn_time_pct").read(r) is None


@pytest.mark.parametrize("name", sorted(NEW))
def test_nothing_to_read_reads_nothing(name):
    """The parent commit's program has no such counter, gauge or op: the
    reader returns ``None`` and does not raise."""
    other = cells.resolve(BENCH, "granite4h-serve-sessions")
    r = _run(before={"serving.decode_iterations": {"value": 5},
                     "serving.token_seconds": _hist(0, 0.0)},
             after={"serving.decode_iterations": {"value": 55},
                    "serving.token_seconds": _hist(50, 0.4),
                    "serving.shared_kv_tokens": {"value": 9000},
                    "serving.tokens_generated": {"value": 90},
                    "serving.prefills": {"value": 9}},
             config=other["config"], flops=other["flops"],
             traffic=other["traffic"], peaks=PEAKS, notes={}, requests=[],
             trace={"busy_s": 1.0, "window_s": 3.0,
                    "ops": {"fusion": 1.0, "ssd_step": 0.1}})
    assert _reader(name).read(r) is None
    r.trace = None
    assert _reader(name).read(r) is None
    # This configuration's own flops, and a program that counted nothing.
    r = _run(before={}, after={}, config=CONFIG, flops=FLOPS,
             traffic=other["traffic"], peaks=PEAKS, notes={},
             trace={"busy_s": 1.0, "window_s": 3.0, "ops": {"fusion": 1.0}})
    assert _reader(name).read(r) is None


def test_the_toy_fixture_of_the_family_runs_through_the_harness(capsys):
    bench = dict(BENCH)
    bench["workloads"] = [{"name": "tiny-trinity-serve",
                           "config": "tiny-trinity",
                           "traffic": "tiny-serve-mixed", "chips": 1,
                           "why": "fixture"}]
    bench["end_to_end"] = [dict(m, workloads=["tiny-trinity-serve"])
                           if "workloads" in m else m
                           for m in BENCH["end_to_end"]]
    line = json.loads(run.measure(
        "tiny-trinity-serve", 2_147_483_999, 1.0, False,
        device.device_info(), time.perf_counter(), bench=bench,
        base=FIXTURES))
    assert line["correct"] is True and line["failed"] == 0
    assert set(line["metrics"]) == {"tpot_p50_ms", "tpot_p90_ms", "setup_s"}
    earlier = capsys.readouterr().out
    compared = [json.loads(l.split(" ", 1)[1]) for l in earlier.splitlines()
                if l.startswith("benchmark:compared")][0]
    gap = [c for c in compared["compared"]
           if c["number"] == "served_gap_max"][0]
    assert gap["inside"] and gap["value"] < 1e-4
