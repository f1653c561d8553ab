"""What ISSUE 33 adds to the benchmark: the configuration file against the
catalog's keys, the byte counts of ``flops/longcat-flash-omni-ep32.py`` at
the published widths, the two new readers on hand-made run records (and
``None`` where the program has no such counter, as the parent commit has
not), the accepted readers the cell is appended to on this configuration's
own ``flops`` file, the cell and its files found by ``cells.py`` with no
edit, and the toy fixture of the family driven through the harness on the
CPU."""

import json
import os
import time
import types

import pytest

from benchmark import cells, device, run
from benchmark.tests.test_span_metrics import _hist, _reader, _run
import conftest
from conftest import FIXTURES

# conftest's ``tiny_bench`` renames every cell a metric lists through a
# table that ends at PR 23's four cells (PERF.md section 7, ask 6).
conftest._RENAME.setdefault("longcat-serve-turns", "tiny-longcat-serve")

BENCH = cells.load_benchmark()
CELL = "longcat-serve-turns"
NAME = "longcat-flash-omni-ep32"
FLOPS = cells.load_module("flops", NAME)
with open(os.path.join(cells.HERE, "configs", f"{NAME}.json")) as f:
    CONFIG = json.load(f)
MODEL = CONFIG["model"]
TRAFFIC = cells._load_json("traffic", "serve-turns-128")
NEW = ["zc_moe_ffn_roofline", "zero_expert_pair_pct"]
APPENDED = ["warm_start_s", "moe_ffn_time_pct", "latent_attn_time_pct",
            "decode_hbm_roofline", "expert_tokens_per_iter",
            "expert_load_max_over_mean"]
# The catalog's row for LongCat-Flash-Omni (model-configs guide,
# architectures.jsonl, ``config``).
PUBLISHED = {
    "attention_bias": False, "vocab_size": 131072, "hidden_size": 6144,
    "ffn_hidden_size": 12288, "expert_ffn_hidden_size": 2048,
    "num_layers": 28, "num_attention_heads": 64, "kv_lora_rank": 512,
    "q_lora_rank": 1536, "qk_rope_head_dim": 64, "v_head_dim": 128,
    "qk_nope_head_dim": 128, "mla_scale_q_lora": True,
    "mla_scale_kv_lora": True, "routed_scaling_factor": 6,
    "n_routed_experts": 512, "max_position_embeddings": 131072,
    "rms_norm_eps": 1e-05, "rope_theta": 10000000,
    "attention_method": "MLA", "zero_expert_num": 256,
    "zero_expert_type": "identity", "moe_topk": 12}


def test_the_file_holds_the_published_keys_and_names_every_cut():
    cut = {"num_layers": 4, "n_routed_experts": 16, "vocab_size": 16384}
    assert CONFIG["reduced"] == list(cut)
    for key, value in PUBLISHED.items():
        want = cut.get(key, value)
        assert CONFIG[key] == want, key          # the top level, as run
        assert MODEL[key] == want, key           # what builder and ref read
    for key in cut:
        assert MODEL[f"{key}_published"] == PUBLISHED[key]
        assert str(PUBLISHED[key]) in CONFIG["reduced_from"][key]
    (entry,) = [c for c in BENCH["configs"] if c["name"] == NAME]
    assert entry["reduced"] == CONFIG["reduced"]
    assert entry["source"] == CONFIG["source"]
    assert entry["file"] == f"benchmark/configs/{NAME}.json"
    assert set(CONFIG["assumed"]) >= {
        "zero_expert_outputs", "norm_topk_prob", "router_bias",
        "rotary_pair_layout", "tie_word_embeddings", "weights"}
    assert "32 chips" in CONFIG["deployment"]
    assert any("audio and vision" in d for d in CONFIG["departures"])
    assert CONFIG["control_precision"] == "fp8"
    assert set(CONFIG["check"]["limits"]) == {"served_gap_max"}


def test_the_byte_counts_are_the_issues_figures():
    p, m = FLOPS.param_counts(MODEL), 1e6
    assert round(p["mla"] / m, 2) == 90.57
    assert round(p["dense_ffn"] / m, 2) == 226.49
    assert round(p["router"] / m, 2) == 4.72
    assert round(p["expert"] / m, 2) == 37.75
    assert round(FLOPS.layer_params_outside_experts(MODEL) / m, 1) == 638.8
    assert round(p["embed"] / m, 1) == 100.7 == round(p["head"] / m, 1)
    assert FLOPS.layer_counts(MODEL) == (0, 4)
    assert round(FLOPS.total_params(MODEL) / 1e9, 2) == 5.17
    assert round(2 * FLOPS.total_params(MODEL) / 1e9, 2) == 10.35
    assert FLOPS.entry_bytes(MODEL) == 1152 and FLOPS.cache_layers(
        MODEL) == 8
    e = TRAFFIC["engine"]
    assert 8 * 640 * 2 * e["slots"] * e["capacity"] == pytest.approx(
        2.68e9, rel=2e-3)
    # One decode iteration at 40 slots alive: 7.8 GB, of it 5.11 outside
    # the experts and 2.3 the touched ones.
    touched = 4 * FLOPS.expected_touched(MODEL, 40)
    whole = FLOPS.decode_iteration_bytes(MODEL, touched, 40 * 544, 40)
    none = FLOPS.decode_iteration_bytes(MODEL, 0, 40 * 544, 40)
    assert whole == pytest.approx(7.78e9, rel=1e-2)
    assert whole - none == pytest.approx(2.26e9, rel=1e-2)
    assert whole / 819e9 == pytest.approx(9.5e-3, rel=1e-2)


def test_the_pairs_share_is_reckoned_over_all_768_outputs():
    assert FLOPS.router_outputs(MODEL) == 768
    assert FLOPS.held_pair_share(MODEL) == 12 * 16 / 768 == 0.25
    # moe_ffn_roofline's own reckoning (k x held / published) would be
    # 1.5 times that: the cell is not on its list.
    assert 12 * 16 / 512 == 1.5 * FLOPS.held_pair_share(MODEL)
    declared = {m["name"]: m for m in BENCH["per_layer"]}
    assert CELL not in declared["moe_ffn_roofline"]["workloads"]
    assert FLOPS.expected_touched(MODEL, 0) == 0
    assert FLOPS.expected_touched(MODEL, 128) == pytest.approx(
        16 * (1 - (1 - 12 / 768) ** 128))
    assert 384 < FLOPS.mean_prompt_tokens(TRAFFIC) < 520
    work = FLOPS.moe_ffn_work(MODEL, 32, 11)
    assert work["flops"] == 2 * 32 * 3 * 6144 * 2048
    assert work["bytes"] == 2 * (11 * 3 * 6144 * 2048 + 32 * 2 * 6144)


def test_the_cell_and_its_files_are_found_with_no_edit():
    r = cells.resolve(BENCH, CELL)
    assert r["cell"] == {
        "name": CELL, "config": NAME, "traffic": "serve-turns-128",
        "chips": 1, "why": r["cell"]["why"]}
    assert 1 <= len(r["cell"]["why"]) <= 200
    assert r["config"] == CONFIG and r["traffic"] == TRAFFIC
    assert r["flops"].KERNELS[0]["name"] == "moe_ffn"
    assert hasattr(r["ref"], "served_logits") and hasattr(r["ref"],
                                                         "init_params")
    assert cells.resolve_callable(CONFIG["serve_builder"]).__name__ == \
        "build_serve"
    assert [m["name"] for m in r["end_to_end"]] == ["tpot_p90_ms", "setup_s"]
    listed = [m["name"] for m in r["per_layer"]]
    assert sorted(listed) == sorted(
        NEW + APPENDED + ["cache_misses", "window_compiles"])
    assert len(BENCH["workloads"]) == 7
    assert [w["name"] for w in BENCH["workloads"] if w["chips"] == 4] == [
        "resnet50-dp4"]
    # The mix ISSUE 33 names.
    assert TRAFFIC["engine"] == {"slots": 128, "page_size": 16,
                                 "capacity": 2048}
    assert TRAFFIC["prompt_tokens"] == {"median": 384, "sigma": 0.8,
                                        "min": 64, "max": 1024}
    assert TRAFFIC["answer_tokens"] == {"median": 160, "sigma": 0.7,
                                        "min": 32, "max": 512}
    assert TRAFFIC["warmup_prompt_tokens"] == [64, 128, 256, 512, 1024]
    assert TRAFFIC["check_requests"] == 8
    assert TRAFFIC["trace_seconds"] == 3.0


def test_the_two_are_declared_for_the_one_cell_with_the_files_own_words():
    declared = {m["name"]: m for m in BENCH["per_layer"]}
    assert [m["name"] for m in BENCH["per_layer"]][-len(NEW):] == NEW
    for name in NEW:
        mod = _reader(name)
        assert declared[name] == {
            "name": name, "unit": mod.UNIT, "better": mod.BETTER,
            "source": mod.SOURCE, "layer": mod.LAYER, "moves": mod.MOVES,
            "workloads": [CELL]}
        assert mod.MOVES == "tpot_p90_ms" and mod.LAYER == "expert layer"
    for name in APPENDED:
        assert declared[name]["workloads"][-1] == CELL, name


PEAKS = {"bf16_flops": 197e12, "hbm_bytes_per_s": 819e9}


def _window(iters=1000, pairs=40000, fullest=7000, touched=30000,
            zero=640000, routed=1920000, tokens=40300, prefills=300,
            seconds=17.0):
    after = {"serving.decode_iterations": {"value": 5 + iters},
             "serving.moe_assignments": {"value": pairs},
             "serving.moe_expert_load_max": {"value": fullest},
             "serving.moe_experts_touched": {"value": touched},
             "serving.moe_zero_assignments": {"value": zero},
             "serving.moe_routed_pairs": {"value": routed},
             "serving.tokens_generated": {"value": tokens},
             "serving.prefills": {"value": prefills},
             "serving.token_seconds": _hist(iters, seconds)}
    before = {"serving.decode_iterations": {"value": 5},
              "serving.token_seconds": _hist(0, 0.0)}
    return _run(before=before, after=after, config=CONFIG, flops=FLOPS,
                peaks=PEAKS, notes={}, traffic=TRAFFIC)


def test_the_counter_readers_on_a_hand_made_window():
    r = _window()
    # 40 slots alive x 12 a token x 4 layers x 1000 iterations routed;
    # a third of it to zero-compute experts.
    assert _reader("zero_expert_pair_pct").read(r) == pytest.approx(
        100 / 3)
    # The accepted readers on this configuration's flops file: 40000
    # pairs over 1000 iterations and 4 expert layers; the fullest 7000
    # against a mean of 40000 / 16; 40 alive, 30 touched, 17 ms a pass.
    assert _reader("expert_tokens_per_iter").read(r) == pytest.approx(10.0)
    assert _reader("expert_load_max_over_mean").read(r) == pytest.approx(
        2.8)
    least = FLOPS.decode_iteration_bytes(MODEL, 30.0, 40 * 64, 40)
    assert _reader("decode_hbm_roofline").read(r) == pytest.approx(
        100 * least / 819e9 / 0.017)
    assert 45 < _reader("decode_hbm_roofline").read(r) < 60


def _traced(r, ops, busy=2.0, window=3.0, span=51.0):
    r.trace = {"busy_s": busy, "window_s": window, "ops": ops}
    r.requests = [types.SimpleNamespace(ok=True, due=100.0, responded=101.0),
                  types.SimpleNamespace(ok=True, due=100.0 + span - 1,
                                        responded=100.0 + span)]
    return r


def test_the_trace_readers_on_hand_made_ops():
    ops = {"fusion": 1.3, FLOPS.KERNELS[0]["sample"]: 0.4,
           FLOPS.KERNELS[1]["sample"]: 0.2, "copy": 0.1}
    r = _traced(_window(), ops)
    assert _reader("moe_ffn_time_pct").read(r) == pytest.approx(20.0)
    assert _reader("latent_attn_time_pct").read(r) == pytest.approx(10.0)
    mod = _reader("zc_moe_ffn_roofline")
    share, work = mod.read(r), mod.window_work(r)
    # Decode as counted; 300 prefills of the mean prompt in 4 layers, a
    # quarter of a pair a token on the held experts, all 16 touched.
    tokens = FLOPS.mean_prompt_tokens(TRAFFIC)
    want = FLOPS.moe_ffn_work(
        MODEL, 40000 + 300 * 4 * tokens * 0.25,
        30000 + 300 * 4 * FLOPS.expected_touched(MODEL, tokens))
    assert work == pytest.approx(want)
    least = max(work["flops"] / 197e12, work["bytes"] / 819e9)
    assert share == pytest.approx(100 * least * 3.0 / 51.0 / 0.4)
    assert r.notes["zc_moe_ffn_roofline_bound"] == "memory"
    assert 0 < share < 100


@pytest.mark.parametrize("name", NEW)
def test_nothing_to_read_reads_nothing(name):
    """The parent commit's program has no such counter, and another
    configuration's flops file no ``held_pair_share``: the reader returns
    ``None`` and does not raise."""
    gpt = cells.resolve(BENCH, "gpt2m-serve-chat")
    r = _run(before={"serving.decode_iterations": {"value": 5},
                     "serving.token_seconds": _hist(0, 0.0)},
             after={"serving.decode_iterations": {"value": 55},
                    "serving.token_seconds": _hist(50, 0.4),
                    "serving.tokens_generated": {"value": 90},
                    "serving.prefills": {"value": 9}},
             config=gpt["config"], flops=gpt["flops"], traffic=gpt["traffic"],
             peaks=PEAKS, notes={}, requests=[],
             trace={"busy_s": 1.0, "window_s": 3.0, "ops": {"fusion": 1.0}})
    assert _reader(name).read(r) is None
    r.trace = None
    assert _reader(name).read(r) is None
    # The other latent family: ragged-dot ops and the held experts'
    # counters, but no zero-compute outputs and no share in its flops.
    axk1 = cells.resolve(BENCH, "axk1-serve-decode")
    r = _traced(_window(zero=0, routed=0), {"ragged-dot-none": 0.5})
    r.config, r.flops, r.traffic = (axk1["config"], axk1["flops"],
                                    axk1["traffic"])
    assert _reader(name).read(r) is None
    # This configuration's own flops, and a program that counted nothing.
    r = _traced(_run(before={}, after={}, config=CONFIG, flops=FLOPS,
                     traffic=TRAFFIC, peaks=PEAKS, notes={}),
                {"fusion": 1.0})
    assert _reader(name).read(r) is None


def test_the_toy_fixture_of_the_family_runs_through_the_harness(capsys):
    bench = dict(BENCH)
    bench["workloads"] = [{"name": "tiny-longcat-serve",
                           "config": "tiny-longcat",
                           "traffic": "tiny-serve-turns", "chips": 1,
                           "why": "fixture"}]
    bench["end_to_end"] = [dict(m, workloads=["tiny-longcat-serve"])
                           if "workloads" in m else m
                           for m in BENCH["end_to_end"]]
    line = json.loads(run.measure(
        "tiny-longcat-serve", 2_147_483_999, 1.0, False,
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
