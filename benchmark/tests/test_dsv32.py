"""What ISSUE 49 adds to the benchmark: the configuration file against the
catalog's keys, the byte counts of ``flops/deepseek-v32-exp-ep16.py`` at the
published widths, the six ``dsa_*`` readers on hand-made run records (and
``None`` where the program has no such counter or kernel, as the parent
commit has not), pinned by NAME and cell, and the toy fixture of the
configuration driven through the harness on the CPU."""

import json
import os
import time
import types

import pytest

from benchmark import cells, device, run
from benchmark.tests.test_span_metrics import _hist, _reader, _run
import conftest
from conftest import FIXTURES

conftest._RENAME.setdefault("dsv32-serve-longdoc", "tiny-dsv32-serve")

BENCH = cells.load_benchmark()
CELL = "dsv32-serve-longdoc"
NAME = "deepseek-v32-exp-ep16"
FLOPS = cells.load_module("flops", NAME)
with open(os.path.join(cells.HERE, "configs", f"{NAME}.json")) as f:
    CONFIG = json.load(f)
MODEL = CONFIG["model"]
NEW = ["dsa_index_time_pct", "dsa_index_roofline", "dsa_select_time_pct",
       "dsa_sparse_attn_time_pct", "dsa_sparse_attn_roofline",
       "dsa_selected_share_pct"]
# The catalog's row for DeepSeek-V3.2-Exp (model-configs guide,
# architectures.jsonl).
PUBLISHED = {
    "attention_bias": False, "ep_size": 1, "first_k_dense_replace": 3,
    "hidden_act": "silu", "hidden_size": 7168, "index_head_dim": 128,
    "index_n_heads": 64, "index_topk": 2048, "intermediate_size": 18432,
    "kv_lora_rank": 512, "max_position_embeddings": 163840,
    "model_type": "deepseek_v32", "moe_intermediate_size": 2048,
    "moe_layer_freq": 1, "n_group": 8, "n_routed_experts": 256,
    "n_shared_experts": 1, "norm_topk_prob": True,
    "num_attention_heads": 128, "num_experts_per_tok": 8,
    "num_hidden_layers": 61, "num_key_value_heads": 128,
    "num_nextn_predict_layers": 1, "q_lora_rank": 1536,
    "qk_nope_head_dim": 128, "qk_rope_head_dim": 64, "rms_norm_eps": 1e-06,
    "rope_scaling": {"beta_fast": 32, "beta_slow": 1, "factor": 40,
                     "mscale": 1, "mscale_all_dim": 1,
                     "original_max_position_embeddings": 4096,
                     "type": "yarn"},
    "rope_theta": 10000, "routed_scaling_factor": 2.5,
    "scoring_func": "sigmoid", "tie_word_embeddings": False,
    "topk_group": 4, "topk_method": "noaux_tc", "v_head_dim": 128,
    "vocab_size": 129280}
CUT = {"num_hidden_layers": 5, "first_k_dense_replace": 1,
       "n_routed_experts": 16, "vocab_size": 16160}


def test_the_file_holds_the_published_keys_and_names_every_cut():
    assert sorted(CONFIG["reduced"]) == sorted(CUT)
    for key, value in PUBLISHED.items():
        want = CUT.get(key, value)
        assert CONFIG[key] == want, key          # the top level, as run
        assert MODEL[key] == want, key           # what builder and ref read
    for key in CUT:
        assert MODEL[f"{key}_published"] == PUBLISHED[key]
        assert str(PUBLISHED[key]) in CONFIG["reduced_from"][key]
    (entry,) = [c for c in BENCH["configs"] if c["name"] == NAME]
    assert entry["reduced"] == CONFIG["reduced"]
    assert entry["source"] == CONFIG["source"]
    assert entry["file"] == f"benchmark/configs/{NAME}.json"
    assert set(CONFIG["assumed"]) >= {"rotary_pair_layout", "weights",
                                      "bytes", "index_key_norm"}
    assert "16 chips" in CONFIG["deployment"]
    assert "rank 0" in CONFIG["deployment"]
    departures = " ".join(CONFIG["departures"])
    for word in ("bfloat16", "Hadamard", "multi-token", "prefix cache",
                 "absorbed"):
        assert word in departures, word
    assert CONFIG["control_precision"] == "fp8"
    readings = CONFIG["check"]["readings"]
    assert {"served_gap_max", "selection_all", "selection_recent",
            "selected_overlap"} <= set(readings)


def test_the_byte_counts_are_the_issues_figures():
    p = FLOPS.param_counts(MODEL)
    m = 1e6
    assert round(p["mla"] / m, 1) == 187.1    # the issue rounds its parts
    assert round(p["indexer"] / m, 1) == 14.0
    assert round(p["shared"] / m, 1) == 44.0 == round(p["expert"] / m, 1)
    assert round(p["router"] / m, 1) == 1.8
    assert round(16 * p["expert"] / m, 1) == 704.6
    assert round((p["mla"] + p["indexer"] + p["dense_ffn"]) / m, 1) == 597.4
    assert round(p["embed"] / m, 1) == 115.8 == round(p["head"] / m, 1)
    expert_layer = (p["mla"] + p["indexer"] + p["shared"] + p["router"]
                    + 16 * p["expert"])
    assert round(expert_layer / m, 1) == 951.6
    assert round(FLOPS.total_params(MODEL) / 1e9, 2) == 4.64
    assert round(2 * FLOPS.total_params(MODEL) / 1e9, 2) == 9.27
    assert FLOPS.entry_bytes(MODEL) == 1152 + 256
    assert FLOPS.index_key_bytes(MODEL) == 256
    assert FLOPS.LATENT_ROW_BYTES == 1280
    # The store keeps 5 x (640 + 128) values x 2 B a token.
    assert 5 * (FLOPS.LATENT_ROW_BYTES + 256) == 7680
    assert FLOPS.index_score_bytes(MODEL, 1e6) == 256e6
    assert FLOPS.sparse_attn_bytes(MODEL, 1e6) == 1280e6


def test_the_six_are_declared_for_the_one_cell_with_the_files_own_words():
    declared = {m["name"]: m for m in BENCH["per_layer"]}
    listed = [m["name"] for m in cells.resolve(BENCH, CELL)["per_layer"]]
    for name in NEW:
        mod = _reader(name)
        assert declared[name] == {
            "name": name, "unit": mod.UNIT, "better": mod.BETTER,
            "source": mod.SOURCE, "layer": mod.LAYER, "moves": mod.MOVES,
            "workloads": [CELL]}
        assert mod.MOVES == "tpot_p90_ms" and name in listed
        assert mod.LAYER == "sparse attention"
    for name in ("moe_ffn_time_pct", "latent_attn_time_pct",
                 "prefill_ms_per_ktok", "full_pages_peak_pct",
                 "expert_tokens_per_iter", "steady_pass_ms"):
        assert CELL in declared[name]["workloads"], name
    e2e = [m["name"] for m in cells.resolve(BENCH, CELL)["end_to_end"]]
    assert e2e == ["tpot_p90_ms", "setup_s"]
    (cell,) = [w for w in BENCH["workloads"] if w["name"] == CELL]
    assert cell["chips"] == 1 and cell["traffic"] == "serve-longdoc-32"
    traffic = cells._load_json("traffic", "serve-longdoc-32")
    assert traffic["prompt_tokens"]["min"] > MODEL["index_topk"]
    assert traffic["engine"]["capacity"] == (
        traffic["prompt_tokens"]["max"] + traffic["answer_tokens"]["max"])
    assert traffic["order_seed"] == 49 and traffic["check_requests"] == 3


PEAKS = {"bf16_flops": 197e12, "hbm_bytes_per_s": 819e9}


def _window(scored=4.0e9, selected=1.0e9, iters=1500, traced=2.0e8):
    after = {"serving.decode_iterations": {"value": 5 + iters},
             "serving.dsa_scored_tokens": {"value": scored},
             "serving.dsa_scored_tokens_traced": {"value": traced},
             "serving.dsa_selected_tokens": {"value": selected},
             "serving.token_seconds": _hist(iters, 30.0)}
    before = {"serving.decode_iterations": {"value": 5},
              "serving.token_seconds": _hist(0, 0.0)}
    return _run(before=before, after=after, config=CONFIG, flops=FLOPS,
                peaks=PEAKS, notes={},
                traffic=cells._load_json("traffic", "serve-longdoc-32"))


def _traced(r, ops, busy=2.0, window=3.0, span=51.0):
    r.trace = {"busy_s": busy, "window_s": window, "ops": ops}
    r.requests = [types.SimpleNamespace(ok=True, due=100.0, responded=101.0),
                  types.SimpleNamespace(ok=True, due=100.0 + span - 1,
                                        responded=100.0 + span)]
    return r


def test_the_readers_on_a_hand_made_window():
    ops = {"fusion": 0.9, "dsa_index_score": 0.1, "dsa_select": 0.05,
           "dsa_sparse_attn": 0.5, "ragged-dot-none": 0.2}
    r = _traced(_window(), ops)
    assert _reader("dsa_selected_share_pct").read(r) == pytest.approx(25.0)
    assert _reader("dsa_index_time_pct").read(r) == pytest.approx(5.0)
    assert _reader("dsa_select_time_pct").read(r) == pytest.approx(2.5)
    assert _reader("dsa_sparse_attn_time_pct").read(r) == pytest.approx(25.0)
    # The accepted reader of the latent attention's share reads this
    # configuration's kernel through its own flops file.
    assert _reader("latent_attn_time_pct").read(r) == pytest.approx(25.0)
    assert _reader("moe_ffn_time_pct").read(r) == pytest.approx(10.0)
    # 2e8 keys of 256 B scored WHILE THE TRACE RECORDED (not the window's
    # 4e9 scaled by 3 s of 51) against 0.1 s of kernel in the trace; as
    # many rows of 1280 B against 0.5 s.
    index = _reader("dsa_index_roofline").read(r)
    assert index == pytest.approx(100 * 2e8 * 256 / 819e9 / 0.1)
    attn = _reader("dsa_sparse_attn_roofline").read(r)
    assert attn == pytest.approx(100 * 2e8 * 1280 / 819e9 / 0.5)
    assert 0 < index < 100 and 0 < attn < 100
    # A trace that fell into a prompt pass holds few decode iterations and
    # little kernel time, in proportion: the share does not move.
    few = _traced(_window(traced=2.0e7), dict(ops, dsa_index_score=0.01))
    assert _reader("dsa_index_roofline").read(few) == pytest.approx(index)
    # A program that does not count under the trace reads nothing.
    assert _reader("dsa_index_roofline").read(
        _traced(_window(traced=0), ops)) is None


@pytest.mark.parametrize("name", NEW)
def test_nothing_to_read_reads_nothing(name):
    """The parent commit's program has no such counter and no such kernel:
    the reader returns ``None`` and does not raise."""
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
    # This configuration's own flops, and a program that counted nothing
    # and ran no kernel of these names (the family without the indexer).
    r = _traced(_run(before={}, after={}, config=CONFIG, flops=FLOPS,
                     traffic=gpt["traffic"], peaks=PEAKS, notes={}),
                {"fusion": 1.0, "latent_paged_attn": 0.5})
    assert _reader(name).read(r) is None


def test_the_toy_fixture_runs_through_the_harness(capsys):
    bench = dict(BENCH)
    bench["workloads"] = [{"name": "tiny-dsv32-serve", "config": "tiny-dsv32",
                           "traffic": "tiny-serve-longdoc", "chips": 1,
                           "why": "fixture"}]
    bench["end_to_end"] = [dict(m, workloads=["tiny-dsv32-serve"])
                           if "workloads" in m else m
                           for m in BENCH["end_to_end"]]
    line = json.loads(run.measure(
        "tiny-dsv32-serve", 2_147_483_999, 1.0, False, device.device_info(),
        time.perf_counter(), bench=bench, base=FIXTURES))
    assert line["correct"] is True and line["failed"] == 0
    assert {"tpot_p90_ms", "setup_s"} <= set(line["metrics"])
    earlier = capsys.readouterr().out
    compared = [json.loads(l.split(" ", 1)[1]) for l in earlier.splitlines()
                if l.startswith("benchmark:compared")][0]
    gap = [c for c in compared["compared"]
           if c["number"] == "served_gap_max"][0]
    assert gap["inside"] and gap["value"] < 1e-4


def test_a_program_without_the_indexer_refuses_the_configuration_at_once():
    """What the parent commit does with the new files laid beside it:
    ``config_of`` raises before a weight is drawn."""
    from benchmark.builders import sparse_latent_moe as builder

    class Old:                       # the family's config as the parent has it
        pass

    real = builder._family.config_of
    builder._family.config_of = lambda m: Old()
    try:
        with pytest.raises(ValueError, match="no indexer"):
            builder.config_of(MODEL)
    finally:
        builder._family.config_of = real
