"""What ISSUE 31 adds to the benchmark: the configuration file against the
catalog's keys, the byte counts of ``flops/phi4-mini-flash.py`` at the
published widths, the five readers on hand-made run records (and ``None``
where the program has no such counter, as the parent commit has not), and
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

# As test_axk1.py does: conftest's rename table ends at PR 23's cells and
# is an accepted file this PR may not edit.
conftest._RENAME.setdefault("axk1-serve-decode", "tiny-axk1-serve")
conftest._RENAME.setdefault("phi4flash-serve-reason", "tiny-phi4flash-serve")

BENCH = cells.load_benchmark()
CELL = "phi4flash-serve-reason"
FLOPS = cells.load_module("flops", "phi4-mini-flash")
with open(os.path.join(cells.HERE, "configs", "phi4-mini-flash.json")) as f:
    CONFIG = json.load(f)
MODEL = CONFIG["model"]
NEW = ["ssm_scan_time_pct", "ssm_scan_roofline",
       "hybrid_decode_hbm_roofline", "prefill_ms_per_ktok",
       "shared_kv_tokens_per_iter"]
# The catalog's row (model-configs guide, architectures.jsonl).
PUBLISHED = {
    "embd_pdrop": 0, "hidden_act": "silu", "hidden_size": 2560,
    "intermediate_size": 10240, "layer_norm_eps": 1e-05,
    "max_position_embeddings": 262144, "mb_per_layer": 2,
    "model_type": "phi4flash", "num_attention_heads": 40,
    "num_hidden_layers": 32, "num_key_value_heads": 20, "resid_pdrop": 0,
    "sliding_window": 512, "tie_word_embeddings": True, "mlp_bias": False,
    "lm_head_bias": False, "vocab_size": 200064}


def test_the_file_holds_the_published_keys_uncut():
    assert CONFIG["reduced"] == []
    for key, value in PUBLISHED.items():
        assert CONFIG[key] == value, key         # the top level, as run
        assert MODEL[key] == value, key          # what builder and ref read
    (entry,) = [c for c in BENCH["configs"] if c["name"] == "phi4-mini-flash"]
    assert entry["reduced"] == [] and entry["source"] == CONFIG["source"]
    # Every size the catalog's config lacks is under ``assumed``.
    assert set(MODEL) - set(PUBLISHED) == {
        "d_state", "d_conv", "expand", "dt_rank", "dtype",
        "initializer_range", "lambda_std"}
    assert set(CONFIG["assumed"]) >= {
        "d_state", "d_conv", "expand", "dt_rank", "layer_layout",
        "positional_encoding", "differential_attention", "attention_bias",
        "window_edge", "weights", "dtype"}
    assert "ONE v5e chip" in CONFIG["deployment"]
    assert any("prefix cache is off" in d for d in CONFIG["departures"])


def test_the_byte_counts_are_the_issues_figures():
    p, m = FLOPS.param_counts(MODEL), 1e6
    assert round(p["ssm"] / m, 1) == 119.9
    assert round(p["window"] / m, 1) == round(p["full"] / m, 1) == 98.3
    assert round(p["gmu"] / m, 1) == 104.9
    assert round(p["cross"] / m, 1) == 91.8
    assert round(p["embed"] / m, 1) == 512.2
    assert FLOPS.layer_counts(MODEL) == {"ssm": 9, "window": 8, "full": 1,
                                         "gmu": 7, "cross": 7}
    assert round(FLOPS.total_params(MODEL) / 1e9, 2) == 3.85
    assert round(2 * FLOPS.total_params(MODEL) / 1e9, 2) == 7.71
    assert FLOPS.position_bytes(MODEL) == 5120
    assert FLOPS.slot_state_bytes(MODEL) == 358400
    stores = FLOPS.store_bytes(MODEL, 64, 6144)
    assert round(stores["paged"] / 1e9, 2) == 2.01
    assert round(stores["window"] / 1e9, 2) == 1.34
    assert round(stores["state"] / 1e9, 2) == 0.21
    # A cache in all 32 layers for the same positions.
    assert 32 * 5120 * 64 * 6144 == pytest.approx(64.4e9, rel=1e-2)
    # 40 slots alive at 1000 cached positions: 8 readers of the one store
    # 1.64 GB, 8 rings 0.84 GB, state 0.26 GB, weights 7.71 GB.
    whole = FLOPS.decode_iteration_bytes(MODEL, 40 * 1000, 40 * 512, 40)
    none = FLOPS.decode_iteration_bytes(MODEL, 0, 0, 40)
    assert whole - none == pytest.approx(1.64e9 + 0.84e9, rel=1e-2)
    assert none == pytest.approx(7.71e9 + 0.26e9, rel=1e-2)
    assert FLOPS.ssm_scan_bytes(MODEL, 2048) == pytest.approx(127.1e6,
                                                              rel=1e-3)


def test_the_five_are_declared_for_the_one_cell_with_the_files_own_words():
    declared = {m["name"]: m for m in BENCH["per_layer"]}
    assert [m["name"] for m in BENCH["per_layer"]][-len(NEW):] == NEW
    listed = [m["name"] for m in cells.resolve(BENCH, CELL)["per_layer"]]
    for name in NEW:
        mod = _reader(name)
        assert declared[name] == {
            "name": name, "unit": mod.UNIT, "better": mod.BETTER,
            "source": mod.SOURCE, "layer": mod.LAYER, "moves": mod.MOVES,
            "workloads": [CELL]}
        assert mod.MOVES == "tpot_p90_ms" and name in listed
    assert sorted(set(listed) - set(NEW)) == ["cache_misses", "warm_start_s",
                                              "window_compiles"]
    e2e = [m["name"] for m in cells.resolve(BENCH, CELL)["end_to_end"]]
    assert e2e == ["tpot_p90_ms", "setup_s"]
    (cell,) = [w for w in BENCH["workloads"] if w["name"] == CELL]
    assert cell["chips"] == 1 and "64 GB" in cell["why"]
    assert BENCH["workloads"][-1] is cell
    traffic = cells._load_json("traffic", "serve-reason-long")
    assert traffic["order_seed"] == 31 and traffic["check_requests"] >= 3
    assert traffic["engine"] == {"slots": 64, "page_size": 16,
                                 "capacity": 6144}
    assert traffic["prompt_tokens"] == {"median": 512, "sigma": 0.9,
                                        "min": 64, "max": 2048}
    assert traffic["answer_tokens"] == {"median": 640, "sigma": 0.6,
                                        "min": 128, "max": 1536}


PEAKS = {"bf16_flops": 197e12, "hbm_bytes_per_s": 819e9}


def _window(iters=2000, shared=80e6, window=36e6, tokens=80100, prefills=100,
            prefill_tokens=70000, prefill_s=14.0, seconds=36.0):
    after = {"serving.decode_iterations": {"value": 5 + iters},
             "serving.shared_kv_tokens": {"value": shared},
             "serving.window_tokens": {"value": window},
             "serving.tokens_generated": {"value": tokens},
             "serving.prefills": {"value": prefills},
             "serving.prefill_tokens": {"value": prefill_tokens},
             "trace.span_seconds.serve.prefill": _hist(prefills, prefill_s),
             "serving.token_seconds": _hist(iters, seconds)}
    before = {"serving.decode_iterations": {"value": 5},
              "serving.token_seconds": _hist(0, 0.0)}
    return _run(before=before, after=after, config=CONFIG, flops=FLOPS,
                peaks=PEAKS, notes={},
                traffic=cells._load_json("traffic", "serve-reason-long"))


def test_the_counter_readers_on_a_hand_made_window():
    r = _window()
    assert _reader("shared_kv_tokens_per_iter").read(r) == pytest.approx(4e4)
    assert _reader("prefill_ms_per_ktok").read(r) == pytest.approx(200.0)
    # 40 slots alive, 40000 shared and 18000 window positions, 18 ms.
    least = FLOPS.decode_iteration_bytes(MODEL, 4e4, 1.8e4, 40.0)
    share = _reader("hybrid_decode_hbm_roofline").read(r)
    assert share == pytest.approx(100 * least / 819e9 / 0.018)
    assert 60 < share < 80
    # ``decode_hbm_roofline`` (axk1's) needs the expert counter: silent.
    assert _reader("decode_hbm_roofline").read(r) is None


def _traced(r, ops, busy=2.0, window=3.0, span=51.0):
    r.trace = {"busy_s": busy, "window_s": window, "ops": ops}
    r.requests = [types.SimpleNamespace(ok=True, due=100.0, responded=101.0),
                  types.SimpleNamespace(ok=True, due=100.0 + span - 1,
                                        responded=100.0 + span)]
    return r


def test_the_trace_readers_on_hand_made_ops():
    ops = {"fusion": 1.5, FLOPS.KERNELS[0]["sample"]: 0.2, "copy": 0.3}
    r = _traced(_window(), ops)
    assert _reader("ssm_scan_time_pct").read(r) == pytest.approx(10.0)
    share = _reader("ssm_scan_roofline").read(r)
    least = FLOPS.ssm_scan_bytes(MODEL, 9 * 70000, 9 * 100) / 819e9
    assert share == pytest.approx(100 * least * 3.0 / 51.0 / 0.2)
    assert 0 < share < 100


@pytest.mark.parametrize("name", NEW)
def test_nothing_to_read_reads_nothing(name):
    """The parent commit's program has no such counter and no such op: the
    reader returns ``None`` and does not raise."""
    gpt = cells.resolve(BENCH, "gpt2m-serve-chat")
    r = _run(before={"serving.decode_iterations": {"value": 5},
                     "serving.token_seconds": _hist(0, 0.0)},
             after={"serving.decode_iterations": {"value": 55},
                    "serving.token_seconds": _hist(50, 0.4),
                    "serving.tokens_generated": {"value": 90},
                    "trace.span_seconds.serve.prefill": _hist(9, 0.1),
                    "serving.prefills": {"value": 9}},
             config=gpt["config"], flops=gpt["flops"], traffic=gpt["traffic"],
             peaks=PEAKS, notes={}, requests=[],
             trace={"busy_s": 1.0, "window_s": 3.0, "ops": {"fusion": 1.0}})
    assert _reader(name).read(r) is None
    r.trace = None
    assert _reader(name).read(r) is None
    # This configuration's own flops, and a program that counted nothing.
    r = _traced(_run(before={}, after={}, config=CONFIG, flops=FLOPS,
                     traffic=gpt["traffic"], peaks=PEAKS, notes={}),
                {"fusion": 1.0})
    assert _reader(name).read(r) is None


def test_the_toy_fixture_of_the_family_runs_through_the_harness(capsys):
    bench = dict(BENCH)
    bench["workloads"] = [{"name": "tiny-phi4flash-serve",
                           "config": "tiny-phi4flash",
                           "traffic": "tiny-serve-reason-long", "chips": 1,
                           "why": "fixture"}]
    bench["end_to_end"] = [dict(m, workloads=["tiny-phi4flash-serve"])
                           if "workloads" in m else m
                           for m in BENCH["end_to_end"]]
    line = json.loads(run.measure(
        "tiny-phi4flash-serve", 2_147_483_999, 1.0, False,
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
