"""What ISSUE 46 adds to the benchmark: the configuration file against the
catalog's keys, the counts of ``flops/olmo-hybrid-7b-l16.py`` at the
published widths, the four new readers on hand-made run records (and
``None`` where the program has no such counter or kernel, as the parent
commit has not), the cell and its files found by ``cells.py`` with no edit,
and the toy fixture of the family driven through the harness on the CPU.
Everything is pinned by NAME and cell, never by position in a list."""

import json
import os
import time
import types

import pytest

from benchmark import cells, device, run
from benchmark.tests.test_span_metrics import _hist, _reader, _run
import conftest
from conftest import FIXTURES

# As the earlier families' test files do: conftest's rename table ends at
# PR 23's cells and is an accepted file this PR may not edit.
for _cell, _toy in (("axk1-serve-decode", "tiny-axk1-serve"),
                    ("phi4flash-serve-reason", "tiny-phi4flash-serve"),
                    ("longcat-serve-turns", "tiny-longcat-serve"),
                    ("granite4h-serve-sessions", "tiny-granite4h-serve"),
                    ("trinity-serve-mixed", "tiny-trinity-serve"),
                    ("olmohybrid-serve-chat96", "tiny-olmohybrid-serve")):
    conftest._RENAME.setdefault(_cell, _toy)

BENCH = cells.load_benchmark()
CELL = "olmohybrid-serve-chat96"
NAME = "olmo-hybrid-7b-l16"
FLOPS = cells.load_module("flops", NAME)
with open(os.path.join(cells.HERE, "configs", NAME + ".json")) as f:
    CONFIG = json.load(f)
MODEL = CONFIG["model"]
NEW = {  # name -> (unit, better, source, layer)
    "gdn_step_time_pct": ("%", "lower", "device_trace", "linear attention"),
    "gdn_chunk_time_pct": ("%", "lower", "device_trace", "linear attention"),
    "gdn_step_roofline": ("%", "higher", "device_trace", "linear attention"),
    "gdn_chunk_roofline": ("%", "higher", "device_trace",
                           "linear attention")}
APPENDED = ["warm_start_s", "prefill_ahead_pct", "steady_pass_ms",
            "admission_pass_ms", "admission_time_pct",
            "tables_after_admission_ms", "tables_h2d_kb_per_pass",
            "tpot_admission_p90_ms", "prefill_ms_per_ktok",
            "steady_decode_hbm_roofline", "state_mb_per_iter",
            "full_pages_peak_pct"]
CATALOG = "/opt/skills/guides/model-configs/architectures.jsonl"


def entry_of(kind, name):
    (entry,) = [e for e in BENCH[kind] if e["name"] == name]
    return entry


def test_the_file_holds_the_published_keys_cut_in_depth_alone():
    assert CONFIG["reduced"] == ["num_hidden_layers", "layer_types"]
    if not os.path.isfile(CATALOG):
        pytest.skip("the model-configs guide's catalog is not here")
    with open(CATALOG) as f:
        (row,) = [r for r in map(json.loads, filter(str.strip, f))
                  if r["name"] == "Olmo-Hybrid-7B"]
    assert CONFIG["source"] == row["source_url"]
    for key, value in row["config"].items():
        if key in CONFIG["reduced"]:
            continue
        assert CONFIG[key] == value, key         # the top level, as run
        assert MODEL[key] == value, key          # what builder and ref read
    assert CONFIG["num_hidden_layers"] == MODEL["num_hidden_layers"] == 16
    assert CONFIG["num_hidden_layers_published"] \
        == row["config"]["num_hidden_layers"] == 32
    # Four WHOLE periods of the published list: 12 linear, 4 full.
    assert MODEL["layer_types"] == row["config"]["layer_types"][:16] \
        == CONFIG["layer_types"]
    assert FLOPS.layer_counts(MODEL) == {"linear_attention": 12,
                                         "full_attention": 4}
    assert set(MODEL) - set(row["config"]) == {"dtype", "initializer_range"}
    entry = entry_of("configs", NAME)
    assert entry["reduced"] == CONFIG["reduced"]
    assert entry["source"] == CONFIG["source"]
    assert entry["file"] == f"benchmark/configs/{NAME}.json"
    assert "two-stage pipeline" in CONFIG["deployment"]
    assert any("prefix cache is off" in d for d in CONFIG["departures"])
    assert any("triangular" in d for d in CONFIG["departures"])
    assert CONFIG["control_precision"] == "fp8"
    assert 0 < CONFIG["check"]["limits"]["served_gap_max"]


def test_the_counts_are_the_issues_figures():
    p = FLOPS.param_counts(MODEL)
    assert p == {"linear_attention": 215_570_172,
                 "full_attention": 185_809_920, "embed": 385_351_680,
                 "head": 385_355_520}
    assert FLOPS.total_params(MODEL) == 4_100_788_944
    published = dict(MODEL, layer_types=MODEL["layer_types"] * 2)
    assert FLOPS.total_params(published) == 7_430_870_688
    assert round(2 * FLOPS.total_params(published) / 1e9, 1) == 14.9
    assert round(2 * FLOPS.total_params(MODEL) / 1e9, 2) == 8.20
    assert FLOPS.position_bytes(MODEL) == 61_440
    assert FLOPS.slot_state_bytes(MODEL) == 2_280_960
    stores = FLOPS.store_bytes(MODEL, 96, 3050 * 16)
    assert round(stores["state"] / 1e9, 2) == 2.63
    assert round(stores["paged"] / 1e9, 2) == 3.00
    # 55 slots alive at 700 cached positions: weights (less the embedding's
    # unread rows) 7.43 GB, state 3.0, pages 2.4: the issue's two fifths.
    whole = FLOPS.decode_iteration_bytes(MODEL, 55 * 700, 0, 55)
    bare = FLOPS.decode_iteration_bytes(MODEL, 0, 0, 0)
    state = 2 * 55 * 12 * 2_280_960
    assert bare == 2 * (4_100_788_944 - 385_351_680)
    assert whole - bare == pytest.approx(state + 55 * 700 * 61_440
                                         + 55 * 3840 * 2)
    assert round(state / 1e9, 1) == 3.0
    assert round(55 * 700 * 61_440 / 1e9, 1) == 2.4
    assert 0.40 < (whole - bare) / whole < 0.44
    assert FLOPS.decode_iteration_bytes(MODEL, 0, 123, 0) == bare
    # The kernels' own work.  One live slot in one layer: 2 x 2,211,840 B.
    assert FLOPS.gdn_step_bytes(MODEL, 1) == 2 * 30 * 96 * 192 * 4
    per_token = FLOPS.gdn_chunk_flops(MODEL, 1)
    assert per_token == 30 * (4 * 64 * 96 + 6 * 96 * 192 + 4 * 64 * 192)
    assert per_token == pytest.approx(5.5e6, rel=0.02)
    assert FLOPS.gdn_chunk_flops(MODEL, 2048) == 2048 * per_token
    assert {k["name"]: k["match"] for k in FLOPS.KERNELS} == {
        "gdn_step": r"^gdn_step", "gdn_chunk_scan": r"^gdn_chunk_scan",
        "gqa_paged_attn": r"^gqa_paged_attn"}


@pytest.mark.parametrize("name", sorted(NEW))
def test_it_is_declared_for_the_one_cell_with_the_files_own_words(name):
    unit, better, source, layer = NEW[name]
    entry = entry_of("per_layer", name)
    mod = _reader(name)
    assert entry == {"name": name, "unit": mod.UNIT, "better": mod.BETTER,
                     "source": mod.SOURCE, "layer": mod.LAYER,
                     "moves": mod.MOVES, "workloads": [CELL]}
    assert (unit, better, source, layer, "tpot_p90_ms") == (
        mod.UNIT, mod.BETTER, mod.SOURCE, mod.LAYER, mod.MOVES)


def test_the_cell_and_its_files_are_found_with_no_edit():
    resolved = cells.resolve(BENCH, CELL)
    assert resolved["config"] == CONFIG and resolved["cell"] == {
        "name": CELL, "config": NAME, "traffic": "serve-chat-96",
        "chips": 1, "why": resolved["cell"]["why"]}
    assert len(resolved["cell"]["why"]) <= 200
    assert resolved["cell"] is entry_of("workloads", CELL)
    four = sum(w["chips"] == 4 for w in BENCH["workloads"])
    assert four <= max(1, len(BENCH["workloads"]) // 4)
    assert hasattr(resolved["ref"], "served_logits")
    assert hasattr(resolved["ref"], "init_params")
    assert cells.resolve_callable(CONFIG["serve_builder"]).__name__ \
        == "build_serve"
    listed = [m["name"] for m in resolved["per_layer"]]
    assert sorted(listed) == sorted(
        set(NEW) | set(APPENDED) | {"cache_misses", "window_compiles"})
    for name in APPENDED:
        assert CELL in entry_of("per_layer", name)["workloads"]
    assert [m["name"] for m in resolved["end_to_end"]] == ["tpot_p90_ms",
                                                           "setup_s"]
    traffic = resolved["traffic"]
    assert traffic["runner"] == "benchmark.serve:run_cell"
    assert traffic["engine"] == {
        "slots": 96, "page_size": 16, "capacity": 2816,
        "kv_pool_bytes": 3_000_000_000, "kv_expected_tokens": 768}
    assert traffic["prompt_tokens"] == {"median": 384, "sigma": 0.9,
                                        "min": 32, "max": 2048}
    assert traffic["answer_tokens"] == {"median": 256, "sigma": 0.7,
                                        "min": 32, "max": 768}
    assert traffic["warmup_prompt_tokens"] == [32, 64, 128, 256, 512, 1024,
                                               2048]
    assert traffic["check_requests"] == 4 and traffic["trace_seconds"] == 3.0
    assert traffic["order_seed"] == 46 and traffic["rate_per_s"] > 0
    assert "sweep" in traffic["rate_note"]
    # The longest request fits a slot, and the mix's every prompt a bucket
    # the set-up has warmed.
    assert (traffic["prompt_tokens"]["max"] + traffic["answer_tokens"]["max"]
            <= traffic["engine"]["capacity"])
    for kind in ("refs", "flops", "builders"):
        name = "olmo_hybrid" if kind == "builders" else NAME
        assert os.path.isfile(os.path.join(cells.HERE, kind, name + ".py"))


PEAKS = {"bf16_flops": 197e12, "hbm_bytes_per_s": 819e9}
A_SLOT = 12 * 2_280_960


def _window(iters=1500, alive=55, prefills=250, prefill_tokens=140000,
            seconds=45.0):
    after = {"serving.decode_iterations": {"value": 5 + iters},
             "serving.state_bytes_moved":
                 {"value": 2 * alive * A_SLOT * iters},
             "serving.shared_kv_tokens": {"value": alive * 700 * iters},
             "serving.tokens_generated":
                 {"value": alive * iters + prefills},
             "serving.prefills": {"value": prefills},
             "serving.prefill_tokens": {"value": prefill_tokens},
             "serving.kv_group_pages_peak.full": {"value": 2400},
             "serving.kv_group_pages_total.full": {"value": 3050},
             "trace.span_seconds.serve.prefill": _hist(prefills, 14.0),
             "serving.token_seconds": _hist(iters, seconds)}
    before = {"serving.decode_iterations": {"value": 5},
              "serving.token_seconds": _hist(0, 0.0)}
    return _run(before=before, after=after, config=CONFIG, flops=FLOPS,
                peaks=PEAKS, notes={}, trace=None, requests=[],
                traffic=cells._load_json("traffic", "serve-chat-96"))


def _traced(r, ops, busy=2.9, window=3.0, span=60.0):
    r.trace = {"busy_s": busy, "window_s": window, "ops": ops}
    r.requests = [types.SimpleNamespace(ok=True, due=100.0, responded=101.0),
                  types.SimpleNamespace(ok=True, due=100.0 + span - 1,
                                        responded=100.0 + span)]
    return r


def test_the_shared_counter_readers_fit_the_cell_as_they_stand():
    r = _window()
    # 55 slots alive: 2 x 55 x 27.37 MB = 3011 MB an iteration.
    assert _reader("state_mb_per_iter").read(r) == pytest.approx(
        2 * 55 * A_SLOT / 1e6)
    assert _reader("prefill_ms_per_ktok").read(r) == pytest.approx(
        1e6 * 14.0 / 140000)
    assert _reader("full_pages_peak_pct").read(r) == pytest.approx(
        100 * 2400 / 3050)
    # steady_decode_hbm_roofline takes this file's arithmetic through the
    # hybrid reader: 12.8 GB over 819 GB/s over a 30 ms pass.
    least = FLOPS.decode_iteration_bytes(MODEL, 55 * 700, 0, 55.0)
    share = _reader("hybrid_decode_hbm_roofline").read(r)
    assert share == pytest.approx(100 * least / 819e9 / 0.030)
    assert 45 < share < 60
    assert _reader("decode_hbm_roofline").read(r) is None


def test_the_trace_readers_on_hand_made_ops():
    ops = {"fusion": 1.5, "gdn_step": 0.6, "gdn_chunk_scan": 0.2,
           "gqa_paged_attn": 0.4, "copy": 0.2}
    r = _traced(_window(), ops)
    assert _reader("gdn_step_time_pct").read(r) == pytest.approx(
        100 * 0.6 / 2.9)
    assert _reader("gdn_chunk_time_pct").read(r) == pytest.approx(
        100 * 0.2 / 2.9)
    # 1500 iterations x 55 slots x 12 layers of 2 x 2,211,840 B, a
    # twentieth of the window traced, over 0.6 s of kernel time.
    least = 1500 * 55 * 12 * 2 * 2_211_840 / 819e9 * 3.0 / 60.0
    step = _reader("gdn_step_roofline").read(r)
    assert step == pytest.approx(100 * least / 0.6)
    assert 0 < step < 100
    # 140,000 prompt tokens in each of 12 layers at 5.5 MFLOP a token.
    bound = FLOPS.gdn_chunk_flops(MODEL, 12 * 140000) / 197e12
    chunk = _reader("gdn_chunk_roofline").read(r)
    assert chunk == pytest.approx(100 * bound * 3.0 / 60.0 / 0.2)
    assert 0 < chunk < 100


@pytest.mark.parametrize("name", ["gdn_step_roofline", "gdn_chunk_roofline"])
def test_more_kernel_time_than_the_work_needs_reads_under_100(name):
    """A kernel given a hundred times the time its work needs reads a
    hundredth; one given none reads nothing."""
    op = "gdn_step" if name == "gdn_step_roofline" else "gdn_chunk_scan"
    fast = _reader(name).read(_traced(_window(), {op: 0.01, "fusion": 2.0}))
    slow = _reader(name).read(_traced(_window(), {op: 1.0, "fusion": 2.0}))
    assert slow == pytest.approx(fast / 100) and slow < 100
    assert _reader(name).read(_traced(_window(), {"fusion": 2.0})) is None
    assert _reader(name).read(_traced(_window(), {op: 0.0})) is None


@pytest.mark.parametrize("name", sorted(NEW))
def test_nothing_to_read_reads_nothing(name):
    """The parent commit's program has no such counter and no such op, and
    another cell's flops file no such kernel: the reader returns ``None``
    and does not raise."""
    other = cells.resolve(BENCH, "granite4h-serve-sessions")
    r = _run(before={"serving.decode_iterations": {"value": 5},
                     "serving.token_seconds": _hist(0, 0.0)},
             after={"serving.decode_iterations": {"value": 55},
                    "serving.token_seconds": _hist(50, 0.4),
                    "serving.tokens_generated": {"value": 90},
                    "serving.state_bytes_moved": {"value": 10 ** 9},
                    "serving.prefill_tokens": {"value": 900},
                    "serving.prefills": {"value": 9}},
             config=other["config"], flops=other["flops"],
             traffic=other["traffic"], peaks=PEAKS, notes={}, requests=[],
             trace={"busy_s": 1.0, "window_s": 3.0,
                    "ops": {"fusion": 1.0, "ssd_step": 0.1}})
    assert _reader(name).read(r) is None
    r.trace = None
    assert _reader(name).read(r) is None
    # This configuration's own flops, and a program that counted nothing
    # (the PARENT's program under this PR's benchmark files).
    r = _traced(_run(before={}, after={}, config=CONFIG, flops=FLOPS,
                     traffic=other["traffic"], peaks=PEAKS, notes={}),
                {"fusion": 1.0})
    assert _reader(name).read(r) is None


def test_the_toy_fixture_of_the_family_runs_through_the_harness(capsys):
    bench = dict(BENCH)
    bench["workloads"] = [{"name": "tiny-olmohybrid-serve",
                           "config": "tiny-olmohybrid",
                           "traffic": "tiny-serve-chat-96", "chips": 1,
                           "why": "fixture"}]
    bench["end_to_end"] = [dict(m, workloads=["tiny-olmohybrid-serve"])
                           if "workloads" in m else m
                           for m in BENCH["end_to_end"]]
    line = json.loads(run.measure(
        "tiny-olmohybrid-serve", 2_147_483_999, 1.0, False,
        device.device_info(), time.perf_counter(), bench=bench,
        base=FIXTURES))
    assert line["correct"] is True and line["failed"] == 0
    assert set(line["metrics"]) == {"tpot_p50_ms", "tpot_p90_ms", "setup_s"}
    earlier = capsys.readouterr().out
    compared = [json.loads(l.split(" ", 1)[1]) for l in earlier.splitlines()
                if l.startswith("benchmark:compared")][0]
    gap = [c for c in compared["compared"]
           if c["number"] == "served_gap_max"][0]
    assert gap["inside"] and gap["value"] < 1e-3
