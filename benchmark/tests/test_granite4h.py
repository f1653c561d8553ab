"""What ISSUE 39 adds to the benchmark: the configuration file against the
catalog's keys, the counts of ``flops/granite-4.0-h-micro.py`` at the
published widths, the five readers on hand-made run records (and ``None``
where the program has no such counter or kernel, as the parent commit has
not), the cell and its files found by ``cells.py`` with no edit, and the
toy fixture of the family driven through the harness on the CPU."""

import json
import os
import time
import types

import pytest

from benchmark import cells, device, run
from benchmark.tests.test_span_metrics import _hist, _reader, _run
import conftest
from conftest import FIXTURES

# As test_axk1.py and test_phi4flash.py do: conftest's rename table ends at
# PR 23's cells and is an accepted file this PR may not edit.
conftest._RENAME.setdefault("axk1-serve-decode", "tiny-axk1-serve")
conftest._RENAME.setdefault("phi4flash-serve-reason", "tiny-phi4flash-serve")
conftest._RENAME.setdefault("longcat-serve-turns", "tiny-longcat-serve")
conftest._RENAME.setdefault("granite4h-serve-sessions",
                            "tiny-granite4h-serve")

BENCH = cells.load_benchmark()
CELL = "granite4h-serve-sessions"
FLOPS = cells.load_module("flops", "granite-4.0-h-micro")
with open(os.path.join(cells.HERE, "configs",
                       "granite-4.0-h-micro.json")) as f:
    CONFIG = json.load(f)
MODEL = CONFIG["model"]
NEW = {  # name -> (unit, better, source, layer)
    "ssd_step_time_pct": ("%", "lower", "device_trace", "state-space layers"),
    "ssd_chunk_time_pct": ("%", "lower", "device_trace",
                           "state-space layers"),
    "ssd_step_roofline": ("%", "higher", "device_trace",
                          "state-space layers"),
    "ssd_chunk_roofline": ("%", "higher", "device_trace",
                           "state-space layers"),
    "state_mb_per_iter": ("MB", "lower", "program_counter", "serving")}
APPENDED = ["warm_start_s", "prefill_ahead_pct", "steady_pass_ms",
            "admission_pass_ms", "admission_time_pct",
            "tables_after_admission_ms", "tables_h2d_kb_per_pass",
            "tpot_admission_p90_ms", "prefill_ms_per_ktok",
            "hybrid_decode_hbm_roofline", "steady_decode_hbm_roofline"]
CATALOG = "/opt/skills/guides/model-configs/architectures.jsonl"


def test_the_file_holds_the_published_keys_uncut():
    assert CONFIG["reduced"] == []
    if not os.path.isfile(CATALOG):
        pytest.skip("the model-configs guide's catalog is not here")
    with open(CATALOG) as f:
        (row,) = [r for r in map(json.loads, filter(str.strip, f))
                  if r["name"] == "granite-4.0-h-micro"]
    assert CONFIG["source"] == row["source_url"]
    for key, value in row["config"].items():
        assert CONFIG[key] == value, key         # the top level, as run
        assert MODEL[key] == value, key          # what builder and ref read
    assert MODEL["layer_types"] == [
        "attention" if l % 10 == 5 else "mamba" for l in range(40)]
    assert set(MODEL) - set(row["config"]) == {"dtype", "initializer_range"}
    (entry,) = [c for c in BENCH["configs"]
                if c["name"] == "granite-4.0-h-micro"]
    assert entry["reduced"] == [] and entry["source"] == CONFIG["source"]
    assert BENCH["configs"][-1] is entry
    assert set(CONFIG["assumed"]) >= {
        "A_log_dt_bias_D", "dt_clamp", "gate_before_norm", "convolution",
        "attention_head", "multipliers", "weights", "dtype", "bytes"}
    assert "ONE v5e chip" in CONFIG["deployment"]
    assert any("prefix cache is off" in d for d in CONFIG["departures"])
    assert CONFIG["control_precision"] == "fp8"
    assert 0 < CONFIG["check"]["limits"]["served_gap_max"]


def test_the_counts_are_the_issues_figures():
    p = FLOPS.param_counts(MODEL)
    assert p == {"mamba": 76_182_976, "attention": 60_821_504,
                 "embed": 205_522_944}
    assert FLOPS.layer_counts(MODEL) == {"mamba": 36, "attention": 4}
    assert FLOPS.total_params(MODEL) == 3_191_396_096
    assert round(2 * FLOPS.total_params(MODEL) / 1e9, 2) == 6.38
    assert FLOPS.position_bytes(MODEL) == 8192
    assert FLOPS.slot_state_bytes(MODEL) == 2_123_264
    stores = FLOPS.store_bytes(MODEL, 64, 3072)
    assert round(stores["state"] / 1e9, 2) == 4.89
    assert round(stores["paged"] / 1e9, 2) == 1.61
    assert round(stores["view"] / 1e9, 2) == 0.40
    # 30 slots alive at 900 cached positions: weights 6.38 GB, state 4.59,
    # pages 0.22: 11.2 GB, the state 41% of it.
    whole = FLOPS.decode_iteration_bytes(MODEL, 30 * 900, 0, 30)
    bare = FLOPS.decode_iteration_bytes(MODEL, 0, 0, 0)
    state = 2 * 30 * 36 * 2_123_264
    assert bare == 2 * 3_191_396_096
    assert whole - bare == pytest.approx(state + 30 * 900 * 8192
                                         + 30 * 2048 * 2)
    assert round(whole / 1e9, 1) == 11.2 and round(state / whole, 2) == 0.41
    assert FLOPS.decode_iteration_bytes(MODEL, 0, 123, 0) == bare
    # The kernels' own work.  One live slot in one layer: 2 x 2 MiB.
    assert FLOPS.ssd_step_bytes(MODEL, 1) == 2 * 64 * 64 * 128 * 4
    work = FLOPS.ssd_chunk_work(MODEL, 2048, 1)
    assert work["flops"] == 2048 * (2 * 256 * 128
                                    + 64 * (2 * 256 * 64 + 4 * 128 * 64))
    assert work["flops"] / 2048 == pytest.approx(4.3e6, rel=0.02)
    assert work["bytes"] == 2048 * (4096 * 6 + 512 + 1024) + 2 * 2**21
    assert [k["name"] for k in FLOPS.KERNELS] == ["ssd_step",
                                                  "ssd_chunk_scan"]


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
    assert entry in BENCH["per_layer"][-len(NEW):]


def test_the_cell_and_its_files_are_found_with_no_edit():
    resolved = cells.resolve(BENCH, CELL)
    assert resolved["config"] == CONFIG and resolved["cell"] == {
        "name": CELL, "config": "granite-4.0-h-micro",
        "traffic": "serve-sessions-64", "chips": 1,
        "why": resolved["cell"]["why"]}
    assert BENCH["workloads"][-1] is resolved["cell"]
    assert len(BENCH["workloads"]) == 8
    assert sum(w["chips"] == 4 for w in BENCH["workloads"]) == 1
    assert hasattr(resolved["ref"], "served_logits")
    assert hasattr(resolved["ref"], "init_params")
    assert cells.resolve_callable(CONFIG["serve_builder"]).__name__ \
        == "build_serve"
    listed = [m["name"] for m in resolved["per_layer"]]
    assert sorted(listed) == sorted(
        set(NEW) | set(APPENDED) | {"cache_misses", "window_compiles"})
    for name in APPENDED:
        (entry,) = [m for m in BENCH["per_layer"] if m["name"] == name]
        assert entry["workloads"][-1] == CELL
    assert [m["name"] for m in resolved["end_to_end"]] == ["tpot_p90_ms",
                                                           "setup_s"]
    traffic = resolved["traffic"]
    assert traffic["engine"] == {"slots": 64, "page_size": 16,
                                 "capacity": 3072}
    assert traffic["prompt_tokens"] == {"median": 512, "sigma": 0.9,
                                        "min": 64, "max": 2048}
    assert traffic["answer_tokens"] == {"median": 256, "sigma": 0.7,
                                        "min": 32, "max": 768}
    assert traffic["warmup_prompt_tokens"] == [64, 128, 256, 512, 1024, 2048]
    assert traffic["check_requests"] == 4 and traffic["trace_seconds"] == 3.0
    assert traffic["order_seed"] == 39 and traffic["rate_per_s"] > 0
    # No file the parent's benchmark already had is shadowed.
    for kind, name in (("refs", "granite-4.0-h-micro"),
                       ("flops", "granite-4.0-h-micro")):
        assert os.path.isfile(os.path.join(cells.HERE, kind, name + ".py"))


PEAKS = {"bf16_flops": 197e12, "hbm_bytes_per_s": 819e9}
A_SLOT = 36 * 2_123_264


def _window(iters=2000, alive=30, prefills=200, prefill_tokens=130000,
            seconds=46.0):
    after = {"serving.decode_iterations": {"value": 5 + iters},
             "serving.state_bytes_moved":
                 {"value": 2 * alive * A_SLOT * iters},
             "serving.shared_kv_tokens": {"value": alive * 900 * iters},
             "serving.tokens_generated":
                 {"value": alive * iters + prefills},
             "serving.prefills": {"value": prefills},
             "serving.prefill_tokens": {"value": prefill_tokens},
             "trace.span_seconds.serve.prefill": _hist(prefills, 12.0),
             "serving.token_seconds": _hist(iters, seconds)}
    before = {"serving.decode_iterations": {"value": 5},
              "serving.token_seconds": _hist(0, 0.0)}
    return _run(before=before, after=after, config=CONFIG, flops=FLOPS,
                peaks=PEAKS, notes={}, trace=None, requests=[],
                traffic=cells._load_json("traffic", "serve-sessions-64"))


def _traced(r, ops, busy=2.9, window=3.0, span=60.0):
    r.trace = {"busy_s": busy, "window_s": window, "ops": ops}
    r.requests = [types.SimpleNamespace(ok=True, due=100.0, responded=101.0),
                  types.SimpleNamespace(ok=True, due=100.0 + span - 1,
                                        responded=100.0 + span)]
    return r


def test_the_counter_readers_on_a_hand_made_window():
    r = _window()
    # 30 slots alive: 2 x 30 x 76.4 MB = 4586 MB an iteration.
    assert _reader("state_mb_per_iter").read(r) == pytest.approx(
        2 * 30 * A_SLOT / 1e6)
    assert _reader("prefill_ms_per_ktok").read(r) == pytest.approx(
        1e6 * 12.0 / 130000)
    # The accepted roofline readers take this file's arithmetic: 11.2 GB
    # over 819 GB/s over a 23 ms pass.
    least = FLOPS.decode_iteration_bytes(MODEL, 30 * 900, 0, 30.0)
    share = _reader("hybrid_decode_hbm_roofline").read(r)
    assert share == pytest.approx(100 * least / 819e9 / 0.023)
    assert 55 < share < 65
    assert _reader("decode_hbm_roofline").read(r) is None


def test_the_trace_readers_on_hand_made_ops():
    ops = {"fusion": 1.6, "ssd_step": 0.9, "ssd_chunk_scan": 0.1,
           "copy": 0.3}
    r = _traced(_window(), ops)
    assert _reader("ssd_step_time_pct").read(r) == pytest.approx(
        100 * 0.9 / 2.9)
    assert _reader("ssd_chunk_time_pct").read(r) == pytest.approx(
        100 * 0.1 / 2.9)
    # 2000 iterations x 30 slots x 36 layers of 4 MiB, a twentieth of the
    # window traced, over 0.9 s of kernel time.
    least = 2000 * 30 * 36 * 2 * 2**21 / 819e9 * 3.0 / 60.0
    step = _reader("ssd_step_roofline").read(r)
    assert step == pytest.approx(100 * least / 0.9)
    assert 0 < step < 100
    work = FLOPS.ssd_chunk_work(MODEL, 36 * 130000, 36 * 200)
    bound = max(work["flops"] / 197e12, work["bytes"] / 819e9)
    assert bound == work["bytes"] / 819e9         # memory bounds it
    chunk = _reader("ssd_chunk_roofline").read(r)
    assert chunk == pytest.approx(100 * bound * 3.0 / 60.0 / 0.1)
    assert 0 < chunk < 100


@pytest.mark.parametrize("name", ["ssd_step_roofline", "ssd_chunk_roofline"])
def test_more_kernel_time_than_the_work_needs_reads_under_100(name):
    """A kernel given ten times the time its bytes need reads a tenth; one
    given none reads nothing."""
    op = "ssd_step" if name == "ssd_step_roofline" else "ssd_chunk_scan"
    fast = _reader(name).read(_traced(_window(), {op: 0.01, "fusion": 2.0}))
    slow = _reader(name).read(_traced(_window(), {op: 1.0, "fusion": 2.0}))
    assert slow == pytest.approx(fast / 100) and slow < 100
    assert _reader(name).read(_traced(_window(), {"fusion": 2.0})) is None
    assert _reader(name).read(_traced(_window(), {op: 0.0})) is None


@pytest.mark.parametrize("name", sorted(NEW))
def test_nothing_to_read_reads_nothing(name):
    """The parent commit's program has no such counter and no such op: the
    reader returns ``None`` and does not raise."""
    phi = cells.resolve(BENCH, "phi4flash-serve-reason")
    r = _run(before={"serving.decode_iterations": {"value": 5},
                     "serving.token_seconds": _hist(0, 0.0)},
             after={"serving.decode_iterations": {"value": 55},
                    "serving.token_seconds": _hist(50, 0.4),
                    "serving.tokens_generated": {"value": 90},
                    "serving.prefill_tokens": {"value": 900},
                    "serving.prefills": {"value": 9}},
             config=phi["config"], flops=phi["flops"],
             traffic=phi["traffic"], peaks=PEAKS, notes={}, requests=[],
             trace={"busy_s": 1.0, "window_s": 3.0,
                    "ops": {"fusion": 1.0, "ssm_scan": 0.1}})
    assert _reader(name).read(r) is None
    r.trace = None
    assert _reader(name).read(r) is None
    # This configuration's own flops, and a program that counted nothing
    # (the PARENT's program under this PR's benchmark files).
    r = _traced(_run(before={}, after={}, config=CONFIG, flops=FLOPS,
                     traffic=phi["traffic"], peaks=PEAKS, notes={}),
                {"fusion": 1.0})
    assert _reader(name).read(r) is None


def test_the_toy_fixture_of_the_family_runs_through_the_harness(capsys):
    bench = dict(BENCH)
    bench["workloads"] = [{"name": "tiny-granite4h-serve",
                           "config": "tiny-granite4h",
                           "traffic": "tiny-serve-sessions", "chips": 1,
                           "why": "fixture"}]
    bench["end_to_end"] = [dict(m, workloads=["tiny-granite4h-serve"])
                           if "workloads" in m else m
                           for m in BENCH["end_to_end"]]
    line = json.loads(run.measure(
        "tiny-granite4h-serve", 2_147_483_999, 1.0, False,
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
