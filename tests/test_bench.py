"""Guard the driver-facing bench artifact: `python bench.py --smoke` must
emit exactly one parseable JSON line with the contract fields, whatever
else happens (the driver records this output verbatim)."""

import json
import os
import subprocess
import sys

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _run(script, *args, env=None, timeout=300):
    return subprocess.run(
        [sys.executable, *script, *args], env=env or dict(os.environ),
        cwd=REPO, capture_output=True, text=True, timeout=timeout)


def _json_lines(text):
    return [ln for ln in text.splitlines() if ln.strip().startswith("{")]


def test_bench_smoke_is_one_process_and_one_json_line():
    """`python bench.py --smoke` (the CPU sanity check): one JSON line
    naming the platform, and no child process — spawning is made to
    raise once the package (whose first import may build the native
    library) is loaded."""
    wrapper = (
        "import runpy, subprocess, sys, os\n"
        "import horovod_tpu\n"
        "def refuse(*a, **k):\n"
        "    raise RuntimeError('bench.py started a child process')\n"
        "subprocess.Popen = refuse\n"
        "os.fork = os.posix_spawn = os.system = refuse\n"
        "sys.argv = ['bench.py', '--smoke']\n"
        "runpy.run_path('bench.py', run_name='__main__')\n")
    proc = _run(["-c", wrapper])
    assert proc.returncode == 0, proc.stderr[-2000:]
    lines = _json_lines(proc.stdout)
    assert len(lines) == 1, proc.stdout
    payload = json.loads(lines[0])
    for key in ("metric", "value", "unit", "vs_baseline", "device"):
        assert key in payload, payload
    assert payload["value"] > 0
    assert payload["device"]["platform"] == "cpu"
    assert "mfu" not in payload  # a TPU peak means nothing on the CPU


def test_bench_chip_path_refuses_the_cpu():
    """Without --smoke the run measures on the chip: on a CPU it exits
    non-zero, says which platform it found and prints no result."""
    proc = _run(["bench.py"], timeout=120)
    assert proc.returncode != 0
    assert "platform 'cpu'" in proc.stderr, proc.stderr[-2000:]
    assert not _json_lines(proc.stdout), proc.stdout


def test_peak_table_is_exact_and_unknown_kind_raises():
    sys.path.insert(0, REPO)
    try:
        import bench
    finally:
        sys.path.remove(REPO)
    assert bench.chip_peak_flops("TPU v5 lite") == 197e12
    for kind in ("TPU v9", "tpu v5 lite", "v5e", "cpu"):
        with pytest.raises(RuntimeError, match="PEAK_BF16_FLOPS"):
            bench.chip_peak_flops(kind)


def test_bench_control_mode_contract_and_speedup():
    """`--mode control` (round 6): the control-plane microbench emits
    one contract JSON line — no XLA, no chip, so it is fast enough
    for tier-1 — and the response cache must show a real speedup (the
    CI job gates at 2x; this asserts a loaded-machine-safe floor —
    a saturated single-core box has measured 1.26x in-suite against
    ~5x quiet, so the floor stays below that)."""
    proc = subprocess.run(
        [sys.executable, os.path.join(REPO, "bench.py"),
         "--mode", "control", "--control-seconds", "0.5"],
        env=dict(os.environ), cwd=REPO, capture_output=True, timeout=120)
    assert proc.returncode == 0, proc.stderr.decode()[-2000:]
    lines = [ln for ln in proc.stdout.decode().splitlines()
             if ln.strip().startswith("{")]
    assert len(lines) == 1, proc.stdout.decode()
    payload = json.loads(lines[0])
    for key in ("metric", "value", "unit", "vs_baseline", "cache_on",
                "cache_off", "speedup"):
        assert key in payload, payload
    assert payload["metric"] == "control_plane_negotiations_per_sec"
    assert payload["cache_on"] > 0 and payload["cache_off"] > 0
    assert payload["speedup"] >= 1.2, payload
    # hvd-telemetry overhead A/B rides the JSON (ISSUE 4 gate): both
    # rates present, the pct computed, and the counters attached.  The
    # ok-boolean itself is asserted by CI on a quiet box, not here — a
    # loaded tier-1 machine can fake either direction.
    tel = payload["telemetry"]
    assert tel["cache_on_metrics_on"] > 0
    assert tel["cache_on_metrics_off"] > 0
    assert "overhead_pct" in tel and "overhead_ok" in tel
    assert isinstance(tel["counters"], dict)
    # hvd-trace overhead A/B rides the same JSON (ISSUE 10 gate, same
    # quiet-box caveat for the ok-boolean).
    tr = payload["trace"]
    assert tr["trace_on"] > 0 and tr["trace_off"] > 0
    assert "overhead_pct" in tr and "overhead_ok" in tr
    # Tree-overlay section (thousand-rank control plane): rank-0 rx
    # frames per simulated cycle must be structurally sub-linear —
    # one merged envelope per direct child, bounded by
    # fanout*log_fanout(world) — at every simulated world size.
    tree = payload["tree"]
    assert {w["world"] for w in tree["worlds"]} == {64, 256, 1024}
    for w in tree["worlds"]:
        assert w["tree_frames_per_cycle"] <= 2 * w["fanout_log_bound"]
        assert w["tree_frames_per_cycle"] * 4 \
            <= w["flat_frames_per_cycle"]
        assert w["negotiations_per_sec"] > 0


def test_bench_dataplane_mode_contract_and_gates():
    """`--mode dataplane` (this round): the data-plane microbench emits
    one contract JSON line — CPU-only like `--mode control`, so it is
    fast enough for tier-1 — and must clear the DETERMINISTIC gates:
    ≥ 2x dispatches/cycle reduction, bitwise identity, hierarchical ≡
    flat psum.  The throughput gate (`--check-speedup`) lives in the CI
    `dataplane-bench` job only: wall-clock ratios on a loaded shared
    box are noise (measured 3.9–10.6x quiet vs ~1x under a concurrent
    test run), and tier-1 must not flake on them."""
    proc = subprocess.run(
        [sys.executable, os.path.join(REPO, "bench.py"),
         "--mode", "dataplane"],
        env=dict(os.environ), cwd=REPO, capture_output=True, timeout=300)
    assert proc.returncode == 0, proc.stderr.decode()[-2000:]
    lines = [ln for ln in proc.stdout.decode().splitlines()
             if ln.strip().startswith("{")]
    assert len(lines) == 1, proc.stdout.decode()
    payload = json.loads(lines[0])
    for key in ("metric", "value", "unit", "vs_baseline", "eager_us",
                "megakernel_us", "speedup", "dispatches_per_cycle",
                "dispatch_reduction", "bitwise_identical",
                "hierarchical_equal"):
        assert key in payload, payload
    assert payload["metric"] == "dataplane_fused_cycle_latency_us"
    assert payload["dispatches_per_cycle"]["megakernel"] >= 1
    assert payload["dispatch_reduction"] >= 2.0, payload
    assert payload["bitwise_identical"] is True, payload
    assert payload["hierarchical_equal"] is True, payload
    # hvd-telemetry overhead A/B rides this JSON too (ISSUE 4): the
    # megakernel counters must show real launches were accounted.
    tel = payload["telemetry"]
    assert tel["megakernel_us_metrics_off"] > 0
    assert "overhead_pct" in tel
    assert tel["counters"].get("megakernel.launches", 0) >= 1, tel
    # hvd-trace overhead A/B on the same leg (ISSUE 10).
    tr = payload["trace"]
    assert tr["megakernel_us_trace_off"] > 0
    assert "overhead_pct" in tr and "overhead_ok" in tr
    # Bytes-on-wire accounting (ISSUE 6): per-compressor legs with
    # logical vs wire bytes per cycle, the compression ratio, the
    # eager-reference equality verdict, and the dispatch count proving
    # the quantize pipeline stayed inside the one fused executable.
    # Deterministic gates only — the throughput floor lives in CI.
    compression = payload["compression"]
    for codec in ("none", "int8", "int4"):
        leg = compression[codec]
        for key in ("cycle_us", "speedup_vs_uncompressed",
                    "dispatches_per_cycle", "logical_bytes_per_cycle",
                    "wire_bytes_per_cycle", "compression_ratio",
                    "reference_equal"):
            assert key in leg, (codec, leg)
        assert leg["dispatches_per_cycle"] == 1, (codec, leg)
    assert compression["none"]["compression_ratio"] == 1.0
    assert compression["int8"]["compression_ratio"] >= 3.0, compression
    assert compression["int4"]["compression_ratio"] >= 6.0, compression
    assert compression["int8"]["reference_equal"] is True, compression
    assert compression["int4"]["reference_equal"] is True, compression
    assert compression["int8"]["wire_bytes_per_cycle"] \
        < compression["none"]["wire_bytes_per_cycle"]
    assert tel["counters"].get("compression.ratio", 0) >= 1.0, tel


def test_bench_fused_mode_contract_and_gates():
    """`--mode fused` (this round): the hvd-fuse microbench emits one
    contract JSON line — CPU-only like the other microbenches — and
    must clear the DETERMINISTIC gates: every fused program bitwise-
    identical to its unfused reference, exactly ONE XLA dispatch per
    fused group on both legs, and the HVD_TPU_FUSE=off fallback pinning
    the reference bytes.  The exposed-communication strictly-below gate
    is wall-clock (XLA:CPU thunk-runtime overlap under a loaded tier-1
    box is not guaranteed) — it lives in the CI `fused-bench` job; here
    only the measurement's presence and shape are asserted."""
    proc = subprocess.run(
        [sys.executable, os.path.join(REPO, "bench.py"),
         "--mode", "fused"],
        env=dict(os.environ), cwd=REPO, capture_output=True, timeout=300)
    assert proc.returncode == 0, proc.stderr.decode()[-2000:]
    lines = [ln for ln in proc.stdout.decode().splitlines()
             if ln.strip().startswith("{")]
    assert len(lines) == 1, proc.stdout.decode()
    payload = json.loads(lines[0])
    for key in ("metric", "value", "unit", "vs_baseline", "exposed_comm",
                "bitwise", "dispatches_per_fused_group", "chunks"):
        assert key in payload, payload
    assert payload["metric"] == "fused_exposed_comm_us"
    for name, ok in payload["bitwise"].items():
        assert ok is True, (name, payload["bitwise"])
    for leg, disp in payload["dispatches_per_fused_group"].items():
        assert disp == 1, (leg, payload["dispatches_per_fused_group"])
    ec = payload["exposed_comm"]
    for key in ("unfused_us", "fused_us", "hidden_pct",
                "strictly_below"):
        assert key in ec, ec
    assert ec["unfused_us"] >= 0 and ec["fused_us"] >= 0
    assert payload["chunks"] >= 1
    tel = payload["telemetry"]
    assert tel["groups_compiled"] >= 1 and tel["launches"] >= 1, tel


def test_bench_input_mode_contract_and_identity():
    """`--mode input` (this round): the input-pipeline microbench emits
    one contract JSON line — CPU-only like the other microbenches — and
    must clear the DETERMINISTIC gate: bitwise-identical trained params
    prefetch on vs off (overlap reorders host work, never arithmetic).
    The ≥ 1.3x throughput gate lives in the CI `input-bench` job; here
    only a loaded-box-safe floor is asserted (wall-clock ratios under a
    concurrent tier-1 run are noise)."""
    proc = subprocess.run(
        [sys.executable, os.path.join(REPO, "bench.py"),
         "--mode", "input"],
        env=dict(os.environ), cwd=REPO, capture_output=True, timeout=300)
    assert proc.returncode == 0, proc.stderr.decode()[-2000:]
    lines = [ln for ln in proc.stdout.decode().splitlines()
             if ln.strip().startswith("{")]
    assert len(lines) == 1, proc.stdout.decode()
    payload = json.loads(lines[0])
    for key in ("metric", "value", "unit", "vs_baseline", "prefetch_on",
                "prefetch_off", "speedup", "params_identical",
                "loader_delay_ms"):
        assert key in payload, payload
    assert payload["metric"] == "input_pipeline_steps_per_sec"
    assert payload["prefetch_on"] > 0 and payload["prefetch_off"] > 0
    assert payload["params_identical"] is True, payload
    # Host-overlap must not LOSE throughput even on a loaded box.
    assert payload["speedup"] >= 0.9, payload
    tel = payload["telemetry"]
    assert tel["batches_staged"] and tel["batches_staged"] > 0


def test_bench_serving_mode_contract_and_determinism():
    """`--mode serving` (this round): the hvd-serve microbench emits one
    contract JSON line and must clear BOTH deterministic gates: the
    continuous and static schedulers produce identical completions
    (batch-composition invariance), and the engine rollout is bitwise-
    equal to the non-incremental forward.  The ≥ 1.5x tokens/sec gate
    lives in the CI `serving-bench` job; here only a loaded-box-safe
    floor is asserted.  Quick-size traces (the deterministic gates hold
    at any trace size); the CI job runs the full trace."""
    env = dict(os.environ)
    env["HVD_TPU_BENCH_SERVING_QUICK"] = "1"
    proc = subprocess.run(
        [sys.executable, os.path.join(REPO, "bench.py"),
         "--mode", "serving"],
        env=env, cwd=REPO, capture_output=True, timeout=420)
    assert proc.returncode == 0, proc.stderr.decode()[-2000:]
    lines = [ln for ln in proc.stdout.decode().splitlines()
             if ln.strip().startswith("{")]
    assert len(lines) == 1, proc.stdout.decode()
    payload = json.loads(lines[0])
    for key in ("metric", "value", "unit", "vs_baseline", "continuous",
                "static", "speedup", "results_identical",
                "bitwise_identical"):
        assert key in payload, payload
    assert payload["metric"] == "serving_tokens_per_sec"
    assert payload["results_identical"] is True, payload
    assert payload["bitwise_identical"] is True, payload
    for leg in ("continuous", "static"):
        assert payload[leg]["tokens_per_sec"] > 0
        assert payload[leg]["ttft_ms"]["p50"] > 0
        assert payload[leg]["token_ms"]["p99"] >= \
            payload[leg]["token_ms"]["p50"]
    # Both legs generate the same token count from the same trace.
    assert payload["continuous"]["tokens"] == payload["static"]["tokens"]
    # Continuous batching must not LOSE throughput even on a loaded box.
    assert payload["speedup"] >= 0.9, payload


@pytest.mark.slow
def test_bench_overlap_mode_contract_and_identity():
    """`--mode overlap` (this round): the backward/communication-overlap
    microbench emits one contract JSON line and must clear every
    bitwise gate — overlapped ≡ monolithic (streaming schedule),
    overlapped ≡ serialized (segmented schedule, incl. under int8 wire
    quantization: per-bucket EF residuals).  The throughput floor lives
    in the CI `overlap-bench` job; wall-clock ratios under a concurrent
    tier-1 run are noise, so none is asserted here (the overlap win
    needs a real accelerator mesh — on the CPU mesh the two legs do the
    same work on one shared thread pool).  Quick-size like the pipeline
    test: the bitwise gates hold at any chain size and compile time
    dominates the full-size run; the CI `overlap-bench` job runs full.
    Slow-marked: even quick-size, XLA compile of the schedule variants
    is ~100 s on a 1-core box — the tier-1 time budget can't carry it,
    and both the CI `full` leg and the `overlap-bench` job still run
    every gate."""
    env = dict(os.environ)
    env["HVD_TPU_BENCH_OVERLAP_QUICK"] = "1"
    proc = subprocess.run(
        [sys.executable, os.path.join(REPO, "bench.py"),
         "--mode", "overlap"],
        env=env, cwd=REPO, capture_output=True, timeout=540)
    assert proc.returncode == 0, proc.stderr.decode()[-2000:]
    lines = [ln for ln in proc.stdout.decode().splitlines()
             if ln.strip().startswith("{")]
    assert len(lines) == 1, proc.stdout.decode()
    payload = json.loads(lines[0])
    for key in ("metric", "value", "unit", "vs_baseline", "overlapped",
                "serialized", "monolithic", "speedup",
                "bitwise_identical", "serial_identical",
                "segmented_close", "int8", "buckets", "segments"):
        assert key in payload, payload
    assert payload["metric"] == "overlap_steps_per_sec"
    assert payload["overlapped"] > 0 and payload["serialized"] > 0 \
        and payload["monolithic"] > 0
    assert payload["bitwise_identical"] is True, payload
    assert payload["plain_close"] is True, payload
    assert payload["serial_identical"] is True, payload
    assert payload["segmented_close"] is True, payload
    assert payload["int8"]["bitwise_identical"] is True, payload
    assert payload["int8"]["quantized_active"] is True, payload
    # The np=2 mp leg rides the JSON ('skipped' in the quick shape).
    assert payload["mp"]["status"] in ("ok", "skipped"), \
        payload["mp"]
    # The transformer chain really segmented and streamed per bucket.
    assert payload["segments"] > 1 and payload["buckets"] > payload["segments"]
    tel = payload["telemetry"]
    assert tel["buckets_dispatched"] and tel["buckets_dispatched"] > 0
    assert tel["fallbacks"] == 0, payload


def test_bench_pipeline_mode_contract_and_identity():
    """`--mode pipeline` (this round): the 1F1B MPMD pipeline-schedule
    microbench emits one contract JSON line and must clear the
    deterministic gates — 1f1b params/loss bitwise ≡ the GPipe-ordered
    dispatch of the same per-stage executables, allclose vs the
    monolithic microbatch-mean gradient, and the exposed-bubble
    seconds strictly below the gpipe leg (the gpipe leg pays fence +
    serialized dispatch + reduction inside the measured window, so the
    ordering survives a loaded box).  The steps/sec floor lives in the
    CI `pipeline-bench` job."""
    env = dict(os.environ)
    env["HVD_TPU_BENCH_PIPELINE_QUICK"] = "1"
    proc = subprocess.run(
        [sys.executable, os.path.join(REPO, "bench.py"),
         "--mode", "pipeline"],
        env=env, cwd=REPO, capture_output=True, timeout=540)
    assert proc.returncode == 0, proc.stderr.decode()[-2000:]
    lines = [ln for ln in proc.stdout.decode().splitlines()
             if ln.strip().startswith("{")]
    assert len(lines) == 1, proc.stdout.decode()
    payload = json.loads(lines[0])
    for key in ("metric", "value", "unit", "vs_baseline",
                "schedule_1f1b", "schedule_gpipe", "speedup",
                "bitwise_identical", "reference_close",
                "exposed_bubble_seconds_per_step", "bubble_hidden",
                "plan", "buckets"):
        assert key in payload, payload
    assert payload["metric"] == "pipeline_steps_per_sec"
    assert payload["schedule_1f1b"] > 0 and payload["schedule_gpipe"] > 0
    assert payload["bitwise_identical"] is True, payload
    assert payload["reference_close"] is True, payload
    assert payload["bubble_hidden"] is True, payload
    plan = payload["plan"]
    # 1F1B's memory bound: peak in-flight activations below GPipe's.
    assert plan["peak_activations_1f1b"] < plan["peak_activations_gpipe"]
    assert payload["buckets"] >= plan["n_stages"]


def test_bench_memory_mode_contract_and_gates():
    """`--mode memory` (this round): the hvd-mem microbench emits one
    contract JSON line and must clear its deterministic gates — the
    planner's framework-bytes prediction within ±15 % of the measured
    ledger high-watermark on both legs, byte-identical plans for
    identical configs, and the seeded RESOURCE_EXHAUSTED producing a
    forensic dump naming the executable and ≥3 ledger categories."""
    env = dict(os.environ)
    proc = subprocess.run(
        [sys.executable, os.path.join(REPO, "bench.py"),
         "--mode", "memory", "--check-memory-plan", "15"],
        env=env, cwd=REPO, capture_output=True, timeout=540)
    assert proc.returncode == 0, proc.stderr.decode()[-2000:]
    lines = [ln for ln in proc.stdout.decode().splitlines()
             if ln.strip().startswith("{")]
    assert len(lines) == 1, proc.stdout.decode()
    payload = json.loads(lines[0])
    for key in ("metric", "value", "unit", "dataplane", "pipeline",
                "plan_deterministic", "oom_dump",
                "ledger_overhead_pct"):
        assert key in payload, payload
    assert payload["metric"] == "memory_plan_prediction_error_pct"
    for leg in ("dataplane", "pipeline"):
        err = payload[leg]["prediction_error_pct"]
        assert err is not None and err <= 15.0, payload
    assert payload["plan_deterministic"] is True
    oom = payload["oom_dump"]
    assert oom["ok"] is True and oom["executable"], payload
    assert len(oom["top_categories"]) >= 3, payload


def test_bench_routing_mode_contract_and_gates():
    """`--mode routing` (this round): the hvd-route microbench is pure
    Python (router + autoscaler + queueing sim — no XLA, no chip), so
    the full smoke trace with every --check-speedup gate armed fits
    tier-1: least-loaded+affinity beats round-robin on p99 TTFT AND
    tokens/sec, the failover leg's merged completions are
    digest-identical to the single-replica reference, and the
    autoscale leg boots/seeds/vetoes/drains planner-priced."""
    proc = subprocess.run(
        [sys.executable, os.path.join(REPO, "bench.py"),
         "--mode", "routing", "--smoke", "--check-speedup", "1.3"],
        env=dict(os.environ), cwd=REPO, capture_output=True,
        timeout=300)
    assert proc.returncode == 0, proc.stderr.decode()[-2000:]
    lines = [ln for ln in proc.stdout.decode().splitlines()
             if ln.strip().startswith("{")]
    assert len(lines) == 1, proc.stdout.decode()
    payload = json.loads(lines[0])
    for key in ("metric", "value", "unit", "vs_baseline",
                "round_robin", "affinity", "p99_ttft_speedup",
                "tokens_per_sec_speedup", "affinity_hit_rate",
                "deterministic_replay", "failover", "autoscale"):
        assert key in payload, payload
    assert payload["metric"] == "routing_tokens_per_sec"
    assert payload["value"] > 0
    # The gates themselves ran inside the subprocess (exit 0 above);
    # re-assert the headline ones on the parsed payload.
    assert payload["p99_ttft_speedup"] >= 1.3, payload
    assert payload["tokens_per_sec_speedup"] >= 1.3, payload
    assert payload["affinity_hit_rate"] > 0, payload
    assert payload["deterministic_replay"] is True
    assert payload["failover"]["digest_identical"] is True
    assert payload["failover"]["continuations"] >= 1
    assert payload["autoscale"]["scaled_up"] is True
    assert payload["autoscale"]["veto"] is True
    assert payload["autoscale"]["oom_free"] is True
    # Both policies place the same trace: same request count, different
    # placements (the digest distinguishes them).
    assert payload["round_robin"]["placement_digest"] != \
        payload["affinity"]["placement_digest"]
