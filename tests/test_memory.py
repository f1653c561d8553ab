"""hvd-mem tests: the device-memory ledger, the static planner, and
the OOM forensics path (horovod_tpu/memory/, docs/memory.md).

Covers the acceptance contracts directly:

* planner determinism — same config ⇒ byte-identical plan JSON;
* planner accuracy — the dataplane/pipeline predictions land within
  ±15 % of the measured ledger high-watermark on the CPU backend;
* seeded RESOURCE_EXHAUSTED (simulated small capacity) produces a
  flight dump naming the failing executable and the top ledger
  categories;
* the flight-recorder metrics tail carries gauges (memory watermarks,
  queue/occupancy) — every dump is self-contained forensics;
* ``serving.kv_free_pages`` rides the KV cache's page management and
  the engine's ``/healthz`` payload.
"""

import glob
import json
import os

import jax
import jax.numpy as jnp
import numpy as np
import optax
import pytest

import horovod_tpu.memory as M
from horovod_tpu import telemetry as _telemetry
from horovod_tpu.memory import ledger as ledger_mod
from horovod_tpu.memory import oom as oom_mod
from horovod_tpu.memory import planner


@pytest.fixture()
def fresh_ledger():
    """Isolated ledger (the process-global one keeps its history)."""
    return ledger_mod.MemoryLedger()


@pytest.fixture(autouse=True)
def _zero_prefetch_category():
    """Order-independence for the prefetch accounting tests: a stager
    from an earlier test (any module — prefetch_to_device charges the
    process-global ledger) can land its final put() after that test's
    drain window, leaving a stale "input.prefetch" residue that skews
    this module's peak/zero assertions.  Pin the category to zero on
    entry so every test starts from its own charges only."""
    ledger_mod.ledger.set("input.prefetch", 0)
    yield


# ---------------------------------------------------------------------------
# Ledger
# ---------------------------------------------------------------------------

def test_ledger_alloc_free_and_peaks(fresh_ledger):
    led = fresh_ledger
    led.alloc("a", 100)
    led.alloc("b", 50)
    assert led.total() == 150
    assert led.watermark() == 150
    led.free("a", 60)
    assert led.bytes_by_category() == {"a": 40, "b": 50}
    led.free("a", 999)  # clamped, never negative
    assert led.bytes_by_category()["a"] == 0
    assert led.peak_by_category() == {"a": 100, "b": 50}
    assert led.watermark() == 150  # all-time, survives the frees


def test_ledger_keyed_entries_are_idempotent(fresh_ledger):
    led = fresh_ledger
    led.alloc("kv", 1000, key="engine1")
    led.alloc("kv", 1000, key="engine1")  # re-alloc REPLACES
    assert led.total() == 1000
    led.alloc("kv", 500, key="engine2")
    assert led.total() == 1500
    led.free("kv", key="engine1")
    assert led.total() == 500
    led.free("kv", key="engine1")  # double free: no-op
    assert led.total() == 500


def test_ledger_set_absolute(fresh_ledger):
    led = fresh_ledger
    led.set("residuals", 400)
    led.set("residuals", 100)
    assert led.bytes_by_category()["residuals"] == 100
    assert led.peak_by_category()["residuals"] == 400


def test_ledger_step_watermark_window(fresh_ledger):
    led = fresh_ledger
    led.alloc("x", 100)
    led.free("x", 100)
    assert led.note_step() == 100   # the window saw the transient
    assert led.step_watermark() == 100
    led.alloc("y", 30)              # long-lived store
    assert led.note_step() == 30
    # next window starts at the carried-over total, not zero
    assert led.note_step() == 30
    assert led.steps() == 3


def test_ledger_top_categories(fresh_ledger):
    led = fresh_ledger
    led.alloc("big", 300)
    led.alloc("mid", 200)
    led.alloc("small", 10)
    led.alloc("zero", 0)
    top = led.top(3)
    assert top == [("big", 300), ("mid", 200), ("small", 10)]


def test_ledger_snapshot_names(fresh_ledger):
    led = fresh_ledger
    led.alloc("serving.kv_pages", 64)
    snap = led.snapshot()
    assert snap["memory.bytes.serving.kv_pages"] == 64
    assert snap["memory.ledger_bytes"] == 64
    assert snap["memory.high_watermark_bytes"] == 64


def test_tree_nbytes_counts_array_leaves():
    tree = {"a": np.zeros((4, 4), np.float32),
            "b": [np.zeros((2,), np.float64), 3, "x"]}
    assert ledger_mod.tree_nbytes(tree) == 4 * 4 * 4 + 2 * 8


# ---------------------------------------------------------------------------
# MemoryWatch
# ---------------------------------------------------------------------------

def test_memory_watch_names_leaking_category(fresh_ledger, capsys):
    w = M.MemoryWatch(patience=3, min_growth=100, ledger_=fresh_ledger)
    fired = None
    for i in range(4):
        fired = w.check({"serving.kv_pages": 1000 + i * 200,
                         "input.prefetch": 500})
    assert fired and fired[0]["category"] == "serving.kv_pages"
    assert fired[0]["growth"] == 600
    err = capsys.readouterr().err
    assert "serving.kv_pages" in err and "MemoryWatch" in err


def test_memory_watch_non_monotonic_resets_streak(fresh_ledger):
    w = M.MemoryWatch(patience=3, min_growth=0, ledger_=fresh_ledger)
    sizes = [100, 200, 150, 250, 300, 350]  # dip at step 3
    fired = [w.check({"c": s}) for s in sizes]
    # streak restarts after the dip: grows at steps 4,5,6 -> fires at
    # the THIRD consecutive growth only
    assert fired[:5] == [None] * 5
    assert fired[5] and fired[5][0]["category"] == "c"


def test_memory_watch_min_growth_filters_noise(fresh_ledger):
    w = M.MemoryWatch(patience=2, min_growth=1 << 30,
                      ledger_=fresh_ledger)
    for i in range(6):
        assert w.check({"c": 100 + i}) is None  # tiny growth: quiet


def test_memory_watch_two_leaks_two_warnings(fresh_ledger):
    w = M.MemoryWatch(patience=2, min_growth=10, ledger_=fresh_ledger)
    fired = None
    for i in range(3):
        fired = w.check({"a": 100 + i * 50, "b": 200 + i * 50})
    assert fired and {f["category"] for f in fired} == {"a", "b"}


def test_memory_watch_validates_args(fresh_ledger):
    with pytest.raises(ValueError, match="patience"):
        M.MemoryWatch(patience=1)


def test_memory_watch_reads_global_ledger_counter():
    before = _telemetry.registry().counter(
        "memory.leak_warnings").value
    w = M.MemoryWatch(patience=2, min_growth=1)
    for i in range(3):
        w.check({"c": 100 + i * 10})
    assert _telemetry.registry().counter(
        "memory.leak_warnings").value > before


# ---------------------------------------------------------------------------
# Planner
# ---------------------------------------------------------------------------

def test_plan_json_is_deterministic():
    a = planner.plan_transformer_lm(batch_size=64, world=4).to_json()
    b = planner.plan_transformer_lm(batch_size=64, world=4).to_json()
    assert a == b  # byte-identical (the CI determinism gate)
    assert json.loads(a)["format"] == planner.PLAN_FORMAT


def test_plan_cli_is_deterministic_and_parseable(capsys):
    from horovod_tpu.memory.__main__ import main

    argv = ["--plan", "--model", "serving", "--kv-slots", "16"]
    assert main(argv) == 0
    out1 = capsys.readouterr().out
    assert main(argv) == 0
    out2 = capsys.readouterr().out
    assert out1 == out2
    plan = json.loads(out1)
    assert plan["framework"]["serving.kv_pages"] == \
        planner.kv_cache_bytes(2, 8, 16, 16, 8, 16)


def test_plan_cli_fit_verdict_rc(capsys):
    from horovod_tpu.memory.__main__ import main

    rc = main(["--plan", "--model", "transformer_lm",
               "--capacity-bytes", "1"])
    assert rc == 3  # scriptable "does not fit"
    plan = json.loads(capsys.readouterr().out)
    assert plan["fits"] is False and plan["headroom_bytes"] < 0
    rc = main(["--plan", "--model", "transformer_lm",
               "--capacity-bytes", str(64 << 30)])
    assert rc == 0
    assert json.loads(capsys.readouterr().out)["fits"] is True


def test_plan_pipeline_what_if_schedule():
    """The what-if the CLI answers: GPipe's activation bound grows with
    the microbatch count, 1F1B's stays at the stage depth."""
    f = planner.plan_pipeline(4, 8, 32, 96, 1, schedule="1f1b")
    g = planner.plan_pipeline(4, 8, 32, 96, 1, schedule="gpipe")
    assert g.framework["pipeline.activations"] > \
        f.framework["pipeline.activations"]
    # the CHANGES-documented figures at S=4/m=8: 9 vs GPipe's 24
    assert f.facts["peak_activation_carries"] == 9
    assert g.facts["peak_activation_carries"] == 24


def test_plan_unknown_model_and_optimizer_name_valid_sets():
    with pytest.raises(ValueError, match="dataplane"):
        planner.build_plan("no_such_model")
    with pytest.raises(ValueError, match="adam"):
        planner.plan_transformer_lm(optimizer="adamax")


def test_dtype_bytes_table_and_errors():
    assert planner.dtype_bytes("float32") == 4
    assert planner.dtype_bytes("bfloat16") == 2
    assert planner.dtype_bytes(jnp.dtype("float16")) == 2
    with pytest.raises(ValueError, match="float32"):
        planner.dtype_bytes("floof")


def test_fusion_group_bytes_variants():
    shapes = ((16,), (4, 4))
    # per-replica: world-leading inputs AND outputs
    assert planner.fusion_group_bytes(shapes, "float32", 8, "sp_pr") \
        == 2 * 8 * 32 * 4
    # replicated: single-copy payloads
    assert planner.fusion_group_bytes(shapes, "float32", 8, "sp_rep") \
        == 2 * 32 * 4


def test_record_compiled_harvests_when_backend_supports_it():
    compiled = jax.jit(lambda x: x * 2).lower(
        jnp.zeros((8,), jnp.float32)).compile()
    got = planner.record_compiled("test/exe", compiled)
    table = planner.harvested()
    if got is None:
        # XLA:CPU without memory_analysis: honest absence, no zeros
        assert "test/exe" not in table
    else:
        assert table["test/exe"] == got
        assert all(isinstance(v, int) for v in got.values())
        sect = planner.harvest_section()
        assert sect["coverage"] >= 1
    planner.clear_harvest()


# ---------------------------------------------------------------------------
# OOM forensics
# ---------------------------------------------------------------------------

def test_is_resource_exhausted_detection():
    assert oom_mod.is_resource_exhausted(
        RuntimeError("RESOURCE_EXHAUSTED: Out of memory allocating"))
    assert oom_mod.is_resource_exhausted(
        oom_mod.ResourceExhaustedError("RESOURCE_EXHAUSTED: sim"))
    assert not oom_mod.is_resource_exhausted(ValueError("shape"))


def _reset_dump_rate_limit():
    """Dumps are rate-limited per reason on the process-global
    recorder; tests that each need their own dump clear the limiter."""
    from horovod_tpu.telemetry import flight as _flight

    with _flight.recorder._dump_lock:
        _flight.recorder._last_dump.clear()


def test_guard_simulated_capacity_dumps_and_raises(tmp_path,
                                                   monkeypatch):
    """The acceptance scenario: a seeded RESOURCE_EXHAUSTED (simulated
    small capacity) produces a flight dump naming the failing
    executable and the top-3 ledger categories."""
    _reset_dump_rate_limit()
    monkeypatch.setenv("HVD_TPU_FLIGHT_DIR", str(tmp_path))
    monkeypatch.setenv(oom_mod.CAPACITY_ENV, str(1 << 20))
    led = ledger_mod.ledger
    led.reset()
    led.alloc("serving.kv_pages", 600_000)
    led.alloc("megakernel.residuals", 300_000)
    led.alloc("input.prefetch", 200_000)
    led.alloc("checkpoint.snapshots", 1)
    try:
        with pytest.raises(oom_mod.ResourceExhaustedError,
                           match="RESOURCE_EXHAUSTED"):
            with oom_mod.guard("megakernel/psum/test",
                               predicted_bytes=500_000):
                raise AssertionError("guard must raise pre-dispatch")
        dumps = glob.glob(str(tmp_path / "*oom*"))
        assert dumps, "no flight dump written"
        payload = json.load(open(dumps[0]))
        extra = payload["extra"]
        assert extra["executable"] == "megakernel/psum/test"
        top = [t["category"] for t in extra["top_categories"]]
        assert top == ["serving.kv_pages", "megakernel.residuals",
                       "input.prefetch"]  # top-3, largest first
        assert extra["predicted_bytes"] == 500_000
        assert extra["advertised_capacity_bytes"] == 1 << 20
        # the metrics tail rides the dump: gauges included (satellite)
        assert payload["metrics"]["memory.ledger_bytes"] \
            == led.total()
    finally:
        led.reset()


def test_guard_captures_real_resource_exhausted(tmp_path, monkeypatch):
    _reset_dump_rate_limit()
    monkeypatch.setenv("HVD_TPU_FLIGHT_DIR", str(tmp_path))
    monkeypatch.delenv(oom_mod.CAPACITY_ENV, raising=False)
    with pytest.raises(RuntimeError, match="RESOURCE_EXHAUSTED"):
        with oom_mod.guard("serving/decode"):
            raise RuntimeError(
                "RESOURCE_EXHAUSTED: Out of memory while trying to "
                "allocate 123 bytes")
    dumps = glob.glob(str(tmp_path / "*oom*"))
    assert dumps
    assert json.load(open(dumps[0]))["extra"]["executable"] \
        == "serving/decode"


def test_guard_passes_other_errors_through_undumped(tmp_path,
                                                    monkeypatch):
    monkeypatch.setenv("HVD_TPU_FLIGHT_DIR", str(tmp_path))
    with pytest.raises(ValueError):
        with oom_mod.guard("pipeline/F0"):
            raise ValueError("shape mismatch")
    assert not glob.glob(str(tmp_path / "*oom*"))


def test_capacity_env_validation(monkeypatch):
    monkeypatch.setenv(oom_mod.CAPACITY_ENV, "lots")
    with pytest.raises(ValueError, match="HVD_TPU_MEM_CAPACITY"):
        oom_mod.validate_env()
    monkeypatch.setenv(oom_mod.CAPACITY_ENV, str(1 << 30))
    oom_mod.validate_env()
    assert oom_mod.advertised_capacity() == 1 << 30


def test_preflight_warn_fires_only_over_capacity(monkeypatch, capsys):
    monkeypatch.setenv(oom_mod.CAPACITY_ENV, "1000")
    assert oom_mod.preflight_warn(500, "test") is False
    assert oom_mod.preflight_warn(2000, "test", "params + grads")
    err = capsys.readouterr().err
    assert "pre-flight" in err and "horovod_tpu.memory --plan" in err


def test_live_array_report_shape():
    x = jnp.zeros((16, 16), jnp.float32)
    rep = ledger_mod.live_array_report(top_n=3)
    assert rep["live_bytes"] is None or rep["live_bytes"] >= x.nbytes
    assert isinstance(rep["top"], list)


# ---------------------------------------------------------------------------
# Flight tail + gauge aggregation (satellites)
# ---------------------------------------------------------------------------

def test_flight_tail_carries_memory_and_gauges():
    led = ledger_mod.ledger
    led.reset()
    led.alloc("serving.kv_pages", 12345)
    try:
        tail = _telemetry._flight_metrics_tail()
        assert tail["memory.bytes.serving.kv_pages"] == 12345
        assert tail["memory.ledger_bytes"] == 12345
        # gauge families ride the tail now (not only counters)
        gauge = _telemetry.gauge("serving.kv_free_pages")
        gauge.set(7)
        tail = _telemetry._flight_metrics_tail()
        assert tail["serving.kv_free_pages"] == 7
    finally:
        led.reset()


def test_cluster_aggregation_exact_over_memory_gauges():
    """min/max/mean of the per-rank memory gauges are exact through
    telemetry.aggregate — the arithmetic the np=3 tree leg
    (tests/test_tree.py) asserts over the real wire."""
    snaps = {r: {"memory.ledger_bytes":
                 {"type": "gauge", "value": (r + 1) * 1000}}
             for r in range(3)}
    agg = _telemetry.aggregate(snaps)["memory.ledger_bytes"]
    assert agg["min"] == 1000 and agg["max"] == 3000
    assert agg["mean"] == 2000 and agg["ranks"] == 3
    assert agg["per_rank"] == {0: 1000, 1: 2000, 2: 3000}


# ---------------------------------------------------------------------------
# Allocation sites (KV cache / prefetch / checkpoint / residuals)
# ---------------------------------------------------------------------------

def test_kv_cache_feeds_ledger_and_free_pages_gauge():
    from horovod_tpu.serving.kv_cache import PagedKVCache

    led = ledger_mod.ledger
    led.reset()
    cache = PagedKVCache(n_layers=2, n_heads=4, head_dim=8,
                         max_slots=2, pages_per_slot=4, page_size=8)
    expected = planner.kv_cache_bytes(2, 4, 8, 2, 4, 8)
    assert led.bytes_by_category()["serving.kv_pages"] == expected
    gauge = _telemetry.registry().gauge("serving.kv_free_pages")
    total = _telemetry.registry().gauge("serving.kv_total_pages")
    assert gauge.value == 8 and total.value == 8
    cache.begin_slot(0, 10)  # 2 pages
    assert gauge.value == 6
    cache.free_slot(0)
    assert gauge.value == 8
    del cache
    import gc

    gc.collect()
    assert led.bytes_by_category().get("serving.kv_pages", 0) == 0
    led.reset()


def test_engine_health_includes_kv_free_pages():
    from horovod_tpu.models.transformer import (TransformerConfig,
                                                init_transformer)
    from horovod_tpu.serving import InferenceEngine

    cfg = TransformerConfig(vocab_size=64, d_model=32, n_heads=4,
                            n_layers=1, d_ff=64, max_seq_len=32)
    params = init_transformer(jax.random.PRNGKey(0), cfg)
    eng = InferenceEngine(params, cfg, max_slots=2, page_size=8,
                          capacity=32)
    ready, payload = eng.health()
    assert payload["kv_free_pages"] == eng.cache.free_pages()
    assert payload["kv_total_pages"] == eng.cache.total_pages
    # page consumption shows up as reduced headroom
    eng.cache.begin_slot(0, 9)
    _, payload2 = eng.health()
    assert payload2["kv_free_pages"] < payload["kv_free_pages"]


def test_prefetch_accounts_staged_batches(hvd):
    from horovod_tpu.parallel.input import prefetch_to_device

    led = ledger_mod.ledger
    led.reset()
    batches = [np.ones((8, 4), np.float32) * i for i in range(4)]
    with prefetch_to_device(iter(batches), depth=2) as it:
        got = next(it)
        assert np.asarray(got)[0, 0] == 0.0
        # whatever is still staged is charged; the consumed one is not
        assert led.peak_by_category().get("input.prefetch", 0) >= \
            batches[0].nbytes
    # close() released everything still queued — including the final
    # batch a stager parked in put() can land AFTER the first drain
    # (the post-join drain in PrefetchIterator.close owns that window).
    assert led.bytes_by_category().get("input.prefetch", 0) == 0
    led.reset()


def test_prefetch_mid_epoch_close_never_leaks_charges(hvd):
    """Regression for the close()-vs-stager race: shutting down with
    the stager mid-stream must drain every charged batch, repeatedly —
    the leaked "input.prefetch" charge was a once-per-hundreds flake,
    so hammer the window."""
    from horovod_tpu.parallel.input import prefetch_to_device

    led = ledger_mod.ledger
    led.reset()
    for trial in range(20):
        batches = (np.full((16, 16), i, np.float32) for i in range(64))
        with prefetch_to_device(batches, depth=2) as it:
            next(it)  # stager now racing to refill the bounded queue
        leaked = led.bytes_by_category().get("input.prefetch", 0)
        assert leaked == 0, (
            f"trial {trial}: {leaked} bytes still charged after close()")
    led.reset()


def test_checkpoint_snapshot_accounting(hvd, tmp_path):
    from horovod_tpu.utils.checkpoint import save_checkpoint

    led = ledger_mod.ledger
    led.reset()
    tree = {"w": np.ones((64, 64), np.float32)}
    h = save_checkpoint(str(tmp_path / "ck.msgpack"), tree)
    assert h.wait(10.0)
    assert led.peak_by_category().get("checkpoint.snapshots", 0) \
        >= tree["w"].nbytes
    assert led.bytes_by_category().get("checkpoint.snapshots", 0) == 0
    led.reset()


# ---------------------------------------------------------------------------
# Accuracy: plan vs measured ledger (the ±15 % contract, CPU backend)
# ---------------------------------------------------------------------------

def _within(pred: int, measured: int, pct: float = 15.0) -> bool:
    return measured > 0 and abs(pred - measured) / measured * 100 <= pct


def test_dataplane_plan_matches_ledger_watermark(hvd):
    """Framework-owned prediction within ±15 % of the measured ledger
    high-watermark for the dataplane workload (the acceptance gate)."""
    tensors, elems = 8, 128
    n = hvd.size()
    rng = np.random.default_rng(3)
    base = [rng.standard_normal((n, elems)).astype(np.float32)
            for _ in range(tensors)]
    inputs = [hvd.shard(t) for t in base]
    led = ledger_mod.ledger
    led.reset()
    # quiesce: one fused launch deterministically (the planner's model)
    # — the drain tick can no longer split the submissions.
    with hvd.quiesce():
        hs = [hvd.allreduce_async(x, average=True, name=f"mem.{j}")
              for j, x in enumerate(inputs)]
    _ = [hvd.synchronize(h) for h in hs]
    plan = planner.plan_dataplane(tensors, elems, n)
    measured = led.watermark()
    assert _within(plan.framework_bytes, measured), \
        (plan.framework_bytes, measured)
    led.reset()


def test_pipeline_plan_matches_ledger_activations(hvd):
    """Pipeline activation prediction (schedule_plan peak × carry
    bytes) within ±15 % of the measured pipeline.activations peak."""
    S, m, d = 3, 4, 16
    n = hvd.size()

    def stage_first(p, carry, b):
        x, _y = b
        return jnp.tanh(x @ p["w"])

    def stage_mid(p, carry, b):
        return jnp.tanh(carry @ p["w"])

    def stage_last(p, carry, b):
        _x, y = b
        return jnp.mean((carry @ p["w"] - y) ** 2)

    from horovod_tpu.parallel.training import shard_batch

    chain = [stage_first] + [stage_mid] * (S - 2) + [stage_last]
    ks = jax.random.split(jax.random.PRNGKey(0), S)
    params = [{"w": jax.random.normal(k, (d, d)) * d ** -0.5}
              for k in ks]
    B = n * m
    x = jax.random.normal(jax.random.PRNGKey(1), (B, d))
    y = jax.random.normal(jax.random.PRNGKey(2), (B, d))
    batch = shard_batch((x, y))
    opt = optax.sgd(0.1)
    step = hvd.make_pipeline_train_step(chain, opt,
                                        num_microbatches=m,
                                        fusion_threshold=d * d * 4)
    led = ledger_mod.ledger
    led.reset()
    p, s, loss = step(params, opt.init(params), batch)
    measured = led.peak_by_category().get("pipeline.activations", 0)
    predicted = planner.pipeline_activation_bytes(
        S, m, microbatch_rows=B // m, width=d)
    assert _within(predicted, measured), (predicted, measured)
    # drained after the step: carries are transient
    assert led.bytes_by_category().get("pipeline.activations", 0) == 0
    # bytes gauge mirrors the peak (the tensors-not-bytes fix)
    snap = hvd.metrics()
    assert snap["pipeline.inflight_activation_bytes"]["value"] \
        == measured
    led.reset()


def test_residual_store_rides_ledger(hvd):
    """Quantized EF residuals appear under megakernel.residuals and
    drain on flush."""
    import horovod_tpu as hv

    from horovod_tpu.ops import megakernel as mk

    led = ledger_mod.ledger
    led.reset()
    hv.set_compression(default="int8")
    try:
        n = hvd.size()
        x = hvd.shard(np.ones((n, 256), np.float32))
        for step_i in range(2):
            h = hvd.allreduce_async(x, average=True, name="resid.t")
            hvd.synchronize(h)
        if mk.residual_count():
            assert led.bytes_by_category().get(
                "megakernel.residuals", 0) > 0
        mk.flush("test")
        assert led.bytes_by_category().get(
            "megakernel.residuals", 0) == 0
    finally:
        hv.set_compression()
    led.reset()


def test_step_watermark_gauge_advances(hvd):
    """make_train_step closes a ledger step window per call (the
    per-step high-watermark surface)."""
    led = ledger_mod.ledger
    led.reset()
    steps0 = led.steps()

    def loss_fn(params, batch):
        return jnp.mean((batch @ params) ** 2)

    from horovod_tpu.parallel.training import (make_train_step,
                                               shard_batch)

    opt = optax.sgd(0.1)
    step = make_train_step(loss_fn, opt, donate=False)
    params = jnp.ones((4, 4), jnp.float32)
    batch = shard_batch(np.ones((hvd.size() * 2, 4), np.float32))
    state = opt.init(params)
    params, state, _loss = step(params, state, batch)
    assert led.steps() == steps0 + 1
    led.reset()
