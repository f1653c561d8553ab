"""Backward/communication overlap (parallel/overlap.py, ISSUE 8).

The bucketed-backward train step streams each gradient bucket's
pack→reduce→unpack megakernel out of the backward pass instead of
waiting for the full gradient pytree.  Its load-bearing contracts:

* bitwise identity: the streamed schedule's parameters equal the
  serialized dispatch of the same sub-programs, bitwise (same programs,
  different interleaving), for the single-backward and the segmented
  schedule, across leaf dtypes; against the monolithic
  ``HVD_TPU_OVERLAP=off`` step the first step is bitwise and float32
  Adam steps stay within a measured few-ulp bound (the apply compiles
  as its own program — see parallel/overlap.py);
* steady state: exactly one megakernel launch per bucket per cycle,
  with the response cache replaying every bucket's sub-program (no
  renegotiation after warmup) — counted at jax's real dispatch choke
  point (utils/xla_dispatch, same policy as tests/test_megakernel.py);
* per-bucket error-feedback residuals survive the partial-cycle
  refactor (int8 wire: overlapped ≡ serialized bitwise across steps);
* a fusion-threshold change re-partitions the dispatch boundaries
  (the same event that flushes the coordinator plan memo);
* unbucketable trees (sparse IndexedSlices leaves, Adasum, subset
  meshes) fall back to the monolithic step.
"""

import json
import os

import jax
import jax.numpy as jnp
import numpy as np
import optax
import pytest

import horovod_tpu.core.state as state_mod
import horovod_tpu.ops.megakernel as mk
from horovod_tpu.core.state import REPLICA_AXIS
from horovod_tpu.ops import compression as compression_mod
from horovod_tpu.ops.sparse import IndexedSlices
from horovod_tpu.parallel import overlap as OV
from horovod_tpu.parallel.training import make_train_step, shard_batch

# ---------------------------------------------------------------------------
# Fixtures: a plain loss (unsegmented schedule) and a 3-stage chain
# (segmented schedule), sized so each segment splits into two buckets
# at _THRESHOLD (b-leaves bucket apart from the w-leaves).
# ---------------------------------------------------------------------------

_DIM = 64
_THRESHOLD = _DIM * _DIM * 4  # one f32 [64, 64] weight fills a bucket


def _plain_loss(params, batch):
    x, y = batch
    h = jnp.tanh(x @ params["w1"] + params["b1"])
    pred = h @ params["w2"] + params["b2"]
    return jnp.mean((pred - y) ** 2)


def _plain_params(key, dtype=jnp.float32):
    k1, k2 = jax.random.split(key)
    s = 1.0 / np.sqrt(_DIM)
    return {
        "w1": (jax.random.normal(k1, (_DIM, _DIM)) * s).astype(dtype),
        "b1": jnp.zeros((_DIM,), dtype),
        "w2": (jax.random.normal(k2, (_DIM, _DIM)) * s).astype(dtype),
        "b2": jnp.zeros((_DIM,), dtype),
    }


def _chain():
    def stage0(p, carry, batch):
        x, _y = batch
        return jnp.tanh(x @ p["w"] + p["b"])

    def stage1(p, carry, batch):
        return jnp.tanh(carry @ p["w"] + p["b"])

    def stage2(p, carry, batch):
        _x, y = batch
        pred = carry @ p["w"] + p["b"]
        return jnp.mean((pred - y) ** 2)

    return OV.ChainedLoss([stage0, stage1, stage2])


def _chain_params(key):
    ks = jax.random.split(key, 3)
    s = 1.0 / np.sqrt(_DIM)
    return [{"w": jax.random.normal(k, (_DIM, _DIM)) * s,
             "b": jnp.zeros((_DIM,))} for k in ks]


def _batch(hvd, key, per=4):
    n = hvd.size()
    kx, ky = jax.random.split(key)
    x = jax.random.normal(kx, (per * n, _DIM))
    y = jax.random.normal(ky, (per * n, _DIM))
    return shard_batch((x, y))


def _leaves_equal(a, b):
    fa = jax.tree_util.tree_leaves(a)
    fb = jax.tree_util.tree_leaves(b)
    assert len(fa) == len(fb)
    return all(np.asarray(x).tobytes() == np.asarray(y).tobytes()
               for x, y in zip(fa, fb))


def _run(step, params, opt, batch, steps):
    p, s = params, opt.init(params)
    loss = None
    for _ in range(steps):
        out = step(p, s, batch)
        p, s, loss = out[0], out[1], out[2]
    jax.block_until_ready(jax.tree_util.tree_leaves(p))
    return p, float(loss)


# ---------------------------------------------------------------------------
# Bitwise identity
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_stream_identical_to_serial_and_monolithic(hvd, dtype):
    """The streaming schedule's params ≡ the serial schedule's, bitwise,
    after several steps, per leaf dtype (buckets partition by wire
    dtype, so each dtype rides its own megakernels) — the contract that
    holds on every backend: same sub-programs, different interleaving.

    Against the monolithic step the first Adam step is bitwise and
    later float32 steps may drift: XLA:CPU contracts the moment update
    ``b*m + (1-b)*g`` into FMAs one way when ``g`` is produced inside
    the program (monolithic) and another when it is a program input
    (the bucketed apply).  Measured under jax 0.9.0: at most 3.6e-8 on
    weights of magnitude 0.47 after 3 steps, 1.0e-7 after 30 (1-3 ulp
    of the leaf's largest magnitude); bfloat16 leaves stay bitwise.
    The bound asserted is 4 ulp of each leaf's largest magnitude."""
    params = _plain_params(jax.random.PRNGKey(0), dtype)
    batch = _batch(hvd, jax.random.PRNGKey(1))
    opt = optax.adam(1e-3)

    def run(mode, steps=3):
        return _run(make_train_step(
            _plain_loss, opt, donate=False, fusion_threshold=_THRESHOLD,
            overlap=mode), params, opt, batch, steps)

    p_on, l_on = run("on")
    p_ser, l_ser = run("serial")
    p_off, l_off = run("off")
    assert l_on == l_ser
    assert _leaves_equal(p_on, p_ser)
    assert _leaves_equal(run("on", 1)[0], run("off", 1)[0])
    if dtype == jnp.bfloat16:
        assert l_on == l_off
        assert _leaves_equal(p_on, p_off)
        return
    for a, b in zip(jax.tree_util.tree_leaves(p_on),
                    jax.tree_util.tree_leaves(p_off)):
        a, b = np.asarray(a), np.asarray(b)
        bound = 4 * np.finfo(np.float32).eps * np.abs(b).max()
        assert np.abs(a - b).max() <= bound, (np.abs(a - b).max(), bound)


def test_stream_bitwise_identical_mixed_dtypes(hvd):
    """One tree mixing f32 and bf16 leaves: the bucket plan groups by
    dtype and the result stays bitwise vs the monolithic step."""
    params = _plain_params(jax.random.PRNGKey(0))
    params["b1"] = params["b1"].astype(jnp.bfloat16)
    params["b2"] = params["b2"].astype(jnp.bfloat16)
    batch = _batch(hvd, jax.random.PRNGKey(1))
    opt = optax.sgd(0.1)
    p_on, _ = _run(make_train_step(
        _plain_loss, opt, donate=False, fusion_threshold=_THRESHOLD,
        overlap="on"), params, opt, batch, 2)
    p_off, _ = _run(make_train_step(
        _plain_loss, opt, donate=False, fusion_threshold=_THRESHOLD,
        overlap="off"), params, opt, batch, 2)
    assert _leaves_equal(p_on, p_off)


def test_segmented_stream_equals_serialized_bitwise(hvd):
    """ChainedLoss: the streamed dispatch ≡ the serialized dispatch of
    the SAME per-bucket sub-programs, bitwise (structural — identical
    programs, different interleaving), and ≈ the monolithic step
    (XLA:CPU compiles per-stage backward programs a ULP apart from the
    fused whole-program backward; see parallel/overlap.py)."""
    chain = _chain()
    params = _chain_params(jax.random.PRNGKey(0))
    batch = _batch(hvd, jax.random.PRNGKey(1))
    opt = optax.adam(1e-3)

    def build(mode):
        return make_train_step(chain, opt, donate=False,
                               fusion_threshold=_THRESHOLD, overlap=mode)

    step_on = build("on")
    p_on, _ = _run(step_on, params, opt, batch, 3)
    p_ser, _ = _run(build("serial"), params, opt, batch, 3)
    p_off, _ = _run(build("off"), params, opt, batch, 3)
    assert step_on.overlap_active
    assert step_on.segment_count == 3
    assert step_on.bucket_count == 6  # (w, b) buckets per stage
    assert _leaves_equal(p_on, p_ser)
    for a, b in zip(jax.tree_util.tree_leaves(p_on),
                    jax.tree_util.tree_leaves(p_off)):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=1e-4, atol=1e-5)


def test_overlap_off_restores_static_step(hvd, monkeypatch):
    """HVD_TPU_OVERLAP=off (and the pre-PR default on CPU meshes via
    auto) builds the plain jitted program — no overlap machinery at
    all."""
    opt = optax.sgd(0.1)
    step_off = make_train_step(_plain_loss, opt, donate=False,
                               overlap="off")
    assert not hasattr(step_off, "overlap_active")
    monkeypatch.delenv(OV.OVERLAP_ENV, raising=False)
    step_auto = make_train_step(_plain_loss, opt, donate=False)
    assert not hasattr(step_auto, "overlap_active")  # auto→off on CPU


# ---------------------------------------------------------------------------
# Steady state: one launch per bucket, response-cache replay
# ---------------------------------------------------------------------------

def test_exactly_one_launch_per_bucket_and_cache_replay(hvd):
    """After warmup, one training cycle issues exactly one megakernel
    launch per bucket — counted at jax's dispatch choke point — and
    every bucket's sub-program replays from the response cache (zero
    new negotiations)."""
    from horovod_tpu.utils import xla_dispatch

    chain = _chain()
    params = _chain_params(jax.random.PRNGKey(0))
    batch = _batch(hvd, jax.random.PRNGKey(1))
    opt = optax.sgd(0.1)
    step = make_train_step(chain, opt, donate=False,
                           fusion_threshold=_THRESHOLD, overlap="on")
    mk.set_enabled(True)
    p, s = params, opt.init(params)
    for _ in range(2):  # cold + first warm cycle
        p, s, _ = step(p, s, batch)
    jax.block_until_ready(jax.tree_util.tree_leaves(p))

    st = state_mod.global_state()
    n_buckets = step.bucket_count
    n_leaves = len(jax.tree_util.tree_leaves(params))
    launches0 = mk.stats.launches
    cache0 = st.response_cache.stats.replayed_tensors
    misses0 = st.response_cache.stats.misses
    with xla_dispatch.exact_scope():
        with xla_dispatch.record(all_threads=True) as scope:
            p, s, _ = step(p, s, batch)
            jax.block_until_ready(jax.tree_util.tree_leaves(p))

    assert mk.stats.launches - launches0 == n_buckets, (
        f"steady-state cycle ran {mk.stats.launches - launches0} "
        f"megakernel launches for {n_buckets} buckets")
    # Choke-point accounting: 1 forward + one backward program per
    # segment + one megakernel per bucket + 1 optimizer apply.  Any
    # eager-op creep on the dispatch path breaks this equality.
    expected = 1 + step.segment_count + n_buckets + 1
    assert scope.count == expected, (
        f"steady-state cycle issued {scope.count} XLA dispatches; "
        f"expected {expected} (fwd + {step.segment_count} bwd + "
        f"{n_buckets} megakernels + apply)")
    # Replay bypassed negotiation for every bucket (per-bucket
    # sub-programs are fully cache-hit: no new misses).
    assert st.response_cache.stats.replayed_tensors - cache0 == n_leaves
    assert st.response_cache.stats.misses == misses0


def test_telemetry_counters_and_timeline_instants(hvd, tmp_path):
    """overlap.buckets_dispatched counts every bucket handed to the
    dynamic path; the stream.take region times the post-backward
    completion wait; each dispatch writes a BUCKET_DISPATCH timeline
    instant."""
    import horovod_tpu as H

    chain = _chain()
    params = _chain_params(jax.random.PRNGKey(0))
    batch = _batch(hvd, jax.random.PRNGKey(1))
    opt = optax.sgd(0.1)
    step = make_train_step(chain, opt, donate=False,
                           fusion_threshold=_THRESHOLD, overlap="on")
    before = H.metrics()
    base = before.get("overlap.buckets_dispatched", {}).get("value", 0)
    took0 = before.get("trace.span_seconds.stream.take",
                       {}).get("count", 0)
    tl_path = tmp_path / "overlap_timeline.json"
    H.start_timeline(str(tl_path))
    try:
        _run(step, params, opt, batch, 2)
    finally:
        H.stop_timeline()
    snap = H.metrics()
    dispatched = snap["overlap.buckets_dispatched"]["value"] - base
    assert dispatched == 2 * step.bucket_count
    assert snap["trace.span_seconds.stream.take"]["count"] - took0 == 2
    events = json.loads(tl_path.read_text())
    if isinstance(events, dict):
        events = events["traceEvents"]
    instants = [e for e in events if e.get("name") == "BUCKET_DISPATCH"]
    assert len(instants) == dispatched
    assert {e["args"]["bucket"] for e in instants} \
        == set(range(step.bucket_count))


def _step_spans(step_no):
    import horovod_tpu.trace as trace

    return [e for e in trace.export_events()
            if e.get("ph") == "X" and e["args"].get("step") == step_no]


def _nest(spans):
    """The roots of ``spans`` with their ``children`` attached, from the
    buffer's structure alone: a region is recorded when it closes, so a
    thread's children precede their parent, and each names its
    ``parent``.  No clock is compared."""
    open_ = []
    for e in spans:
        mine = [c for c in open_ if c["args"].get("parent") == e["name"]]
        rest = [c for c in open_ if c["args"].get("parent") != e["name"]]
        open_ = rest + [dict(e, children=mine)]
    return open_


def _names(spans):
    return sorted(e["name"] for e in spans)


def test_stream_step_emits_nested_regions_with_one_step(hvd):
    """One stream step on the 8-device mesh: step/stream > stream.backward,
    stream.submit, stream.drain > execute/allreduce > megakernel/psum,
    stream.take, stream.apply, all with one ``step`` (ISSUE 24)."""
    import horovod_tpu as H
    import horovod_tpu.core.state as state_mod
    import horovod_tpu.trace as trace

    chain = _chain()
    params = _chain_params(jax.random.PRNGKey(0))
    batch = _batch(hvd, jax.random.PRNGKey(1))
    opt = optax.sgd(0.1)
    step = make_train_step(chain, opt, donate=False,
                           fusion_threshold=_THRESHOLD, overlap="on")
    # The 5 ms background tick drains the same queue: a tick landing
    # between a bucket's submit and the step's own drain executes that
    # bucket on ITS thread, outside stream.drain.  Stopped, the step's
    # drain is the only one and the nesting below is the only outcome.
    state_mod.global_state().bg_stop.set()
    p, s = params, opt.init(params)
    for _ in range(2):                       # build, negotiate, replay
        p, s, _loss = step(p, s, batch)
    jax.block_until_ready(jax.tree_util.tree_leaves(p))
    before = H.metrics()
    p, s, _loss = step(p, s, batch)
    jax.block_until_ready(jax.tree_util.tree_leaves(p))
    after = H.metrics()
    n_buckets, n_segs = step.bucket_count, step.segment_count
    # negotiate.wait (submit to response, one a bucket) is a plain
    # trace.span() record: it sits in no region and names no parent.
    (whole,) = [r for r in _nest(_step_spans(trace.current_step()))
                if r["name"] != "negotiate.wait"]
    assert whole["name"] == "step/stream"
    assert "parent" not in whole["args"]
    assert _names(whole["children"]) == sorted(
        ["stream.backward"] * (n_segs + 1)       # fwd + bwd_k
        + ["stream.submit", "stream.drain"] * n_buckets
        + ["stream.take", "stream.apply"])
    by_name = {}
    for e in whole["children"]:
        by_name.setdefault(e["name"], []).append(e)
    assert sorted(e["args"]["bucket"] for e in by_name["stream.submit"]) \
        == list(range(n_buckets))
    assert all(e["args"]["tensors"] >= 1 and e["args"]["bytes"] > 0
               for e in by_name["stream.submit"])
    assert sorted(e["args"]["bucket"] for e in by_name["stream.drain"]) \
        == list(range(n_buckets))
    for drain in by_name["stream.drain"]:
        assert _names(drain["children"]) == ["execute/allreduce",
                                             "negotiate.tick"]
        (execute,) = [c for c in drain["children"] if c["children"]]
        assert _names(execute["children"]) == ["megakernel/psum"]
    # The registry keeps what the ring may wrap: every region fed its
    # trace.span_seconds histogram once per occurrence.
    def moved(name, field="count"):
        key = "trace.span_seconds." + name
        return after[key][field] - before.get(key, {}).get(field, 0)

    assert moved("step/stream") == 1
    assert moved("stream.submit") == n_buckets
    assert moved("stream.drain") == n_buckets
    assert moved("stream.take") == 1
    assert moved("step/stream", "sum") == pytest.approx(
        whole["dur"] / 1e6)


def test_step_region_is_named_by_what_was_built(hvd):
    import horovod_tpu.trace as trace

    params = _plain_params(jax.random.PRNGKey(0))
    batch = _batch(hvd, jax.random.PRNGKey(1))
    opt = optax.sgd(0.1)
    for overlap, name in (("off", "step/monolithic"),
                          ("serial", "step/serial"),
                          ("on", "step/stream")):
        step = make_train_step(_plain_loss, opt, donate=False,
                               fusion_threshold=_THRESHOLD,
                               overlap=overlap)
        _run(step, params, opt, batch, 1)
        names = {e["name"] for e in _step_spans(trace.current_step())}
        assert name in names, (overlap, sorted(names))
        assert not {"step/monolithic", "step/serial",
                    "step/stream"} - {name} & names


# ---------------------------------------------------------------------------
# Quantized wire: per-bucket error-feedback residuals
# ---------------------------------------------------------------------------

def test_int8_ef_residuals_carry_over_per_bucket(hvd):
    """Under int8 wire compression the streamed schedule stays bitwise
    equal to the serialized schedule across steps — only true when each
    bucket's error-feedback residual is stored and re-consumed under
    its own (per-bucket sub-program) key, and the residual actually
    carries: the quantized trajectory must diverge from full precision."""
    import horovod_tpu as H

    chain = _chain()
    params = _chain_params(jax.random.PRNGKey(0))
    batch = _batch(hvd, jax.random.PRNGKey(1))
    opt = optax.adam(1e-3)

    def build(mode):
        return make_train_step(chain, opt, donate=False,
                               fusion_threshold=_THRESHOLD, overlap=mode)

    p_fp, _ = _run(build("on"), params, opt, batch, 3)
    H.set_compression(default="int8")
    try:
        step_on = build("on")
        p_on, _ = _run(step_on, params, opt, batch, 3)
        p_ser, _ = _run(build("serial"), params, opt, batch, 3)
        # One EF residual entry per bucket survives for the next step.
        assert mk.residual_count() >= step_on.bucket_count
    finally:
        H.set_compression(default="none")
    assert _leaves_equal(p_on, p_ser)
    assert not _leaves_equal(p_on, p_fp)  # the wire really quantized


# ---------------------------------------------------------------------------
# Fusion-threshold flush
# ---------------------------------------------------------------------------

def test_fusion_threshold_change_replans_buckets(hvd):
    """set_fusion_threshold mid-training (the autotune event that
    flushes the coordinator plan memo and the megakernel cache) makes
    the overlapped step re-partition its dispatch boundaries on the
    next call — and the result stays bitwise vs the monolithic step."""
    params = _plain_params(jax.random.PRNGKey(0))
    batch = _batch(hvd, jax.random.PRNGKey(1))
    opt = optax.sgd(0.1)
    st = state_mod.global_state()
    st.coordinator.set_fusion_threshold(_THRESHOLD)
    try:
        step = make_train_step(_plain_loss, opt, donate=False,
                               overlap="on")
        p, s = params, opt.init(params)
        p, s, _ = step(p, s, batch)
        coarse = step.bucket_count
        # Below one bias leaf (256 B): every leaf becomes its own bucket.
        st.coordinator.set_fusion_threshold(128)
        p, s, _ = step(p, s, batch)
        fine = step.bucket_count
        assert fine > coarse, (coarse, fine)

        # Same two-threshold trajectory on the monolithic step: the
        # re-planned buckets still reduce to identical parameters.
        st.coordinator.set_fusion_threshold(_THRESHOLD)
        step_off = make_train_step(_plain_loss, opt, donate=False,
                                   overlap="off")
        q, t = params, opt.init(params)
        q, t, _ = step_off(q, t, batch)
        st.coordinator.set_fusion_threshold(128)
        q, t, _ = step_off(q, t, batch)
        assert _leaves_equal(p, q)
    finally:
        st.coordinator.set_fusion_threshold(64 * 1024 * 1024)


# ---------------------------------------------------------------------------
# Fallbacks: unbucketable trees keep the monolithic program, and every
# fallback leaves the triple-entry record — ONE overlap.fallbacks
# counter tick and ONE overlap_fallback flight event, carrying the
# NAMED reason (the warn line rides stderr).
# ---------------------------------------------------------------------------

def _fallback_events():
    from horovod_tpu.telemetry import flight

    return [e for e in flight.snapshot() if e[1] == "overlap_fallback"]


def _fallbacks_counter():
    import horovod_tpu as H

    return H.metrics().get("overlap.fallbacks", {}).get("value", 0)


def _assert_fell_back_once(step, reason, counter0, events0):
    assert step.overlap_active is False
    assert step._fallback_reason == reason
    assert _fallbacks_counter() - counter0 == 1
    new = _fallback_events()[events0:]
    assert len(new) == 1, new
    assert new[0][2][0] == reason, new


def test_sparse_gradient_leaves_fall_back(hvd):
    """IndexedSlices gradient leaves ship a negotiated-size payload the
    bucket planner cannot size: the trace-time probe refuses them."""
    opt = optax.sgd(0.1)
    step = make_train_step(_plain_loss, opt, donate=False, overlap="on")

    def sparse_grad_fn(params, batch):
        grads = dict(params)
        grads["w1"] = IndexedSlices(jnp.zeros((2, _DIM)),
                                    jnp.zeros((2,), jnp.int32),
                                    (_DIM, _DIM))
        return jnp.zeros(()), grads

    with pytest.raises(OV._Unbucketable, match="sparse") as ei:
        step._detect_sparse(sparse_grad_fn,
                            _plain_params(jax.random.PRNGKey(0)), None,
                            _batch(hvd, jax.random.PRNGKey(1)))
    assert ei.value.reason == "sparse"


def test_sparse_fallback_counts_and_flight_records_once(hvd, monkeypatch):
    """The sparse refusal surfaces through the step as the named
    ``sparse`` fallback: counter and flight event exactly once."""
    opt = optax.sgd(0.1)
    step = make_train_step(_plain_loss, opt, donate=False, overlap="on")
    monkeypatch.setattr(
        OV._OverlapStep, "_detect_sparse",
        lambda self, *a: (_ for _ in ()).throw(OV._Unbucketable(
            "sparse", "seeded sparse leaf")))
    c0, e0 = _fallbacks_counter(), len(_fallback_events())
    params = _plain_params(jax.random.PRNGKey(0))
    batch = _batch(hvd, jax.random.PRNGKey(1))
    p, s, _loss = step(params, opt.init(params), batch)
    step(p, s, batch)  # second step: no new record
    _assert_fell_back_once(step, "sparse", c0, e0)


def test_adasum_fallback_counts_and_flight_records_once(hvd):
    """op=Adasum combines the WHOLE gradient vector — no per-bucket
    decomposition exists, so the first call falls back to the static
    step under the named ``adasum`` reason (counted + flight-recorded
    exactly once, further steps free)."""
    import horovod_tpu as H

    opt = optax.sgd(0.1)
    step = make_train_step(_plain_loss, opt, donate=False, op=H.Adasum,
                           overlap="on")
    c0, e0 = _fallbacks_counter(), len(_fallback_events())
    params = _plain_params(jax.random.PRNGKey(0))
    batch = _batch(hvd, jax.random.PRNGKey(1))
    p, s, loss = step(params, opt.init(params), batch)
    p, s, loss = step(p, s, batch)  # second step: no new record
    assert np.isfinite(float(loss))
    _assert_fell_back_once(step, "adasum", c0, e0)


def test_subset_mesh_falls_back(hvd):
    """A step built over a sub-mesh of the global replica set keeps its
    in-program reduction (the dynamic path negotiates over ALL
    replicas); results match the monolithic sub-mesh step bitwise, and
    the fallback records once under the named ``sub-mesh`` reason."""
    devices = jax.devices()[:4]
    mesh = jax.sharding.Mesh(np.asarray(devices), (REPLICA_AXIS,))
    params = _plain_params(jax.random.PRNGKey(0))
    x = jax.random.normal(jax.random.PRNGKey(1), (4 * len(devices), _DIM))
    y = jax.random.normal(jax.random.PRNGKey(2), (4 * len(devices), _DIM))
    opt = optax.sgd(0.1)
    step = make_train_step(_plain_loss, opt, mesh=mesh, donate=False,
                           overlap="on")
    c0, e0 = _fallbacks_counter(), len(_fallback_events())
    p_on, _ = _run(step, params, opt, (x, y), 2)
    _assert_fell_back_once(step, "sub-mesh", c0, e0)
    step_off = make_train_step(_plain_loss, opt, mesh=mesh, donate=False,
                               overlap="off")
    p_off, _ = _run(step_off, params, opt, (x, y), 2)
    assert _leaves_equal(p_on, p_off)


def test_mp_is_not_a_fallback_anymore(hvd, monkeypatch):
    """After this PR a plain multi-process build (one replica per
    process, aligned meshes) passes the build gates and proceeds to
    the bucketed path — asserted by faking the mp state flags and
    watching the build reach plan construction instead of falling
    back with an ``mp`` reason.  (The real np=2 bitwise leg rides
    tests/mp_worker.py scenario_overlap under CI's jax.)"""
    import horovod_tpu.ops.collective as C
    import horovod_tpu.core.state as state_mod

    st = state_mod.global_state()
    monkeypatch.setattr(st, "multiprocess", True)
    monkeypatch.setattr(st, "process_count", st.size)
    monkeypatch.setattr(C, "_mp_kernels",
                        lambda: (st.mesh, None))
    opt = optax.sgd(0.1)
    step = make_train_step(_plain_loss, opt, donate=False, overlap="on")
    reached = {}

    def probe(self, *a):
        reached["build"] = True
        raise OV._Unbucketable("grad-tree", "stop before any transport")

    monkeypatch.setattr(OV._OverlapStep, "_build_unsegmented", probe)
    c0, e0 = _fallbacks_counter(), len(_fallback_events())
    params = _plain_params(jax.random.PRNGKey(0))
    step(params, opt.init(params), _batch(hvd, jax.random.PRNGKey(1)))
    assert reached.get("build"), "mp build gate still falls back"
    _assert_fell_back_once(step, "grad-tree", c0, e0)


# ---------------------------------------------------------------------------
# Env knob: validation, resolution, HELLO fingerprint
# ---------------------------------------------------------------------------

def test_env_knob_validation(monkeypatch):
    monkeypatch.setenv(OV.OVERLAP_ENV, "bogus")
    with pytest.raises(ValueError, match="HVD_TPU_OVERLAP"):
        OV.validate_env()
    for ok in ("auto", "on", "off", "serial", "1", "0", "ON", " off "):
        monkeypatch.setenv(OV.OVERLAP_ENV, ok)
        OV.validate_env()
    monkeypatch.setenv(OV.OVERLAP_ENV, "1")
    assert OV.overlap_mode() == "on"
    monkeypatch.setenv(OV.OVERLAP_ENV, "0")
    assert OV.overlap_mode() == "off"


def test_init_rejects_malformed_overlap_env(monkeypatch):
    """hvd.init() fails fast — not the first training step — on a
    malformed knob, like the compression/topology knobs."""
    import horovod_tpu as H

    monkeypatch.setenv(OV.OVERLAP_ENV, "sideways")
    with pytest.raises(ValueError, match="HVD_TPU_OVERLAP"):
        H.init(devices=jax.devices())


def _fake_mesh(platform, procs, drop=()):
    """A mesh-shaped stand-in: one device per entry of ``procs`` (its
    ``process_index``), without the attributes ``drop`` names."""
    from types import SimpleNamespace

    devs = [SimpleNamespace(**{
        k: v for k, v in (("platform", platform), ("process_index", proc))
        if k not in drop}) for proc in procs]
    return SimpleNamespace(devices=np.asarray(devs))


_MESHES = {
    "cpu8": lambda: _fake_mesh("cpu", [0] * 8),
    "tpu1": lambda: _fake_mesh("tpu", [0]),
    "tpu8_one_process": lambda: _fake_mesh("tpu", [jax.process_index()] * 8),
    "tpu8_two_processes": lambda: _fake_mesh("tpu", [0] * 4 + [1] * 4),
    "tpu8_no_process_index": lambda: _fake_mesh(
        "tpu", [0] * 8, drop=("process_index",)),
    "no_platform": lambda: _fake_mesh("tpu", [0] * 8, drop=("platform",)),
}


@pytest.mark.parametrize("override,mesh,want", [
    # auto decides from the mesh alone: the one-program step wherever
    # this process drives every device of it (ISSUE 32: four chips under
    # one process lost a fifth of their rate to the host-paced stream
    # schedule); streaming only where the mesh spans processes.
    (None, "cpu8", "off"),
    (None, "tpu1", "off"),                  # nothing to reduce
    (None, "tpu8_one_process", "off"),
    (None, "tpu8_two_processes", "stream"),
    (None, "tpu8_no_process_index", "off"),  # exotic mesh: stay monolithic
    (None, "no_platform", "off"),
    ("auto", "tpu8_one_process", "off"),
    ("auto", "tpu8_two_processes", "stream"),
    # Explicit values win on every mesh.
    ("on", "cpu8", "stream"),
    ("on", "tpu8_one_process", "stream"),
    ("on", "tpu8_two_processes", "stream"),
    ("1", "tpu8_one_process", "stream"),
    ("serial", "cpu8", "serial"),
    ("serial", "tpu8_one_process", "serial"),
    ("serial", "tpu8_two_processes", "serial"),
    ("off", "cpu8", "off"),
    ("off", "tpu8_one_process", "off"),
    ("off", "tpu8_two_processes", "off"),
    ("0", "tpu8_two_processes", "off"),
])
def test_auto_resolution_per_mesh_platform(monkeypatch, override, mesh, want):
    monkeypatch.delenv(OV.OVERLAP_ENV, raising=False)
    assert OV.resolve_mode(override, _MESHES[mesh]()) == want


@pytest.mark.parametrize("mesh", ["cpu8", "tpu8_one_process",
                                  "tpu8_two_processes"])
def test_env_knob_is_the_default_and_unknown_values_raise(monkeypatch, mesh):
    monkeypatch.setenv(OV.OVERLAP_ENV, "serial")
    assert OV.resolve_mode(None, _MESHES[mesh]()) == "serial"
    assert OV.resolve_mode("off", _MESHES[mesh]()) == "off"  # argument wins
    with pytest.raises(ValueError, match="overlap"):
        OV.resolve_mode("diagonal", _MESHES[mesh]())


# ---------------------------------------------------------------------------
# The stateful variant under auto: one program, no bucket dispatched
# ---------------------------------------------------------------------------

def _stateful_loss(params, model_state, batch):
    """A normalising layer with running statistics: the smallest loss
    that returns a new model state (``make_train_step_with_state``)."""
    x, y = batch
    h = x @ params["w1"] + params["b1"]
    mean = jnp.mean(h, axis=0)
    h = jnp.tanh(h - mean)
    pred = h @ params["w2"] + params["b2"]
    new_state = {"mean": 0.9 * model_state["mean"] + 0.1 * mean}
    return jnp.mean((pred - y) ** 2), new_state


def _stateful_first_step(hvd, overlap):
    from horovod_tpu.parallel.training import make_train_step_with_state

    params = _plain_params(jax.random.PRNGKey(0))
    stats = {"mean": jnp.zeros((_DIM,))}
    batch = _batch(hvd, jax.random.PRNGKey(1))
    opt = optax.sgd(0.1, momentum=0.9)
    step = make_train_step_with_state(
        _stateful_loss, opt, donate=False, fusion_threshold=_THRESHOLD,
        **({} if overlap is None else {"overlap": overlap}))
    out = step(params, stats, opt.init(params), batch)
    jax.block_until_ready(jax.tree_util.tree_leaves(out))
    return step, out


def test_auto_stateful_step_is_one_program_and_dispatches_no_bucket(
        hvd, monkeypatch):
    """What ``resnet50-dp4`` builds (``make_train_step_with_state``,
    the schedule left to the program) on a mesh this process owns: the
    ``step/monolithic`` region, no ``step/stream``, and neither
    ``overlap.buckets_dispatched`` nor ``overlap.fallbacks`` moves."""
    import horovod_tpu as H
    import horovod_tpu.trace as trace

    monkeypatch.delenv(OV.OVERLAP_ENV, raising=False)
    assert OV.resolve_mode(None, hvd.mesh()) == "off"
    before = H.metrics()
    step, _out = _stateful_first_step(hvd, None)
    after = H.metrics()
    assert not hasattr(step, "overlap_active")
    names = {e["name"] for e in _step_spans(trace.current_step())}
    assert "step/monolithic" in names
    assert not {"step/stream", "step/serial"} & names

    def moved(name, field="value"):
        return (after.get(name, {}).get(field, 0)
                - before.get(name, {}).get(field, 0))

    assert moved("trace.span_seconds.step/monolithic", "count") == 1
    assert moved("trace.span_seconds.step/stream", "count") == 0
    assert moved("overlap.buckets_dispatched") == 0
    assert moved("overlap.fallbacks") == 0


def test_auto_stateful_first_sgd_step_bitwise_the_stream_steps(
        hvd, monkeypatch):
    """The identity contract for the stateful variant: the first SGD
    step of the one-program step ``auto`` builds is bitwise the
    ``overlap="on"`` step's — parameters, model state, momentum, loss."""
    monkeypatch.delenv(OV.OVERLAP_ENV, raising=False)
    step_on, out_on = _stateful_first_step(hvd, "on")
    _step, out_auto = _stateful_first_step(hvd, None)
    assert step_on.overlap_active and step_on.bucket_count >= 2
    assert float(out_on[3]) == float(out_auto[3])
    assert _leaves_equal(out_on[:3], out_auto[:3])


def test_overlap_knob_in_hello_env_fingerprint(monkeypatch):
    """HVD_TPU_OVERLAP rides the HELLO env fingerprint: a rank
    diverging on the overlap mode is named at startup like the
    compression/topology knobs."""
    assert "HVD_TPU_OVERLAP" in compression_mod._SPMD_ENV_KNOBS
    monkeypatch.setenv(OV.OVERLAP_ENV, "on")
    fp_on = compression_mod.env_fingerprint()
    monkeypatch.setenv(OV.OVERLAP_ENV, "off")
    fp_off = compression_mod.env_fingerprint()
    assert fp_on != fp_off
