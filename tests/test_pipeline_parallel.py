"""Pipeline-parallel tests.

Half one: the GPipe scan must reproduce sequential layer application,
forward and backward (one compiled program over the pipe axis).

Half two: the host-scheduled 1F1B MPMD rebuild (ISSUE 12) — the
dryrun schedule plan (dependency-valid ticks, bounded activation
memory, interleave shrinking the bubble), the per-stage-executable
train step (bitwise 1f1b ≡ gpipe-ordered dispatch, allclose vs the
monolithic mean-loss gradient), streamed partial-cycle reduction
riding the response cache, schedule-shape validation naming the axis
and the nearest valid counts, and the env knobs.
"""

import os

import jax
import numpy as np
import optax
import jax.numpy as jnp
import pytest
from jax.sharding import PartitionSpec as P

import horovod_tpu.parallel.pipeline as PL
from horovod_tpu.core.topology import PIPE_AXIS, make_mesh
from horovod_tpu.parallel.pipeline import (gpipe, make_pipeline_train_step,
                                           schedule_plan,
                                           select_stage_params,
                                           stage_index)

TOL = 1e-5


def _stage_fn(params, x):
    w, b = params
    return jnp.tanh(x @ w + b)


def _stacked_params(n_stages, d, seed=0):
    key = jax.random.PRNGKey(seed)
    kw, kb = jax.random.split(key)
    w = jax.random.normal(kw, (n_stages, d, d)) * (d ** -0.5)
    b = jax.random.normal(kb, (n_stages, d)) * 0.1
    return w, b


def _sequential(params, x):
    w, b = params
    for s in range(w.shape[0]):
        x = _stage_fn((w[s], b[s]), x)
    return x


@pytest.mark.parametrize("n_stages,n_micro", [(2, 2), (4, 4), (4, 8)])
def test_gpipe_matches_sequential(n_stages, n_micro):
    mesh = make_mesh(pipe=n_stages, devices=jax.devices()[:n_stages])
    d = 8
    params = _stacked_params(n_stages, d)
    x = jax.random.normal(jax.random.PRNGKey(1), (16, d))

    def run(params, x):
        mine = select_stage_params(params)
        return gpipe(_stage_fn, mine, x, num_microbatches=n_micro)

    got = jax.jit(jax.shard_map(run, mesh=mesh, in_specs=(P(), P()),
                                out_specs=P(), check_vma=False))(params, x)
    want = _sequential(params, x)
    assert jnp.max(jnp.abs(got - want)) < TOL


def test_gpipe_gradients_match_sequential():
    n_stages, n_micro = 4, 4
    mesh = make_mesh(pipe=n_stages, devices=jax.devices()[:n_stages])
    d = 8
    params = _stacked_params(n_stages, d, seed=2)
    x = jax.random.normal(jax.random.PRNGKey(3), (8, d))

    sm = jax.shard_map(
        lambda params, x: gpipe(_stage_fn, select_stage_params(params), x,
                                num_microbatches=n_micro),
        mesh=mesh, in_specs=(P(), P()), out_specs=P(), check_vma=False)
    got = jax.jit(jax.grad(lambda p: jnp.sum(sm(p, x) ** 2)))(params)
    want = jax.grad(lambda p: jnp.sum(_sequential(p, x) ** 2))(params)
    for a, b in zip(got, want):
        assert jnp.max(jnp.abs(a - b)) < 1e-4


def test_gpipe_rejects_indivisible_microbatches():
    mesh = make_mesh(pipe=2, devices=jax.devices()[:2])
    params = _stacked_params(2, 4)
    x = jnp.zeros((6, 4))
    sm = jax.shard_map(
        lambda params, x: gpipe(_stage_fn, select_stage_params(params), x,
                                num_microbatches=4),
        mesh=mesh, in_specs=(P(), P()), out_specs=P(), check_vma=False)
    with pytest.raises(ValueError, match="not divisible"):
        sm(params, x)


def test_stage_index():
    mesh = make_mesh(pipe=4, devices=jax.devices()[:4])
    out = jax.jit(jax.shard_map(lambda: stage_index()[None], mesh=mesh,
                                in_specs=(), out_specs=P(PIPE_AXIS),
                                check_vma=False))()
    assert list(out) == [0, 1, 2, 3]


def test_gpipe_composes_with_data_parallel():
    mesh = make_mesh(data=2, pipe=2, devices=jax.devices()[:4])
    d = 8
    params = _stacked_params(2, d, seed=4)
    x = jax.random.normal(jax.random.PRNGKey(5), (8, d))

    def run(params, x):
        mine = select_stage_params(params)
        return gpipe(_stage_fn, mine, x, num_microbatches=2)

    got = jax.jit(jax.shard_map(run, mesh=mesh, in_specs=(P(), P("data")),
                                out_specs=P("data"),
                                check_vma=False))(params, x)
    want = _sequential(params, x)
    assert jnp.max(jnp.abs(got - want)) < TOL


def test_gpipe_error_names_axis_and_nearest_counts():
    """The indivisible-batch error names the axis size and suggests the
    nearest valid microbatch counts (divisors of the batch)."""
    mesh = make_mesh(pipe=2, devices=jax.devices()[:2])
    params = _stacked_params(2, 4)
    x = jnp.zeros((6, 4))
    sm = jax.shard_map(
        lambda params, x: gpipe(_stage_fn, select_stage_params(params), x,
                                num_microbatches=4),
        mesh=mesh, in_specs=(P(), P()), out_specs=P(), check_vma=False)
    with pytest.raises(ValueError) as ei:
        sm(params, x)
    msg = str(ei.value)
    assert "size 6" in msg and "num_microbatches=4" in msg
    assert "3 or 6" in msg  # nearest divisors of 6 around 4


def test_select_stage_params_pytree():
    """Direct unit test (previously only exercised through the
    transformer example): slicing a stacked pytree of dicts per stage."""
    mesh = make_mesh(pipe=4, devices=jax.devices()[:4])
    stacked = {"w": jnp.arange(4 * 3).reshape(4, 3).astype(jnp.float32),
               "b": jnp.arange(4.0)}
    out = jax.jit(jax.shard_map(
        lambda p: select_stage_params(p)["w"][None],
        mesh=mesh, in_specs=(P(),), out_specs=P(PIPE_AXIS),
        check_vma=False))(stacked)
    np.testing.assert_array_equal(np.asarray(out),
                                  np.asarray(stacked["w"]))
    outb = jax.jit(jax.shard_map(
        lambda p: select_stage_params(p)["b"][None],
        mesh=mesh, in_specs=(P(),), out_specs=P(PIPE_AXIS),
        check_vma=False))(stacked)
    np.testing.assert_array_equal(np.asarray(outb).ravel(),
                                  np.asarray(stacked["b"]))


# ---------------------------------------------------------------------------
# The 1F1B MPMD schedule plan (the dryrun surface: no hardware, no jax)
# ---------------------------------------------------------------------------

def _check_plan_valid(plan):
    """Every dependency points to an EARLIER tick, and the plan fires
    exactly one forward and one backward per (stage, microbatch)."""
    S, m = plan.n_stages, plan.num_microbatches
    fwd_tick, bwd_tick = {}, {}
    for t, tick in enumerate(plan.ticks):
        for a in tick:
            if a.phase == "F":
                assert (a.stage, a.mb) not in fwd_tick
                fwd_tick[(a.stage, a.mb)] = t
                if a.stage > 0:
                    assert fwd_tick[(a.stage - 1, a.mb)] < t
            else:
                assert (a.stage, a.mb) not in bwd_tick
                bwd_tick[(a.stage, a.mb)] = t
                assert fwd_tick[(a.stage, a.mb)] < t
                if a.stage < S - 1:
                    assert bwd_tick[(a.stage + 1, a.mb)] < t
    assert set(fwd_tick) == {(s, i) for s in range(S) for i in range(m)}
    assert set(bwd_tick) == set(fwd_tick)
    # Backwards execute in microbatch order at EVERY stage — the
    # bitwise gradient-accumulation contract between schedules.
    for s in range(S):
        ticks = [bwd_tick[(s, i)] for i in range(m)]
        assert ticks == sorted(ticks)


@pytest.mark.parametrize("schedule", ["1f1b", "gpipe"])
@pytest.mark.parametrize("S,m,v", [(2, 2, 1), (4, 8, 1), (4, 8, 2),
                                   (4, 4, 4), (8, 4, 2), (3, 5, 1)])
def test_schedule_plan_valid(schedule, S, m, v):
    if S % v != 0:
        pytest.skip("interleave must divide stages")
    _check_plan_valid(schedule_plan(S, m, schedule, v))


def test_schedule_plan_1f1b_bounds_activation_memory():
    """1F1B's reason to exist at equal bubble: in-flight stage-boundary
    activations bounded by the stage depth, while GPipe grows with the
    microbatch count."""
    f = schedule_plan(4, 16, "1f1b")
    g = schedule_plan(4, 16, "gpipe")
    assert g.peak_activations == (4 - 1) * 16
    assert f.peak_activations <= 3 * 4
    assert f.peak_activations < g.peak_activations


def test_schedule_plan_interleave_shrinks_bubble():
    """Interleaved virtual stages fill the ramp: at a fixed executor
    count, splitting the model into more round-robin chunks lowers the
    idle fraction (arXiv:2412.14374's interleaved-1F1B claim, gated
    structurally with no hardware)."""
    flat = schedule_plan(4, 8, "1f1b", interleave=1)
    inter = schedule_plan(4, 8, "1f1b", interleave=2)
    assert inter.bubble_fraction < flat.bubble_fraction
    # Same comparison at a fixed FOUR-executor fleet: 8 chunks over 4
    # executors vs 4 stages over 4 executors.
    flat4 = schedule_plan(4, 4, "1f1b", interleave=1)
    inter4 = schedule_plan(8, 4, "1f1b", interleave=2)
    assert flat4.n_executors == inter4.n_executors == 4
    assert inter4.bubble_fraction < flat4.bubble_fraction


def test_schedule_plan_validation():
    with pytest.raises(ValueError, match="does not divide"):
        schedule_plan(4, 8, "1f1b", interleave=3)
    with pytest.raises(ValueError, match="nearest valid interleave"):
        schedule_plan(6, 8, "1f1b", interleave=4)
    with pytest.raises(ValueError, match="expected one of"):
        schedule_plan(4, 8, "zigzag")
    with pytest.raises(ValueError, match=">= 1"):
        schedule_plan(0, 8)


def test_pipeline_env_knobs(monkeypatch):
    monkeypatch.setenv(PL.SCHEDULE_ENV, "bogus")
    with pytest.raises(ValueError, match="HVD_TPU_PIPELINE_SCHEDULE"):
        PL.validate_env()
    monkeypatch.setenv(PL.SCHEDULE_ENV, "gpipe")
    monkeypatch.setenv(PL.INTERLEAVE_ENV, "x")
    with pytest.raises(ValueError, match="HVD_TPU_PIPELINE_INTERLEAVE"):
        PL.validate_env()
    monkeypatch.setenv(PL.INTERLEAVE_ENV, "2")
    PL.validate_env()
    assert schedule_plan(4, 4).schedule == "gpipe"
    assert schedule_plan(4, 4).interleave == 2
    monkeypatch.delenv(PL.SCHEDULE_ENV)
    monkeypatch.delenv(PL.INTERLEAVE_ENV)
    assert schedule_plan(4, 4).schedule == "1f1b"


def test_pipeline_knobs_in_hello_env_fingerprint(monkeypatch):
    """The schedule knobs select the dispatch order of compiled
    programs — they ride the HELLO env fingerprint like the overlap
    knob."""
    from horovod_tpu.ops import compression as compression_mod

    assert "HVD_TPU_PIPELINE_SCHEDULE" in compression_mod._SPMD_ENV_KNOBS
    assert "HVD_TPU_PIPELINE_INTERLEAVE" in compression_mod._SPMD_ENV_KNOBS
    monkeypatch.setenv(PL.SCHEDULE_ENV, "1f1b")
    fp_a = compression_mod.env_fingerprint()
    monkeypatch.setenv(PL.SCHEDULE_ENV, "gpipe")
    fp_b = compression_mod.env_fingerprint()
    assert fp_a != fp_b


def test_init_rejects_malformed_pipeline_env(monkeypatch):
    import horovod_tpu as H

    monkeypatch.setenv(PL.SCHEDULE_ENV, "sideways")
    with pytest.raises(ValueError, match="HVD_TPU_PIPELINE_SCHEDULE"):
        H.init(devices=jax.devices())


# ---------------------------------------------------------------------------
# The MPMD pipeline train step
# ---------------------------------------------------------------------------

_D = 16


def _pipe_stage0(p, carry, b):
    x, _y = b
    return jnp.tanh(x @ p["w"] + p["b"])


def _pipe_stage_mid(p, carry, b):
    return jnp.tanh(carry @ p["w"] + p["b"])


def _pipe_stage_last(p, carry, b):
    _x, y = b
    pred = carry @ p["w"] + p["b"]
    return jnp.mean((pred - y) ** 2)


def _pipe_chain(n_stages=4):
    import horovod_tpu as H

    stages = ([_pipe_stage0]
              + [_pipe_stage_mid] * (n_stages - 2) + [_pipe_stage_last])
    return H.ChainedLoss(stages)


def _pipe_params(key, n_stages=4):
    ks = jax.random.split(key, n_stages)
    return [{"w": jax.random.normal(k, (_D, _D)) * _D ** -0.5,
             "b": jnp.zeros((_D,))} for k in ks]


def _pipe_batch(hvd, key, m=4, per_mb=2):
    from horovod_tpu.parallel.training import shard_batch

    n = hvd.size()
    B = n * m * per_mb
    kx, ky = jax.random.split(key)
    x = jax.random.normal(kx, (B, _D))
    y = jax.random.normal(ky, (B, _D))
    return shard_batch((x, y)), (x, y), B


def _leaves_equal(a, b):
    fa, fb = jax.tree_util.tree_leaves(a), jax.tree_util.tree_leaves(b)
    assert len(fa) == len(fb)
    return all(np.asarray(u).tobytes() == np.asarray(v).tobytes()
               for u, v in zip(fa, fb))


def _run_steps(step, params, opt, batch, steps=2):
    p, s = params, opt.init(params)
    loss = None
    for _ in range(steps):
        p, s, loss = step(p, s, batch)
    jax.block_until_ready(jax.tree_util.tree_leaves(p))
    return p, float(loss)


def test_pipeline_step_1f1b_bitwise_equals_gpipe_leg(hvd):
    """The tentpole bitwise gate: same per-stage executables, same
    microbatch accumulation order — the 1F1B interleaving (with
    streamed partial-cycle reduction) reproduces the GPipe-ordered
    dispatch (reduction serialized after a flush fence) bit for bit,
    loss included."""
    chain = _pipe_chain()
    params = _pipe_params(jax.random.PRNGKey(0))
    batch, _, _ = _pipe_batch(hvd, jax.random.PRNGKey(1))
    opt = optax.adam(1e-3)
    kw = dict(num_microbatches=4, fusion_threshold=_D * _D * 4)
    step_f = make_pipeline_train_step(chain, opt, schedule="1f1b", **kw)
    step_g = make_pipeline_train_step(chain, opt, schedule="gpipe", **kw)
    p_f, l_f = _run_steps(step_f, params, opt, batch, 3)
    p_g, l_g = _run_steps(step_g, params, opt, batch, 3)
    assert step_f.plan.schedule == "1f1b"
    assert step_f.bucket_count >= 2 * len(params)
    assert l_f == l_g
    assert _leaves_equal(p_f, p_g)


def test_pipeline_step_interleaved_bitwise(hvd):
    """Interleave changes only the dispatch order — results stay
    bitwise (accumulation order per stage is microbatch order under
    every interleave depth)."""
    chain = _pipe_chain(4)
    params = _pipe_params(jax.random.PRNGKey(0), 4)
    batch, _, _ = _pipe_batch(hvd, jax.random.PRNGKey(1))
    opt = optax.sgd(0.1)
    kw = dict(num_microbatches=4, fusion_threshold=_D * _D * 4)
    p_1, _ = _run_steps(make_pipeline_train_step(
        chain, opt, schedule="1f1b", interleave=1, **kw),
        params, opt, batch)
    p_2, _ = _run_steps(make_pipeline_train_step(
        chain, opt, schedule="1f1b", interleave=2, **kw),
        params, opt, batch)
    assert _leaves_equal(p_1, p_2)


def test_pipeline_step_matches_monolithic_reference(hvd):
    """Loss/grad parity with the monolithic evaluation: one SGD step
    through the pipeline equals p0 - lr * grad(mean-over-microbatches
    loss) (allclose — per-stage programs compile with different fusion
    decisions than one whole-graph backward)."""
    m, n = 4, hvd.size()
    chain = _pipe_chain()
    params = _pipe_params(jax.random.PRNGKey(0))
    batch, (x, y), B = _pipe_batch(hvd, jax.random.PRNGKey(1), m=m)
    opt = optax.sgd(0.1)
    step = make_pipeline_train_step(chain, opt, num_microbatches=m,
                                    schedule="1f1b")

    def mb_of(arr, i):
        lb = B // n
        return jnp.concatenate(
            [arr[r * lb:(r + 1) * lb].reshape(
                m, lb // m, _D)[i] for r in range(n)], 0)

    def ref_loss(p):
        tot = 0.0
        for i in range(m):
            tot = tot + chain(p, (mb_of(x, i), mb_of(y, i)))
        return tot / m

    g_ref = jax.grad(ref_loss)(params)
    p1, _, l1 = step(params, opt.init(params), batch)
    np.testing.assert_allclose(float(l1), float(ref_loss(params)),
                               rtol=1e-5)
    for a, p0, g in zip(jax.tree_util.tree_leaves(p1),
                        jax.tree_util.tree_leaves(params),
                        jax.tree_util.tree_leaves(g_ref)):
        np.testing.assert_allclose(
            np.asarray(a), np.asarray(p0) - 0.1 * np.asarray(g),
            rtol=2e-5, atol=2e-6)


def test_pipeline_steady_state_cache_replay(hvd):
    """After warmup every stage's partial cycle replays from the
    response cache: further steps add ZERO negotiation misses."""
    import horovod_tpu.core.state as state_mod

    chain = _pipe_chain()
    params = _pipe_params(jax.random.PRNGKey(0))
    batch, _, _ = _pipe_batch(hvd, jax.random.PRNGKey(1))
    opt = optax.sgd(0.1)
    step = make_pipeline_train_step(chain, opt, num_microbatches=4,
                                    schedule="1f1b",
                                    fusion_threshold=_D * _D * 4)
    p, s = params, opt.init(params)
    for _ in range(2):
        p, s, _loss = step(p, s, batch)
    st = state_mod.global_state()
    misses0 = st.response_cache.stats.misses
    replayed0 = st.response_cache.stats.replayed_tensors
    p, s, _loss = step(p, s, batch)
    jax.block_until_ready(jax.tree_util.tree_leaves(p))
    assert st.response_cache.stats.misses == misses0
    n_leaves = len(jax.tree_util.tree_leaves(params))
    assert st.response_cache.stats.replayed_tensors - replayed0 \
        == n_leaves


def test_pipeline_telemetry_and_memory(hvd):
    """pipeline.microbatches counts m per step; bubble_seconds records
    the exposed reduction wait; the in-flight activation gauge reports
    the 1F1B bound (below the GPipe peak at m > S)."""
    import horovod_tpu as H

    m = 8
    chain = _pipe_chain(3)
    params = _pipe_params(jax.random.PRNGKey(0), 3)
    batch, _, _ = _pipe_batch(hvd, jax.random.PRNGKey(1), m=m)
    opt = optax.sgd(0.1)
    base = H.metrics().get("pipeline.microbatches", {}).get("value", 0)
    bubbles0 = H.metrics().get(
        "pipeline.bubble_seconds", {}).get("count", 0)
    step_f = make_pipeline_train_step(chain, opt, num_microbatches=m,
                                      schedule="1f1b")
    _run_steps(step_f, params, opt, batch, 1)
    snap = H.metrics()
    assert snap["pipeline.microbatches"]["value"] - base == m
    assert snap["pipeline.bubble_seconds"]["count"] == bubbles0 + 1
    peak_f = snap["pipeline.inflight_activations"]["value"]
    step_g = make_pipeline_train_step(chain, opt, num_microbatches=m,
                                      schedule="gpipe")
    _run_steps(step_g, params, opt, batch, 1)
    peak_g = H.metrics()["pipeline.inflight_activations"]["value"]
    assert peak_f < peak_g, (peak_f, peak_g)
    assert peak_g == (3 - 1) * m


def test_pipeline_batch_validation_names_counts(hvd):
    """A batch whose axis does not divide by num_microbatches fails
    naming the axis size and the nearest valid counts; a microbatch
    that does not shard by the replica count fails naming both."""
    from horovod_tpu.parallel.training import shard_batch

    chain = _pipe_chain()
    params = _pipe_params(jax.random.PRNGKey(0))
    opt = optax.sgd(0.1)
    n = hvd.size()
    step = make_pipeline_train_step(chain, opt, num_microbatches=3)
    B = 4 * n  # divisible by n, not by 3 microbatches
    x = jnp.zeros((B, _D))
    with pytest.raises(ValueError) as ei:
        step(params, opt.init(params), shard_batch((x, x)))
    assert f"size {B}" in str(ei.value)
    assert "num_microbatches=3" in str(ei.value)
    assert "nearest valid counts" in str(ei.value)
    # Divisible by m at the global axis but not per replica.
    step2 = make_pipeline_train_step(chain, opt, num_microbatches=n * 2)
    x2 = jnp.zeros((2 * n, _D))
    with pytest.raises(ValueError, match="per-replica batch"):
        step2(params, opt.init(params), shard_batch((x2, x2)))


def test_pipeline_single_stage_rejected(hvd):
    with pytest.raises(ValueError, match="at least 2 stages"):
        make_pipeline_train_step([_pipe_stage_last], optax.sgd(0.1),
                                 num_microbatches=2)


# ---------------------------------------------------------------------------
# Sub-mesh placement (mp × pipeline; hvd-fuse)
# ---------------------------------------------------------------------------

def _run_steps_placed(step, params, opt, batch, steps=2):
    p, s = params, [opt.init(pp) for pp in params]
    loss = None
    for _ in range(steps):
        p, s, loss = step(p, s, batch)
    jax.block_until_ready(jax.tree_util.tree_leaves(p))
    return p, float(loss)


def _placed_batch(n_rep, m=4, per_mb=2, seed=1):
    B = n_rep * m * per_mb
    kx, ky = jax.random.split(jax.random.PRNGKey(seed))
    return (jax.random.normal(kx, (B, _D)),
            jax.random.normal(ky, (B, _D)))


def test_stage_submeshes_split(hvd):
    meshes = PL.stage_submeshes(4)
    assert len(meshes) == 4
    devs = [tuple(mk.devices.flat) for mk in meshes]
    assert sum(len(d) for d in devs) == len(jax.devices())
    assert len({d for block in devs for d in block}) == len(jax.devices())
    mp = PL.stage_submeshes(2, model=2)
    assert mp[0].shape["hvd"] == 2 and mp[0].shape["model"] == 2
    with pytest.raises(ValueError, match="do not split"):
        PL.stage_submeshes(3)
    with pytest.raises(ValueError, match="not divisible by"):
        PL.stage_submeshes(4, model=3)


def test_pipeline_placed_1f1b_bitwise_equals_gpipe(hvd):
    """The placement bitwise gate: per-stage executables on their own
    sub-meshes, gradients through per-stage fused reduce+apply
    programs — 1F1B (applies streamed at each stage's last backward)
    reproduces the GPipe-ordered dispatch bit for bit."""
    meshes = PL.stage_submeshes(4)
    chain = _pipe_chain()
    params = _pipe_params(jax.random.PRNGKey(0))
    batch = _placed_batch(2)
    opt = optax.adam(1e-3)
    kw = dict(num_microbatches=4, stage_meshes=meshes)
    step_f = make_pipeline_train_step(chain, opt, schedule="1f1b", **kw)
    step_g = make_pipeline_train_step(chain, opt, schedule="gpipe", **kw)
    p_f, l_f = _run_steps_placed(step_f, params, opt, batch, 3)
    p_g, l_g = _run_steps_placed(step_g, params, opt, batch, 3)
    assert step_f.placed and step_f.stage_meshes == meshes
    assert l_f == l_g
    assert _leaves_equal(p_f, p_g)


def test_pipeline_placed_executables_live_on_declared_submeshes(hvd):
    """Real MPMD placement: stage k's updated parameters come back
    committed to exactly stage k's sub-mesh devices."""
    meshes = PL.stage_submeshes(4)
    chain = _pipe_chain()
    params = _pipe_params(jax.random.PRNGKey(0))
    batch = _placed_batch(2)
    opt = optax.sgd(0.1)
    step = make_pipeline_train_step(chain, opt, num_microbatches=4,
                                    stage_meshes=meshes)
    p1, _ = _run_steps_placed(step, params, opt, batch, 1)
    for k, stage_params in enumerate(p1):
        want = set(meshes[k].devices.flat)
        for leaf in jax.tree_util.tree_leaves(stage_params):
            assert set(leaf.sharding.device_set) == want, k


def test_pipeline_placed_matches_unplaced_allclose(hvd):
    """Placed and unplaced steps compute the same mean-loss SGD update
    (allclose, not bitwise: the reduction arithmetic moves from the
    dynamic bucket path over 8 replicas to an in-program psum over
    each stage's 2)."""
    chain = _pipe_chain()
    params = _pipe_params(jax.random.PRNGKey(0))
    batch = _placed_batch(8, per_mb=1)  # divides for both layouts
    opt = optax.sgd(0.1)
    step_u = make_pipeline_train_step(chain, opt, num_microbatches=4)
    step_p = make_pipeline_train_step(chain, opt, num_microbatches=4,
                                      stage_meshes=PL.stage_submeshes(4))
    p_u, l_u = _run_steps(step_u, params, opt, batch, 1)
    p_p, l_p = _run_steps_placed(step_p, params, opt, batch, 1)
    np.testing.assert_allclose(l_p, l_u, rtol=1e-6)
    for a, b in zip(jax.tree_util.tree_leaves(p_p),
                    jax.tree_util.tree_leaves(p_u)):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=2e-5, atol=2e-6)


def _mp_stage0(p, carry, b):
    from horovod_tpu.parallel.tensor import local_shard, tp_mlp

    x, _y = b
    return tp_mlp(x, local_shard(p["w"], 1), None,
                  local_shard(p["w2"], 0), None)


def _mp_stage_last(p, carry, b):
    _x, y = b
    pred = carry @ p["w"] + p["b"]
    return jnp.mean((pred - y) ** 2)


def test_pipeline_placed_mp_composition_bitwise(hvd):
    """mp × pipeline: each stage's sub-mesh carries a model axis and
    the stage body runs the fused tensor-parallel closers inside it —
    1f1b ≡ gpipe stays bitwise under the composition."""
    import horovod_tpu as H

    meshes = PL.stage_submeshes(2, model=2)
    chain = H.ChainedLoss([_mp_stage0, _mp_stage_last])
    k1, k2, k3 = jax.random.split(jax.random.PRNGKey(0), 3)
    params = [
        {"w": jax.random.normal(k1, (_D, _D)) * _D ** -0.5,
         "w2": jax.random.normal(k2, (_D, _D)) * _D ** -0.5},
        {"w": jax.random.normal(k3, (_D, _D)) * _D ** -0.5,
         "b": jnp.zeros((_D,))},
    ]
    batch = _placed_batch(2)
    opt = optax.sgd(0.1)
    kw = dict(num_microbatches=4, stage_meshes=meshes)
    step_f = make_pipeline_train_step(chain, opt, schedule="1f1b", **kw)
    step_g = make_pipeline_train_step(chain, opt, schedule="gpipe", **kw)
    p_f, l_f = _run_steps_placed(step_f, params, opt, batch, 2)
    p_g, l_g = _run_steps_placed(step_g, params, opt, batch, 2)
    assert np.isfinite(l_f)
    assert l_f == l_g
    assert _leaves_equal(p_f, p_g)


def test_pipeline_placed_validation(hvd):
    chain = _pipe_chain()
    params = _pipe_params(jax.random.PRNGKey(0))
    opt = optax.sgd(0.1)
    with pytest.raises(ValueError, match="one sub-mesh per stage"):
        make_pipeline_train_step(chain, opt, num_microbatches=4,
                                 stage_meshes=PL.stage_submeshes(2))
    bad = make_mesh(pipe=2, devices=jax.devices()[:2])
    with pytest.raises(ValueError, match="replica"):
        make_pipeline_train_step(chain, opt, num_microbatches=4,
                                 stage_meshes=[bad] * 4)
    step = make_pipeline_train_step(chain, opt, num_microbatches=4,
                                    stage_meshes=PL.stage_submeshes(4))
    batch = _placed_batch(2)
    with pytest.raises(ValueError, match="PER-STAGE opt_state"):
        step(params, opt.init(params), batch)
