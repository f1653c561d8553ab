"""Transformer LM tests: every parallelism composition must reproduce the
single-device forward, and the combined train step must learn."""

import jax
import jax.numpy as jnp
import numpy as np
import optax
import pytest
from jax.sharding import PartitionSpec as P

from horovod_tpu.core.topology import make_mesh
from horovod_tpu.models.transformer import (ParallelAxes,
                                            TransformerConfig,
                                            chained_lm_loss,
                                            chained_lm_params, forward,
                                            init_transformer,
                                            make_loss_fn,
                                            next_token_nll,
                                            synthetic_lm_batch)
from horovod_tpu.parallel.training import (make_parallel_train_step,
                                           shard_parallel_batch)

CFG = TransformerConfig(vocab_size=64, d_model=32, n_heads=4, n_layers=2,
                        d_ff=64, max_seq_len=128, block_q=16, block_k=16)
TOL = 2e-4


def _data(cfg=CFG, batch=8, seq=32, seed=0):
    key = jax.random.PRNGKey(seed)
    kp, kd = jax.random.split(key)
    params = init_transformer(kp, cfg)
    tokens, targets = synthetic_lm_batch(kd, batch, seq, cfg.vocab_size)
    return params, tokens, targets


def _single_device_logits(params, tokens, cfg=CFG):
    logits, aux = forward(params, tokens, cfg, ParallelAxes(data=None))
    return logits, aux


@pytest.mark.parametrize("axes_kw,mesh_kw,batch_spec", [
    (dict(data="data"), dict(data=8), P("data", None)),
    (dict(data="data", model="model"), dict(data=2, model=4),
     P("data", None)),
    (dict(data="data", seq="seq"), dict(data=2, seq=4),
     P("data", "seq")),
    (dict(data="data", seq="seq", model="model"),
     dict(data=2, seq=2, model=2), P("data", "seq")),
])
def test_parallel_forward_matches_single_device(axes_kw, mesh_kw,
                                                batch_spec):
    mesh = make_mesh(**mesh_kw)
    ax = ParallelAxes(**axes_kw)
    params, tokens, targets = _data()

    def local(params, tokens):
        logits, aux = forward(params, tokens, CFG, ax)
        return logits

    out_spec = P(ax.data, ax.seq, None)
    got = jax.jit(jax.shard_map(local, mesh=mesh,
                                in_specs=(P(), batch_spec),
                                out_specs=out_spec,
                                check_vma=False))(params, tokens)
    want, _ = _single_device_logits(params, tokens)
    assert np.max(np.abs(np.asarray(got) - np.asarray(want))) < TOL


def test_pipeline_forward_matches_single_device():
    mesh = make_mesh(data=2, pipe=2, devices=jax.devices()[:4])
    ax = ParallelAxes(data="data", pipe="pipe", num_microbatches=2)
    params, tokens, targets = _data()

    def local(params, tokens):
        logits, aux = forward(params, tokens, CFG, ax)
        return logits

    got = jax.jit(jax.shard_map(local, mesh=mesh,
                                in_specs=(P(), P("data", None)),
                                out_specs=P("data", None, None),
                                check_vma=False))(params, tokens)
    want, _ = _single_device_logits(params, tokens)
    assert np.max(np.abs(np.asarray(got) - np.asarray(want))) < TOL


def test_moe_transformer_runs_and_is_finite():
    cfg = TransformerConfig(vocab_size=64, d_model=32, n_heads=4,
                            n_layers=2, d_ff=64, max_seq_len=128,
                            num_experts=4, top_k=2, capacity_factor=4.0,
                            block_q=16, block_k=16)
    mesh = make_mesh(data=4, devices=jax.devices()[:4])
    ax = ParallelAxes(data="data", expert="data")
    params, tokens, targets = _data(cfg)

    loss_fn = make_loss_fn(cfg, ax, mesh_axes=mesh.axis_names)
    sm = jax.shard_map(loss_fn, mesh=mesh,
                       in_specs=(P(), P("data", None)), out_specs=P(),
                       check_vma=False)
    loss = jax.jit(sm)(params, (tokens, targets))
    assert bool(jnp.isfinite(loss))
    grads = jax.jit(jax.grad(sm))(params, (tokens, targets))
    flat, _ = jax.tree_util.tree_flatten(grads)
    assert all(bool(jnp.all(jnp.isfinite(g))) for g in flat)
    # Expert + router weights actually receive gradient.
    assert bool(jnp.any(grads["layers"]["router"] != 0))
    assert bool(jnp.any(grads["layers"]["moe_w_in"] != 0))


def test_combined_train_step_learns():
    # dp=2 × sp=2 × tp=2: the full jitted step on an 8-device mesh.
    mesh = make_mesh(data=2, seq=2, model=2)
    ax = ParallelAxes(data="data", seq="seq", model="model")
    params, tokens, targets = _data(batch=8)

    loss_fn = make_loss_fn(CFG, ax, mesh_axes=mesh.axis_names)
    opt = optax.adam(1e-2)
    step = make_parallel_train_step(loss_fn, opt, mesh,
                                    P("data", "seq"), donate=False)
    batch = shard_parallel_batch((tokens, targets), mesh,
                                 P("data", "seq"))
    opt_state = opt.init(params)
    losses = []
    for _ in range(8):
        params, opt_state, loss = step(params, opt_state, batch)
        losses.append(float(loss))
    assert losses[-1] < losses[0] - 0.1, losses


def test_parallel_gradients_match_single_device():
    mesh = make_mesh(data=2, seq=2, model=2)
    ax = ParallelAxes(data="data", seq="seq", model="model")
    params, tokens, targets = _data(batch=4)

    loss_fn = make_loss_fn(CFG, ax, mesh_axes=mesh.axis_names)
    sm = jax.shard_map(loss_fn, mesh=mesh,
                       in_specs=(P(), P("data", "seq")), out_specs=P(),
                       check_vma=False)
    got = jax.jit(jax.grad(sm))(params, (tokens, targets))

    single_loss = make_loss_fn(CFG, ParallelAxes(data=None),
                               mesh_axes=())
    want = jax.jit(jax.grad(
        lambda p: single_loss(p, (tokens, targets))))(params)
    flat_got, _ = jax.tree_util.tree_flatten(got)
    flat_want, _ = jax.tree_util.tree_flatten(want)
    for a, b in zip(flat_got, flat_want):
        assert np.max(np.abs(np.asarray(a) - np.asarray(b))) < 5e-4


def test_remat_matches_exact_gradients():
    # cfg.remat must change memory/FLOPs only — loss and gradients are
    # bit-compatible with the non-remat trace (same ops, same order).
    import dataclasses

    params, tokens, targets = _data(batch=4)
    base = make_loss_fn(CFG, ParallelAxes(data=None), mesh_axes=())
    remat_cfg = dataclasses.replace(CFG, remat=True)
    rem = make_loss_fn(remat_cfg, ParallelAxes(data=None), mesh_axes=())

    l0, g0 = jax.jit(jax.value_and_grad(
        lambda p: base(p, (tokens, targets))))(params)
    l1, g1 = jax.jit(jax.value_and_grad(
        lambda p: rem(p, (tokens, targets))))(params)
    np.testing.assert_allclose(np.asarray(l0), np.asarray(l1), rtol=1e-6)
    for a, b in zip(jax.tree_util.tree_leaves(g0),
                    jax.tree_util.tree_leaves(g1)):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=1e-5, atol=1e-6)


def test_chunked_loss_matches_dense():
    # cfg.loss_chunk must change memory only: loss and gradients match
    # the full-logits computation.
    import dataclasses

    params, tokens, targets = _data(batch=4)
    dense = make_loss_fn(CFG, ParallelAxes(data=None), mesh_axes=())
    chunked_cfg = dataclasses.replace(CFG, loss_chunk=8)
    chunked = make_loss_fn(chunked_cfg, ParallelAxes(data=None),
                           mesh_axes=())

    l0, g0 = jax.jit(jax.value_and_grad(
        lambda p: dense(p, (tokens, targets))))(params)
    l1, g1 = jax.jit(jax.value_and_grad(
        lambda p: chunked(p, (tokens, targets))))(params)
    np.testing.assert_allclose(np.asarray(l0), np.asarray(l1), rtol=1e-5)
    for a, b in zip(jax.tree_util.tree_leaves(g0),
                    jax.tree_util.tree_leaves(g1)):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=1e-4, atol=1e-5)


def test_chunked_loss_composes_with_seq_parallel():
    import dataclasses

    mesh = make_mesh(data=2, seq=4)
    ax = ParallelAxes(data="data", seq="seq")
    cfg = dataclasses.replace(CFG, loss_chunk=4, remat=True)
    params, tokens, targets = _data(batch=4)
    loss_fn = make_loss_fn(cfg, ax, mesh_axes=mesh.axis_names)
    sm = jax.shard_map(loss_fn, mesh=mesh,
                       in_specs=(P(), P("data", "seq")), out_specs=P(),
                       check_vma=False)
    loss, grads = jax.jit(jax.value_and_grad(sm))(params,
                                                  (tokens, targets))
    single = make_loss_fn(CFG, ParallelAxes(data=None), mesh_axes=())
    want_l, want_g = jax.jit(jax.value_and_grad(
        lambda p: single(p, (tokens, targets))))(params)
    np.testing.assert_allclose(np.asarray(loss), np.asarray(want_l),
                               rtol=1e-4)
    for a, b in zip(jax.tree_util.tree_leaves(grads),
                    jax.tree_util.tree_leaves(want_g)):
        assert np.max(np.abs(np.asarray(a) - np.asarray(b))) < 5e-4


def test_remat_composes_with_parallel_axes():
    import dataclasses

    mesh = make_mesh(data=2, seq=2, model=2)
    ax = ParallelAxes(data="data", seq="seq", model="model")
    cfg = dataclasses.replace(CFG, remat=True)
    params, tokens, targets = _data(batch=4)
    loss_fn = make_loss_fn(cfg, ax, mesh_axes=mesh.axis_names)
    sm = jax.shard_map(loss_fn, mesh=mesh,
                       in_specs=(P(), P("data", "seq")), out_specs=P(),
                       check_vma=False)
    loss, grads = jax.jit(jax.value_and_grad(sm))(params,
                                                  (tokens, targets))
    assert np.isfinite(np.asarray(loss))
    # Against the non-remat single-device reference.
    single = make_loss_fn(CFG, ParallelAxes(data=None), mesh_axes=())
    want = jax.jit(jax.grad(lambda p: single(p, (tokens, targets))))(params)
    for a, b in zip(jax.tree_util.tree_leaves(grads),
                    jax.tree_util.tree_leaves(want)):
        assert np.max(np.abs(np.asarray(a) - np.asarray(b))) < 5e-4


def test_pipeline_rejects_indivisible_layers():
    mesh = make_mesh(pipe=3, devices=jax.devices()[:3])
    ax = ParallelAxes(data=None, pipe="pipe")
    params, tokens, _ = _data()
    sm = jax.shard_map(
        lambda p, t: forward(p, t, CFG, ax)[0], mesh=mesh,
        in_specs=(P(), P()), out_specs=P(), check_vma=False)
    with pytest.raises(ValueError, match="not divisible"):
        sm(params, tokens)


# ---------------------------------------------------------------------------
# next_token_nll: the one custom-VJP loss behind make_loss_fn (dense and
# chunked) and chained_lm_loss's head stage.
# ---------------------------------------------------------------------------

ROWS, DIM, VOCAB = 48, 16, 503          # 503: not a multiple of 128


def _plain_nll(hidden, unembed, targets):
    """The log_softmax formula in float32."""
    logits = jnp.dot(hidden.astype(jnp.float32),
                     unembed.astype(jnp.float32))
    logp = jax.nn.log_softmax(logits, axis=-1)
    return -jnp.take_along_axis(logp, targets[..., None], axis=-1)[..., 0]


def _loss_inputs(dtype):
    kh, kw, kt, kr = jax.random.split(jax.random.PRNGKey(48), 4)
    hidden = jax.random.normal(kh, (ROWS, DIM), jnp.float32).astype(dtype)
    unembed = (jax.random.normal(kw, (DIM, VOCAB), jnp.float32)
               * 0.5).astype(dtype)
    targets = jax.random.randint(kt, (ROWS,), 0, VOCAB)
    targets = targets.at[0].set(0).at[1].set(VOCAB - 1)   # the two edges
    weights = jax.random.uniform(kr, (ROWS,), jnp.float32, 0.2, 2.0)
    return hidden, unembed, targets, weights


def _mean(nll, h, w, t, weights):
    return jnp.mean(nll(h, w, t))


def _three_times(nll, h, w, t, weights):
    return 3.0 * jnp.mean(nll(h, w, t))


def _row_weighted(nll, h, w, t, weights):
    return jnp.sum(weights * nll(h, w, t))


def _checkpointed_scan(nll, h, w, t, weights):
    # The chunked caller: chunks of rows, each rematerialised.
    n = 4
    hs, ts = h.reshape(n, ROWS // n, DIM), t.reshape(n, ROWS // n)

    @jax.checkpoint
    def chunk(hc, tc):
        return jnp.sum(nll(hc, w, tc))

    total, _ = jax.lax.scan(lambda acc, xt: (acc + chunk(*xt), None),
                            jnp.zeros((), jnp.float32), (hs, ts))
    return total / ROWS


def _data_shard_map(nll, h, w, t, weights):
    mesh = make_mesh(data=4, devices=jax.devices()[:4])

    def local(h, w, t):
        return jax.lax.pmean(jnp.mean(nll(h, w, t)), "data")

    return jax.shard_map(local, mesh=mesh,
                         in_specs=(P("data"), P(), P("data")),
                         out_specs=P(), check_vma=False)(h, w, t)


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16],
                         ids=["float32", "bfloat16"])
@pytest.mark.parametrize("use", [_mean, _three_times, _row_weighted,
                                 _checkpointed_scan, _data_shard_map],
                         ids=lambda f: f.__name__.strip("_"))
def test_next_token_nll_matches_log_softmax(use, dtype):
    # Loss AND both gradients against the plain formula in float32 on the
    # same (rounded) operands.  The gradient of the logits is kept in
    # bfloat16 (2^-9 a number, signs mixed), and bfloat16 operands get
    # bfloat16 gradients back.
    h, w, t, weights = _loss_inputs(dtype)
    got_l, got_g = jax.jit(jax.value_and_grad(
        lambda h, w: use(next_token_nll, h, w, t, weights),
        argnums=(0, 1)))(h, w)
    want_l, want_g = jax.jit(jax.value_and_grad(
        lambda h, w: use(_plain_nll, h, w, t, weights), argnums=(0, 1)))(
            h.astype(jnp.float32), w.astype(jnp.float32))
    np.testing.assert_allclose(float(got_l), float(want_l), rtol=2e-6)
    for got, want, arg in zip(got_g, want_g, (h, w)):
        assert got.dtype == arg.dtype and got.shape == arg.shape
        got, want = np.asarray(got, np.float32), np.asarray(want)
        gap = np.linalg.norm(got - want) / np.linalg.norm(want)
        assert gap < (3e-3 if dtype == jnp.float32 else 8e-3), gap
    # The edge targets' own columns of d unembed: -hidden's share is there.
    d_w = np.asarray(got_g[1], np.float32)
    for col in (0, VOCAB - 1):
        want_col = np.asarray(want_g[1])[:, col]
        assert np.linalg.norm(d_w[:, col] - want_col) \
            < 2e-2 * np.linalg.norm(want_col)


def _eqns(jaxpr):
    """Every equation of a jaxpr and of the jaxprs inside it."""
    for eqn in jaxpr.eqns:
        yield eqn
        for sub in jax.core.jaxprs_in_params(eqn.params):
            yield from _eqns(sub)


def test_loss_gradient_is_one_bfloat16_array_and_no_scatter():
    # The mechanism, where no chip is: the gradient of make_loss_fn's loss
    # scatters into nothing as wide as the logits (the embedding's own
    # scatter-add into [vocab, d] stays), the loss keeps ONE [rows, vocab]
    # array for its backward, in bfloat16, and the backward forms no
    # float32 one.
    params, tokens, targets = _data(batch=2, seq=16)
    loss_fn = make_loss_fn(CFG, ParallelAxes(data=None), mesh_axes=())
    grad_jaxpr = jax.make_jaxpr(jax.grad(
        lambda p: loss_fn(p, (tokens, targets))))(params)
    scattered = [v.aval.shape for e in _eqns(grad_jaxpr.jaxpr)
                 if e.primitive.name.startswith("scatter")
                 for v in e.outvars]
    assert scattered and all(s[-1] != CFG.vocab_size for s in scattered), \
        scattered

    h, w, t, _ = _loss_inputs(jnp.float32)
    _, pullback = jax.vjp(lambda h, w: next_token_nll(h, w, t), h, w)
    wide = [r for r in jax.tree_util.tree_leaves(pullback)
            if getattr(r, "shape", ()) == (ROWS, VOCAB)]
    assert [r.dtype for r in wide] == [jnp.bfloat16]
    back = jax.make_jaxpr(pullback)(jnp.ones((ROWS,), jnp.float32))
    made = [v.aval for e in _eqns(back.jaxpr) for v in e.outvars]
    assert made and not [a for a in made
                         if a.shape == (ROWS, VOCAB)
                         and a.dtype == jnp.float32]


def test_chained_head_stage_is_the_same_loss():
    # The stream schedule's last stage calls the one loss too: the chain's
    # value and gradients are make_loss_fn's on one device.
    params, tokens, targets = _data(batch=2, seq=16)
    single = make_loss_fn(CFG, ParallelAxes(data=None), mesh_axes=())
    want_l, want_g = jax.jit(jax.value_and_grad(
        lambda p: single(p, (tokens, targets))))(params)
    got_l, got_g = jax.jit(jax.value_and_grad(
        lambda p: chained_lm_loss(CFG)(chained_lm_params(p, CFG),
                                       (tokens, targets))))(params)
    np.testing.assert_allclose(np.asarray(got_l), np.asarray(want_l),
                               rtol=1e-5)
    for a, b in zip(jax.tree_util.tree_leaves(got_g),
                    jax.tree_util.tree_leaves(want_g)):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=1e-4, atol=1e-5)
