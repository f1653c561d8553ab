"""Mixture-of-Experts / expert-parallel tests.

Parity check: the expert-sharded layer (tokens exchanged with all_to_all)
must reproduce the single-group computation when capacity is ample, and
degrade only by dropping when it is not."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import PartitionSpec as P

from horovod_tpu.core.topology import EXPERT_AXIS, make_mesh
from horovod_tpu.parallel.expert import (MoEOutput, init_moe_params,
                                         local_experts, moe_layer)

TOL = 1e-4
E, D, H = 8, 16, 32


def _inputs(tokens=64, seed=0):
    key = jax.random.PRNGKey(seed)
    kx, kp = jax.random.split(key)
    x = jax.random.normal(kx, (tokens, D))
    params = init_moe_params(kp, E, D, H)
    return x, params


def _run(n_devices, x, params, **kw):
    mesh = make_mesh(expert=n_devices, devices=jax.devices()[:n_devices])

    def f(x, params):
        mine = local_experts(params, axis_name=EXPERT_AXIS)
        return moe_layer(x, mine, axis_name=EXPERT_AXIS, num_experts=E,
                         **kw)

    return jax.jit(jax.shard_map(
        f, mesh=mesh, in_specs=(P(EXPERT_AXIS), P()),
        out_specs=MoEOutput(P(EXPERT_AXIS), P(), P()),
        check_vma=False))(x, params)


@pytest.mark.parametrize("top_k", [1, 2])
def test_sharded_matches_single_group(top_k):
    x, params = _inputs()
    # Ample capacity: nothing drops, so 1-group and 4-group answers agree.
    kw = dict(top_k=top_k, capacity_factor=8.0)
    out1, aux1, drop1 = _run(1, x, params, **kw)
    out4, aux4, drop4 = _run(4, x, params, **kw)
    assert float(drop1) == 0.0
    assert float(drop4) == 0.0
    # Fetch to host: the two runs live on different meshes.
    assert np.max(np.abs(np.asarray(out1) - np.asarray(out4))) < TOL


def test_fused_roundtrip_bitwise_equals_unfused():
    # The chunked dispatch -> FFN -> combine round trip (capacity rows
    # are reduction-free) reproduces the unfused program's bytes.
    x, params = _inputs(tokens=256)
    kw = dict(top_k=2, capacity_factor=8.0)
    fused = _run(8, x, params, fuse=True, fuse_chunks=4, **kw)
    unfused = _run(8, x, params, fuse=False, **kw)
    for a, b in zip(fused, unfused):
        assert np.asarray(a).tobytes() == np.asarray(b).tobytes()


def test_moe_output_is_gated_expert_mix():
    # With top_k = E and huge capacity every expert fires: the output must
    # equal the dense mixture sum_e p_e * expert_e(x).
    x, params = _inputs(tokens=32)
    out, _, drop = _run(1, x, params, top_k=E, capacity_factor=float(E))
    assert float(drop) == 0.0
    probs = jax.nn.softmax(x @ params["router"], axis=-1)
    h = jnp.einsum("td,edh->teh", x, params["w_in"])
    dense = jnp.einsum("teh,ehd->ted", jax.nn.gelu(h), params["w_out"])
    want = jnp.einsum("ted,te->td", dense, probs)
    assert jnp.max(jnp.abs(out - want)) < TOL


def test_capacity_drops_tokens():
    x, params = _inputs(tokens=64)
    _, _, drop = _run(1, x, params, top_k=1, capacity_factor=0.25)
    assert float(drop) > 0.0


def test_aux_loss_is_finite_and_positive():
    x, params = _inputs()
    _, aux, _ = _run(4, x, params, top_k=2, capacity_factor=4.0)
    assert bool(jnp.isfinite(aux))
    assert float(aux) > 0.0


# ---------------------------------------------------------------------------
# _top_k_dispatch edge cases (hvd-fuse satellite): pinned BEFORE the
# fused rewrite — the routing arithmetic is the part the chunked hot
# path must preserve exactly, so these run against the function
# directly (no mesh, no collectives).
# ---------------------------------------------------------------------------

def test_top_k_dispatch_capacity_one_admits_first_token_only():
    from horovod_tpu.parallel.expert import _top_k_dispatch

    # Both tokens prefer expert 0; capacity 1 admits only the earlier
    # token (cumsum order) and drops the other.
    probs = jnp.asarray([[0.9, 0.1],
                         [0.8, 0.2]], jnp.float32)
    dispatch, combine, dropped = _top_k_dispatch(probs, k=1, capacity=1)
    assert dispatch.shape == (2, 2, 1)
    assert float(dispatch[0, 0, 0]) == 1.0   # token 0 admitted
    assert float(dispatch[1, 0, 0]) == 0.0   # token 1 over capacity
    assert float(jnp.sum(dispatch[1])) == 0.0
    # Combine carries the gate for the admitted token only.
    assert float(combine[0, 0, 0]) == pytest.approx(0.9)
    assert float(jnp.sum(combine[1])) == 0.0
    assert float(dropped) == pytest.approx(0.5)


def test_top_k_dispatch_all_dropped_token_contributes_zero():
    from horovod_tpu.parallel.expert import _top_k_dispatch

    # Three tokens all racing for expert 0 at capacity 1 with k=1:
    # tokens 1 and 2 lose every round — their dispatch AND combine rows
    # must be exactly zero (the all-dropped token's output is zero, not
    # stale buffer content).
    probs = jnp.asarray([[0.99, 0.01],
                         [0.98, 0.02],
                         [0.97, 0.03]], jnp.float32)
    dispatch, combine, dropped = _top_k_dispatch(probs, k=1, capacity=1)
    assert float(jnp.sum(dispatch[1])) == 0.0
    assert float(jnp.sum(dispatch[2])) == 0.0
    assert float(jnp.sum(combine[1])) == 0.0
    assert float(jnp.sum(combine[2])) == 0.0
    assert float(dropped) == pytest.approx(2.0 / 3.0)


def test_top_k_dispatch_top_k_equals_num_experts():
    from horovod_tpu.parallel.expert import _top_k_dispatch

    # k == E with ample capacity: every token reaches every expert
    # exactly once, each expert's buffer slots fill without collision
    # (admission order interleaves the k greedy rounds, so positions
    # are a permutation of the slots, not token order), and the combine
    # weights are the full softmax row (sum = 1 per token).
    tokens, experts, capacity = 4, 3, 4
    key = jax.random.PRNGKey(3)
    probs = jax.nn.softmax(jax.random.normal(key, (tokens, experts)),
                           axis=-1)
    dispatch, combine, dropped = _top_k_dispatch(probs, k=experts,
                                                 capacity=capacity)
    assert float(dropped) == 0.0
    # One slot per (token, expert) pair.
    per_pair = jnp.sum(dispatch, axis=-1)
    assert bool(jnp.all(per_pair == 1.0))
    # Every expert buffer fills its slots exactly once (a permutation).
    pos = jnp.argmax(dispatch, axis=-1)  # [t, E]
    for e in range(experts):
        assert sorted(int(p) for p in pos[:, e]) == list(range(tokens))
    # Combine weight per (token, expert) is that pair's gate.
    gates = jnp.sum(combine, axis=-1)
    assert jnp.max(jnp.abs(gates - probs)) < 1e-6
    assert bool(jnp.all(jnp.abs(jnp.sum(gates, axis=-1) - 1.0) < 1e-6))


def test_moe_gradients_flow_to_all_param_groups():
    x, params = _inputs(tokens=32)
    mesh = make_mesh(expert=4, devices=jax.devices()[:4])

    sm = jax.jit(jax.shard_map(
        lambda x, params: moe_layer(
            x, local_experts(params, axis_name=EXPERT_AXIS),
            axis_name=EXPERT_AXIS, num_experts=E, top_k=2,
            capacity_factor=4.0),
        mesh=mesh, in_specs=(P(EXPERT_AXIS), P()),
        out_specs=MoEOutput(P(EXPERT_AXIS), P(), P()),
        check_vma=False))

    def loss(params):
        out, aux, _ = sm(x, params)
        return jnp.sum(out ** 2) + aux

    grads = jax.jit(jax.grad(loss))(params)
    for name, g in grads.items():
        assert bool(jnp.any(g != 0)), f"no gradient reached {name}"
        assert bool(jnp.all(jnp.isfinite(g)))
