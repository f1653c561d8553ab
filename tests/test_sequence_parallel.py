"""Sequence-parallel attention tests: ring + Ulysses vs dense attention.

Self-verifying in the reference's style (SURVEY.md §4): the sharded
computation must reproduce the single-device result over the gathered
sequence, forward and backward.
"""

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import PartitionSpec as P

from horovod_tpu.core.topology import SEQ_AXIS, make_mesh
from horovod_tpu.ops.flash_attention import mha_reference
from horovod_tpu.parallel.sequence import ring_attention, ulysses_attention

TOL = 5e-5
SPEC = P(None, None, SEQ_AXIS)


def _qkv(b=2, h=4, s=256, d=32, seed=1):
    ks = jax.random.split(jax.random.PRNGKey(seed), 3)
    return tuple(jax.random.normal(k, (b, h, s, d)) for k in ks)


def _sharded(fn, mesh):
    return jax.jit(jax.shard_map(fn, mesh=mesh, in_specs=SPEC,
                                 out_specs=SPEC, check_vma=False))


@pytest.mark.parametrize("ring_size", [2, 4, 8])
@pytest.mark.parametrize("causal", [False, True])
def test_ring_attention_matches_dense(ring_size, causal):
    mesh = make_mesh(seq=ring_size, devices=jax.devices()[:ring_size])
    q, k, v = _qkv()

    sm = _sharded(
        lambda q, k, v: ring_attention(q, k, v, causal=causal, block_q=32,
                                       block_k=32), mesh)
    o = sm(q, k, v)
    ref = mha_reference(q, k, v, causal=causal)
    assert jnp.max(jnp.abs(o - ref)) < TOL


@pytest.mark.parametrize("causal", [False, True])
def test_ring_attention_gradients(causal):
    mesh = make_mesh(seq=4, devices=jax.devices()[:4])
    q, k, v = _qkv(s=128, d=16)
    w = jnp.sin(jnp.arange(16))

    sm = _sharded(
        lambda q, k, v: ring_attention(q, k, v, causal=causal, block_q=32,
                                       block_k=32), mesh)
    got = jax.grad(lambda q, k, v: jnp.sum(sm(q, k, v) * w),
                   (0, 1, 2))(q, k, v)
    want = jax.grad(
        lambda q, k, v: jnp.sum(mha_reference(q, k, v, causal=causal) * w),
        (0, 1, 2))(q, k, v)
    for a, b in zip(got, want):
        assert jnp.max(jnp.abs(a - b)) < 1e-4


@pytest.mark.parametrize("causal", [False, True])
def test_ulysses_matches_dense(causal):
    mesh = make_mesh(seq=4, devices=jax.devices()[:4])
    q, k, v = _qkv()

    sm = _sharded(
        lambda q, k, v: ulysses_attention(q, k, v, causal=causal,
                                          block_q=32, block_k=32), mesh)
    o = sm(q, k, v)
    ref = mha_reference(q, k, v, causal=causal)
    assert jnp.max(jnp.abs(o - ref)) < TOL


def test_ulysses_gradients():
    mesh = make_mesh(seq=4, devices=jax.devices()[:4])
    q, k, v = _qkv(s=128, d=16)
    w = jnp.sin(jnp.arange(16))

    sm = _sharded(
        lambda q, k, v: ulysses_attention(q, k, v, causal=True, block_q=32,
                                          block_k=32), mesh)
    got = jax.grad(lambda q, k, v: jnp.sum(sm(q, k, v) * w),
                   (0, 1, 2))(q, k, v)
    want = jax.grad(
        lambda q, k, v: jnp.sum(mha_reference(q, k, v, causal=True) * w),
        (0, 1, 2))(q, k, v)
    for a, b in zip(got, want):
        assert jnp.max(jnp.abs(a - b)) < 1e-4


def test_ulysses_rejects_indivisible_heads():
    mesh = make_mesh(seq=4, devices=jax.devices()[:4])
    q, k, v = _qkv(h=3)
    sm = _sharded(lambda q, k, v: ulysses_attention(q, k, v), mesh)
    with pytest.raises(ValueError, match="divisible"):
        sm(q, k, v)


def test_ring_attention_composes_with_data_parallel():
    # 2-D mesh: batch over 'data', sequence ring over 'seq'.
    mesh = make_mesh(data=2, seq=4)
    q, k, v = _qkv(b=4, s=128)

    spec = P("data", None, SEQ_AXIS)
    sm = jax.jit(jax.shard_map(
        lambda q, k, v: ring_attention(q, k, v, causal=True, block_q=32,
                                       block_k=32),
        mesh=mesh, in_specs=spec, out_specs=spec, check_vma=False))
    o = sm(q, k, v)
    ref = mha_reference(q, k, v, causal=True)
    assert jnp.max(jnp.abs(o - ref)) < TOL
