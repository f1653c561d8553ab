"""Tensor-parallel matmul tests: sharded results must equal the dense
single-device computation (self-verifying, SURVEY.md §4 style)."""

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import PartitionSpec as P

from horovod_tpu.core.topology import MODEL_AXIS, make_mesh
from horovod_tpu.parallel.tensor import (column_parallel,
                                         gather_column_parallel,
                                         local_shard, row_parallel,
                                         row_parallel_scatter, tp_mlp)

TOL = 1e-5


def _mesh(n=4):
    return make_mesh(model=n, devices=jax.devices()[:n])


def test_column_then_row_matches_dense():
    mesh = _mesh()
    key = jax.random.PRNGKey(0)
    k1, k2, k3, k4, k5 = jax.random.split(key, 5)
    x = jax.random.normal(k1, (8, 16))
    w1 = jax.random.normal(k2, (16, 32)) * 0.1
    b1 = jax.random.normal(k3, (32,)) * 0.1
    w2 = jax.random.normal(k4, (32, 16)) * 0.1
    b2 = jax.random.normal(k5, (16,)) * 0.1

    def tp(x, w1, b1, w2, b2):
        h = column_parallel(x, local_shard(w1, 1),
                            local_shard(b1, 0))
        h = jax.nn.gelu(h)
        return row_parallel(h, local_shard(w2, 0), b2)

    got = jax.jit(jax.shard_map(
        tp, mesh=mesh, in_specs=(P(), P(), P(), P(), P()),
        out_specs=P(), check_vma=False))(x, w1, b1, w2, b2)
    want = jax.nn.gelu(x @ w1 + b1) @ w2 + b2
    assert jnp.max(jnp.abs(got - want)) < TOL


def test_tp_mlp_helper_matches_dense():
    mesh = _mesh()
    key = jax.random.PRNGKey(1)
    k1, k2, k3 = jax.random.split(key, 3)
    x = jax.random.normal(k1, (4, 8))
    w1 = jax.random.normal(k2, (8, 16)) * 0.1
    w2 = jax.random.normal(k3, (16, 8)) * 0.1

    def tp(x, w1, w2):
        return tp_mlp(x, local_shard(w1, 1), None, local_shard(w2, 0),
                      None)

    got = jax.jit(jax.shard_map(tp, mesh=mesh, in_specs=(P(),) * 3,
                                out_specs=P(), check_vma=False))(x, w1, w2)
    want = jax.nn.gelu(x @ w1) @ w2
    assert jnp.max(jnp.abs(got - want)) < TOL


def test_column_parallel_gather_output():
    mesh = _mesh()
    x = jnp.eye(8)
    w = jnp.arange(8.0 * 8).reshape(8, 8)

    def tp(x, w):
        return column_parallel(x, local_shard(w, 1), gather_output=True)

    got = jax.jit(jax.shard_map(tp, mesh=mesh, in_specs=(P(), P()),
                                out_specs=P(), check_vma=False))(x, w)
    assert jnp.max(jnp.abs(got - w)) < TOL


def test_row_parallel_unsharded_input():
    mesh = _mesh()
    key = jax.random.PRNGKey(2)
    x = jax.random.normal(key, (4, 16))
    w = jax.random.normal(jax.random.PRNGKey(3), (16, 8)) * 0.1

    def tp(x, w):
        return row_parallel(x, local_shard(w, 0),
                            input_is_parallel=False)

    got = jax.jit(jax.shard_map(tp, mesh=mesh, in_specs=(P(), P()),
                                out_specs=P(), check_vma=False))(x, w)
    assert jnp.max(jnp.abs(got - x @ w)) < TOL


# ---------------------------------------------------------------------------
# hvd-fuse: fused computation-collective closers/openers
# ---------------------------------------------------------------------------


def _tp_mlp_bytes(fuse, fuse_chunks=None):
    mesh = _mesh()
    key = jax.random.PRNGKey(7)
    k1, k2, k3 = jax.random.split(key, 3)
    x = jax.random.normal(k1, (16, 8))
    w1 = jax.random.normal(k2, (8, 16)) * 0.1
    w2 = jax.random.normal(k3, (16, 8)) * 0.1

    def tp(x, w1, w2):
        return tp_mlp(x, local_shard(w1, 1), None, local_shard(w2, 0),
                      None, fuse=fuse, fuse_chunks=fuse_chunks)

    got = jax.jit(jax.shard_map(tp, mesh=mesh, in_specs=(P(),) * 3,
                                out_specs=P(), check_vma=False))(
        x, w1, w2)
    import numpy as np
    return np.asarray(got).tobytes()


@pytest.mark.parametrize("chunks", [2, 4])
def test_fused_row_parallel_bitwise_vs_unfused(chunks):
    # The fused (chunk-interleaved) psum closer must reproduce the
    # unfused reference program's bytes exactly.
    assert _tp_mlp_bytes(True, chunks) == _tp_mlp_bytes(False)


def test_fused_env_off_pins_reference(monkeypatch):
    from horovod_tpu.ops import fused as F
    monkeypatch.setenv(F.FUSE_ENV, "off")
    off = _tp_mlp_bytes(None)
    monkeypatch.setenv(F.FUSE_ENV, "on")
    on = _tp_mlp_bytes(None)
    assert off == on


def test_scatter_gather_pair_matches_dense():
    # row_parallel_scatter → gather_column_parallel: the feature-sharded
    # handoff must compose back to the dense two-block computation.
    mesh = _mesh()
    key = jax.random.PRNGKey(8)
    k1, k2, k3 = jax.random.split(key, 3)
    x = jax.random.normal(k1, (8, 16))
    w1 = jax.random.normal(k2, (16, 16)) * 0.1
    w2 = jax.random.normal(k3, (16, 8)) * 0.1

    def tp(x, w1, w2):
        s = row_parallel_scatter(x, local_shard(w1, 0))
        return gather_column_parallel(s, local_shard(w2, 1))

    got = jax.jit(jax.shard_map(
        tp, mesh=mesh,
        in_specs=(P(None, MODEL_AXIS), P(), P()),
        out_specs=P(None, MODEL_AXIS), check_vma=False))(x, w1, w2)
    want = (x @ w1) @ w2
    assert jnp.max(jnp.abs(got - want)) < TOL


@pytest.mark.parametrize("chunks", [2, 4])
def test_fused_scatter_gather_pair_bitwise_vs_unfused(chunks):
    mesh = _mesh()
    key = jax.random.PRNGKey(9)
    k1, k2, k3 = jax.random.split(key, 3)
    x = jax.random.normal(k1, (16, 16))
    w1 = jax.random.normal(k2, (16, 16)) * 0.1
    w2 = jax.random.normal(k3, (16, 8)) * 0.1

    def run(fuse, n=None):
        def tp(x, w1, w2):
            s = row_parallel_scatter(x, local_shard(w1, 0), fuse=fuse,
                                     fuse_chunks=n)
            return gather_column_parallel(s, local_shard(w2, 1),
                                          fuse=fuse, fuse_chunks=n)

        got = jax.jit(jax.shard_map(
            tp, mesh=mesh, in_specs=(P(None, MODEL_AXIS), P(), P()),
            out_specs=P(None, MODEL_AXIS), check_vma=False))(x, w1, w2)
        import numpy as np
        return np.asarray(got).tobytes()

    assert run(True, chunks) == run(False)


def test_tp_gradients_match_dense():
    mesh = _mesh(2)
    key = jax.random.PRNGKey(4)
    k1, k2, k3 = jax.random.split(key, 3)
    x = jax.random.normal(k1, (4, 8))
    w1 = jax.random.normal(k2, (8, 16)) * 0.1
    w2 = jax.random.normal(k3, (16, 8)) * 0.1

    sm = jax.shard_map(
        lambda x, w1, w2: tp_mlp(x, local_shard(w1, 1), None,
                                 local_shard(w2, 0), None),
        mesh=mesh, in_specs=(P(),) * 3, out_specs=P(), check_vma=False)
    got = jax.jit(jax.grad(lambda w1, w2: jnp.sum(sm(x, w1, w2) ** 2),
                           (0, 1)))(w1, w2)
    want = jax.grad(
        lambda w1, w2: jnp.sum((jax.nn.gelu(x @ w1) @ w2) ** 2),
        (0, 1))(w1, w2)
    for a, b in zip(got, want):
        assert jnp.max(jnp.abs(a - b)) < 1e-4
