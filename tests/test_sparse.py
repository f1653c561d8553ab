"""Sparse-gradient path tests (≙ the reference's IndexedSlices allreduce,
tensorflow/__init__.py:67-78, and the word2vec example that exercises it)."""

import jax
import jax.numpy as jnp
import numpy as np
import optax
import pytest

from horovod_tpu.ops import sparse as S
from horovod_tpu.models import word2vec as W


def test_sparse_allreduce_union_of_rows(hvd):
    """Each replica contributes different rows; result is the union with
    averaged values — exactly the gather-of-(values, indices) semantics."""
    size = hvd.size()
    dense_shape = (100, 4)
    per = []
    for r in range(size):
        nnz = (r % 3) + 1  # variable nnz per replica → Allgatherv path
        idx = jnp.asarray([10 * r + k for k in range(nnz)], jnp.int32)
        vals = jnp.full((nnz, 4), float(r + 1), jnp.float32)
        per.append(S.IndexedSlices(vals, idx, dense_shape))
    out = S.allreduce(per, average=False)
    assert out.values.shape[0] == sum((r % 3) + 1 for r in range(size))
    dense = S.as_dense(out)
    # Each replica's rows landed at its indices with its value.
    arr = np.asarray(dense)
    for r in range(size):
        for k in range((r % 3) + 1):
            np.testing.assert_allclose(arr[10 * r + k],
                                       np.full(4, float(r + 1)))


def test_sparse_allreduce_average_divides_values(hvd):
    sl = S.IndexedSlices(jnp.ones((2, 3)), jnp.asarray([0, 1], jnp.int32),
                         (10, 3))
    out = S.allreduce(sl, average=True)
    # Replicated contribution gathered from `size` replicas then averaged:
    # size*nnz rows of 1/size.
    assert out.values.shape[0] == 2 * hvd.size()
    np.testing.assert_allclose(np.asarray(out.values),
                               np.full((2 * hvd.size(), 3),
                                       1.0 / hvd.size()), rtol=1e-6)
    dense = S.as_dense(out)
    np.testing.assert_allclose(np.asarray(dense[0]), np.ones(3), rtol=1e-6)


def test_as_dense_accumulates_duplicates(hvd):
    sl = S.IndexedSlices(jnp.ones((3, 2)),
                         jnp.asarray([5, 5, 7], jnp.int32), (10, 2))
    dense = np.asarray(S.as_dense(sl))
    np.testing.assert_allclose(dense[5], [2.0, 2.0])
    np.testing.assert_allclose(dense[7], [1.0, 1.0])
    assert dense.sum() == 6.0


def test_apply_to_embedding_rows(hvd):
    emb = jnp.zeros((8, 2))
    sl = S.IndexedSlices(jnp.ones((2, 2)), jnp.asarray([1, 3], jnp.int32),
                         (8, 2))
    out = np.asarray(S.apply_to(emb, sl, scale=-0.5))
    np.testing.assert_allclose(out[1], [-0.5, -0.5])
    np.testing.assert_allclose(out[3], [-0.5, -0.5])
    assert out.sum() == -2.0


def test_word2vec_sparse_training_step(hvd):
    """End-to-end word2vec step: dense grad → sparse slices → sparse
    allreduce → scatter update; embedding moves only on touched rows."""
    vocab, dim = 50, 16
    params = W.init_params(vocab, dim)
    corpus = W.synthetic_corpus(vocab, 2000)
    rng = np.random.RandomState(0)
    centers, targets = W.skipgram_batch(rng, corpus, batch_size=16)
    negs = rng.randint(0, vocab, size=8).astype("int32")

    def loss_fn(emb):
        p = params._replace(embeddings=emb)
        return W.nce_loss(p, jnp.asarray(centers), jnp.asarray(targets),
                          jnp.asarray(negs))

    dense_grad = jax.grad(loss_fn)(params.embeddings)
    sl = S.sparse_grad_from_dense(dense_grad, jnp.asarray(centers))
    red = S.allreduce(sl, average=True)
    new_emb = S.apply_to(params.embeddings, red, scale=-0.5)
    # Untouched rows unchanged.
    untouched = sorted(set(range(vocab)) - set(centers.tolist()))[0]
    np.testing.assert_allclose(np.asarray(new_emb[untouched]),
                               np.asarray(params.embeddings[untouched]))
    # Loss decreased after the sparse update.
    assert float(loss_fn(new_emb)) < float(loss_fn(params.embeddings))


def test_sparse_grad_from_dense_no_padding_duplication(hvd):
    """Regression: duplicate touched rows (incl. the last row) must not
    double-apply any row's gradient via unique() padding."""
    dense = jnp.zeros((10, 2)).at[9].set(1.0).at[5].set(2.0)
    touched = jnp.asarray([5, 5, 9], jnp.int32)
    sl = S.sparse_grad_from_dense(dense, touched)
    assert sl.values.shape[0] == 2  # unique rows only
    out = np.asarray(S.as_dense(sl))
    np.testing.assert_allclose(out[9], [1.0, 1.0])  # not 2x
    np.testing.assert_allclose(out[5], [2.0, 2.0])


def test_allreduce_dispatches_indexed_slices(hvd):
    """hvd.allreduce on IndexedSlices takes the sparse path transparently
    (≙ tensorflow/__init__.py:67-78) and matches the dense result."""
    import horovod_tpu as H

    dense_shape = (20, 3)
    sl = S.IndexedSlices(jnp.full((2, 3), 4.0), jnp.asarray([3, 7]),
                         dense_shape)
    out = H.allreduce(sl, average=True, name="dispatch.sparse")
    assert isinstance(out, S.IndexedSlices)
    got = np.asarray(S.as_dense(out))
    want = np.asarray(S.as_dense(sl))  # every replica contributed the same
    np.testing.assert_allclose(got, want, rtol=1e-6)


def test_distributed_optimizer_sparse_matches_dense(hvd):
    """DistributedOptimizer.update with an IndexedSlices leaf produces the
    same update as the equivalent dense gradient (eager path)."""
    import horovod_tpu as H

    dense_shape = (12, 4)
    dense_grad = jnp.zeros(dense_shape).at[2].set(1.5).at[9].set(-0.5)
    sparse_grad = S.sparse_grad_from_dense(dense_grad,
                                           jnp.asarray([2, 9], jnp.int32))
    params = {"emb": jnp.ones(dense_shape), "w": jnp.ones((4,))}
    opt = H.DistributedOptimizer(optax.sgd(0.1))
    state0 = opt.init(params)

    g_dense = {"emb": dense_grad, "w": jnp.full((4,), 2.0)}
    g_sparse = {"emb": sparse_grad, "w": jnp.full((4,), 2.0)}
    upd_dense, _ = opt.update(g_dense, state0, params)
    upd_sparse, _ = opt.update(g_sparse, state0, params)
    jax.tree_util.tree_map(
        lambda a, b: np.testing.assert_allclose(np.asarray(a),
                                                np.asarray(b), rtol=1e-6),
        upd_dense, upd_sparse)


def test_distributed_optimizer_sparse_as_dense_override(hvd):
    """sparse_as_dense=True densifies before the exchange (the reference's
    device_dense routing choice) with identical results."""
    import horovod_tpu as H

    dense_shape = (8, 2)
    sparse_grad = S.IndexedSlices(jnp.full((1, 2), 3.0),
                                  jnp.asarray([5], jnp.int32), dense_shape)
    params = {"emb": jnp.zeros(dense_shape)}
    for flag in (False, True):
        opt = H.DistributedOptimizer(optax.sgd(1.0), sparse_as_dense=flag)
        upd, _ = opt.update({"emb": sparse_grad}, opt.init(params), params)
        out = np.asarray(upd["emb"])
        np.testing.assert_allclose(out[5], [-3.0, -3.0], rtol=1e-6)
        assert np.all(out[:5] == 0) and np.all(out[6:] == 0)


def test_static_path_sparse_gradients(hvd):
    """IndexedSlices leaves reduce inside a shard_map trace via all_gather
    (the SPMD spelling of the sparse exchange)."""
    from jax.sharding import PartitionSpec as P

    from horovod_tpu.parallel.data import allreduce_gradients

    size = hvd.size()
    dense_shape = (size * 2, 3)

    def step(vals, idxs):
        vals = jnp.squeeze(vals, 0)
        idxs = jnp.squeeze(idxs, 0)
        sl = S.IndexedSlices(vals, idxs, dense_shape)
        red = allreduce_gradients({"e": sl}, average=False)["e"]
        return S.as_dense(red)[None]

    mesh = hvd.mesh()
    vals = jnp.stack([jnp.full((1, 3), float(r + 1)) for r in range(size)])
    idxs = jnp.stack([jnp.asarray([2 * r], jnp.int32) for r in range(size)])
    fn = jax.jit(jax.shard_map(step, mesh=mesh,
                               in_specs=(P("hvd"), P("hvd")),
                               out_specs=P("hvd"), check_vma=False))
    out = np.asarray(fn(hvd.shard(vals), hvd.shard(idxs)))
    for r in range(size):
        np.testing.assert_allclose(out[r, 2 * r], float(r + 1))
