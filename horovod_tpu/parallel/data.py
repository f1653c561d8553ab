"""Data-parallel training glue: DistributedOptimizer + parameter broadcast.

TPU-native re-design of the reference's L5 layer:

* ``DistributedOptimizer`` — reference wraps a TF optimizer's
  ``compute_gradients`` (tensorflow/__init__.py:133-192), a Torch
  optimizer's grad-accumulator hooks (torch/__init__.py:62-87), or a Keras
  optimizer's ``get_gradients`` (keras/__init__.py:29-89).  The JAX
  analogue of "the thing that transforms gradients before the update" is an
  :mod:`optax` gradient transformation, so ours wraps any
  ``optax.GradientTransformation`` and averages gradients across replicas
  before the inner update.
* ``broadcast_parameters`` / ``broadcast_global_variables`` — replica-
  consistent initialization (reference: torch/__init__.py:125-152,
  tensorflow/__init__.py:88-130).

Two execution contexts, chosen automatically:

* **static path** (inside a ``shard_map``/``pmap`` trace over the replica
  axis): gradients reduce with ``lax.psum`` using Tensor-Fusion bucketing —
  same-dtype gradients are flattened and concatenated into buckets of at
  most ``HOROVOD_FUSION_THRESHOLD`` bytes (default 64 MB, reference
  operations.cc:140) so small tensors ride one collective
  (reference: docs/tensor-fusion.md).  XLA then overlaps these collectives
  with remaining backprop compute.
* **eager path** (no replica axis bound, e.g. host-driven loops): each
  gradient goes through the dynamic-path collective queue as
  ``allreduce_async`` and all handles are synchronized before the update —
  exactly the reference Torch optimizer's hook + ``step()`` flow
  (torch/__init__.py:62-87).
"""

from __future__ import annotations

import math
import os
from typing import Any, NamedTuple, Optional

import jax
import jax.numpy as jnp
import numpy as np

from ..core import state as _state
from ..core.state import REPLICA_AXIS
from ..ops.wire import ReduceOp


def _resolve_grad_op(average: bool, op) -> ReduceOp:
    """Gradient-reduction operator: op supersedes average (the post-v0.13
    contract); only sum/average/adasum are meaningful for gradients."""
    if op is None:
        return ReduceOp.AVERAGE if average else ReduceOp.SUM
    red = ReduceOp(op)
    if red not in (ReduceOp.AVERAGE, ReduceOp.SUM, ReduceOp.ADASUM):
        raise ValueError(
            f"gradient reduction supports op=Average/Sum/Adasum; got "
            f"{red.name.lower()} (min/max/product are not gradient "
            f"combiners).")
    return red


def _in_replica_context() -> bool:
    """True when tracing under a mesh axis named ``REPLICA_AXIS`` (i.e.
    inside shard_map/pmap over the replica mesh)."""
    try:
        jax.lax.psum(jnp.zeros((), jnp.float32), REPLICA_AXIS)
        return True
    except NameError:
        return False
    except Exception:
        return False


def _fusion_threshold_bytes() -> int:
    st = _state.global_state()
    if st.initialized:
        return st.fusion_threshold_bytes
    return int(os.environ.get("HOROVOD_FUSION_THRESHOLD", 64 * 1024 * 1024))


def partition_fusion_buckets(leaves, threshold: int):
    """Greedy Tensor-Fusion partition of a flat leaf list.

    Group by dtype in first-appearance order, then pack each dtype's
    leaves — in order — into buckets of at most ``threshold`` bytes (a
    leaf bigger than the threshold alone forms its own bucket; a
    threshold <= 0 disables fusion, one bucket per leaf).  ``leaves``
    may be arrays or aval-likes (anything with ``shape``/``dtype``).
    Returns a list of index lists covering every leaf exactly once.

    This is THE partition rule of the repo: the static path's wire
    packing below, the coordinator's fusion planning over one
    submission window (``ops/cache.plan_fusion`` reproduces it for the
    tensors a single drain tick sees) and the overlap path's
    dispatch-boundary planning (``parallel/overlap.py``) all derive
    from it — keeping them identical is what makes the overlapped
    step's per-bucket quantized reduction bitwise-comparable to a
    serialized dispatch of the same buckets (same bucket partition ⇒
    same pow2-scale blocks and error-feedback keys per bucket).
    """
    by_dtype: dict = {}
    for i, g in enumerate(leaves):
        by_dtype.setdefault(jnp.dtype(g.dtype), []).append(i)
    buckets: list = []
    for dtype, idxs in by_dtype.items():
        itemsize = jnp.dtype(dtype).itemsize
        bucket: list = []
        bucket_bytes = 0
        for i in idxs:
            nbytes = int(np.prod(leaves[i].shape, dtype=np.int64)) \
                * itemsize if leaves[i].shape else itemsize
            if threshold <= 0 or (
                    bucket and bucket_bytes + nbytes > threshold):
                if bucket:
                    buckets.append(bucket)
                bucket, bucket_bytes = [], 0
            bucket.append(i)
            bucket_bytes += nbytes
        if bucket:
            buckets.append(bucket)
    return buckets


def _adasum_gradients(grads):
    """Whole-gradient Adasum inside the replica trace.

    The model gradient is ONE logical vector here (unlike user-visible
    eager allreduces, which are independent per-tensor ops and therefore
    never fuse under adasum), so the scale-insensitive combination
    (arXiv:2006.02924) runs on the flattened concatenation: log2(n)
    ``ppermute`` exchange rounds on ICI, each combining partner vectors
    with ``(1 - a·b/2||a||²) a + (1 - a·b/2||b||²) b`` — total wire cost
    log2(n) × |grad|, vs 2×|grad|(n-1)/n for a ring allreduce.
    """
    from ..ops.sparse import IndexedSlices

    leaves, treedef = jax.tree_util.tree_flatten(
        grads, is_leaf=lambda g: isinstance(g, IndexedSlices))
    if any(isinstance(g, IndexedSlices) for g in leaves):
        raise ValueError(
            "op=Adasum does not support sparse (IndexedSlices) gradients; "
            "pass sparse_as_dense=True to densify them first.")
    n = jax.lax.axis_size(REPLICA_AXIS)
    if n & (n - 1) != 0:
        raise ValueError(
            f"op=Adasum requires a power-of-two replica count for its "
            f"recursive-doubling ppermute ladder; got {n}.")
    # Accumulation dtype: promote over the leaf dtypes with a float32
    # floor, matching the eager _adasum_ladder's promote_types rule.
    # (Without jax x64 mode this always resolves to float32; the loop
    # keeps the two Adasum paths' precision contract identical.)
    acc_dtype = jnp.float32
    for g in leaves:
        acc_dtype = jnp.promote_types(acc_dtype, g.dtype)
    v = jnp.concatenate(
        [jnp.ravel(g).astype(acc_dtype) for g in leaves])
    for r in range(int(math.log2(n))):
        dist = 1 << r
        perm = [(i, i ^ dist) for i in range(n)]
        other = jax.lax.ppermute(v, REPLICA_AXIS, perm)
        dot = jnp.sum(v * other)
        na = jnp.sum(v * v)
        nb = jnp.sum(other * other)
        ca = 1.0 - jnp.where(na > 0, dot / (2.0 * na), 0.0)
        cb = 1.0 - jnp.where(nb > 0, dot / (2.0 * nb), 0.0)
        v = ca * v + cb * other
    out, off = [], 0
    for g in leaves:
        out.append(v[off:off + g.size].reshape(g.shape).astype(g.dtype))
        off += g.size
    return jax.tree_util.tree_unflatten(treedef, out)


def allreduce_gradients(grads, average: bool = True,
                        fusion_threshold: Optional[int] = None,
                        compression=None, op=None):
    """Cross-replica gradient reduction with Tensor Fusion bucketing.

    Must be called inside a replica-axis trace (shard_map/pmap).  Gradients
    are grouped by dtype and packed into flat buckets up to the fusion
    threshold; each bucket is one ``lax.psum`` — mirroring the reference's
    fusion buffer (operations.cc:941-1034) but letting XLA schedule and
    overlap the collectives.  A threshold of 0 disables fusion (one psum
    per tensor, reference docs/tensor-fusion.md).

    The threshold is not only a wire-packing knob: under the overlap
    mode (``HVD_TPU_OVERLAP``, docs/performance.md) the SAME partition
    (:func:`partition_fusion_buckets`) sets the dispatch-boundary
    granularity — each bucket becomes one megakernel launch streamed
    out of the backward pass.  ``op=Adasum`` ignores the threshold (and
    ``compression``) entirely: its dot products are defined on the
    whole full-precision gradient, so it never buckets, never fuses and
    never overlaps (see :func:`_adasum_gradients`).

    ``compression`` (a :class:`~horovod_tpu.ops.compression.Compressor`,
    e.g. ``hvd.Compression.bf16``) casts dense gradients down for the
    wire and restores the dtype after — sparse leaves already ship a
    minimal payload and pass through uncompressed.

    ``op`` (hvd.Average/Sum/Adasum, superseding ``average``) selects the
    combiner; Adasum runs the whole-gradient ppermute ladder (see
    :func:`_adasum_gradients`) and ignores fusion_threshold/compression
    (its dots are defined on the full-precision gradient).

    :class:`~horovod_tpu.ops.sparse.IndexedSlices` leaves exchange as an
    all_gather of (values, indices) — the reference's sparse branch
    (tensorflow/__init__.py:67-78) — and stay sparse in the result.
    """
    from ..ops.compression import NoneCompressor
    from ..ops.sparse import IndexedSlices

    red = _resolve_grad_op(average, op)
    if red == ReduceOp.ADASUM:
        return _adasum_gradients(grads)
    average = red == ReduceOp.AVERAGE
    compression = compression or NoneCompressor
    threshold = (_fusion_threshold_bytes()
                 if fusion_threshold is None else fusion_threshold)
    leaves, treedef = jax.tree_util.tree_flatten(
        grads, is_leaf=lambda g: isinstance(g, IndexedSlices))
    if not leaves:
        return grads
    # Compress dense leaves for the wire; remember each ctx for the
    # decompress after the reduction.  Bucketing below then groups by the
    # *compressed* dtype, so fused buckets stay narrow end-to-end.
    ctxs: list = [None] * len(leaves)
    for i, g in enumerate(leaves):
        if not isinstance(g, IndexedSlices):
            leaves[i], ctxs[i] = compression.compress(g)
    denom = None
    if average:
        # Under shard_map the axis size is static.
        denom = jax.lax.psum(jnp.ones((), jnp.float32), REPLICA_AXIS)

    def finish(x):
        # Applied AFTER decompress for dense leaves, so averaging divides
        # in the restored dtype (f32), not the narrow wire dtype —
        # matching the ZeRO-1 path's numerics (zero.py) at no wire cost.
        return (x / denom.astype(x.dtype)) if average else x

    def gather_sparse(g):
        vals = jax.lax.all_gather(g.values, REPLICA_AXIS, axis=0,
                                  tiled=True)
        idxs = jax.lax.all_gather(g.indices, REPLICA_AXIS, axis=0,
                                  tiled=True)
        return IndexedSlices(finish(vals), idxs, g.dense_shape)

    if threshold <= 0:
        red = [gather_sparse(g) if isinstance(g, IndexedSlices)
               else finish(compression.decompress(
                   jax.lax.psum(g, REPLICA_AXIS), ctx))
               for g, ctx in zip(leaves, ctxs)]
        return jax.tree_util.tree_unflatten(treedef, red)

    # Bucket by dtype, preserving leaf order for unflatten.  Sparse leaves
    # bypass bucketing (their payload is already minimal).  The partition
    # itself is the shared fusion rule (partition_fusion_buckets) so the
    # overlap path's dispatch boundaries match the wire packing exactly.
    out: list = [None] * len(leaves)
    dense: list = []
    for i, g in enumerate(leaves):
        if isinstance(g, IndexedSlices):
            out[i] = gather_sparse(g)
        else:
            dense.append(i)
    for bucket_pos in partition_fusion_buckets(
            [jnp.asarray(leaves[i]) for i in dense], threshold):
        bucket = [dense[p] for p in bucket_pos]
        if len(bucket) == 1:
            i = bucket[0]
            out[i] = jax.lax.psum(leaves[i], REPLICA_AXIS)
            continue
        flat = jnp.concatenate([jnp.ravel(leaves[i]) for i in bucket])
        red = jax.lax.psum(flat, REPLICA_AXIS)
        off = 0
        for i in bucket:
            n = leaves[i].size
            out[i] = red[off:off + n].reshape(leaves[i].shape)
            off += n
    out = [o if isinstance(g, IndexedSlices)
           else finish(compression.decompress(o, ctx))
           for o, g, ctx in zip(out, leaves, ctxs)]
    return jax.tree_util.tree_unflatten(treedef, out)


def _eager_allreduce_grads(grads, average: bool = True, compression=None):
    """Dynamic-path gradient reduction: fire all allreduces async, then
    synchronize — the Torch hook + step() pattern (torch/__init__.py:62-87),
    with coordinator-level fusion batching the small tensors.  Sparse
    (IndexedSlices) leaves take the allgather exchange transparently."""
    from ..ops import collective as C
    from ..ops import sparse as S
    from ..ops.compression import NoneCompressor

    compression = compression or NoneCompressor
    leaves, treedef = jax.tree_util.tree_flatten(
        grads, is_leaf=lambda g: isinstance(g, S.IndexedSlices))

    def _is_traced(g):
        if isinstance(g, S.IndexedSlices):
            return any(isinstance(f, jax.core.Tracer)
                       for f in (g.values, g.indices))
        return isinstance(g, jax.core.Tracer)

    if any(_is_traced(g) for g in leaves):
        raise RuntimeError(
            "DistributedOptimizer.update was traced (jit) outside a replica "
            "context. Either call it inside shard_map/pmap over the "
            f"'{REPLICA_AXIS}' axis, or build the step with "
            "horovod_tpu.parallel.training.make_train_step, which wires the "
            "reduction into the SPMD program.")
    # Fire EVERYTHING async first (sparse = one allgather pair per leaf),
    # then synchronize — so sparse and dense exchanges all overlap.
    handles = []
    for i, g in enumerate(leaves):
        if isinstance(g, S.IndexedSlices):
            handles.append((g, C.allgather_async(g.values,
                                                 name=f"grad.{i}.values"),
                            C.allgather_async(g.indices,
                                              name=f"grad.{i}.indices")))
        else:
            wire, ctx = compression.compress(g)
            handles.append((ctx, C.allreduce_async(wire, average=average,
                                                   name=f"grad.{i}")))
    denom = _state.contributor_count()
    red = []
    for h in handles:
        if len(h) == 3:
            g, hv, hi = h
            values = C.synchronize(hv)
            red.append(S.IndexedSlices(
                values / denom if average else values,
                C.synchronize(hi), g.dense_shape))
        else:
            ctx, handle = h
            red.append(compression.decompress(C.synchronize(handle), ctx))
    return jax.tree_util.tree_unflatten(treedef, red)


def _eager_adasum_grads(grads):
    """Dynamic-path whole-gradient Adasum: one flattened vector through
    the eager wire (same semantics as the static ladder — each process
    contributes its gradient as one logical vector)."""
    from ..ops import collective as C
    from ..ops.sparse import IndexedSlices

    leaves, treedef = jax.tree_util.tree_flatten(
        grads, is_leaf=lambda g: isinstance(g, IndexedSlices))
    if any(isinstance(g, IndexedSlices) for g in leaves):
        raise ValueError(
            "op=Adasum does not support sparse (IndexedSlices) gradients; "
            "pass sparse_as_dense=True to densify them first.")
    flat = jnp.concatenate([jnp.ravel(jnp.asarray(g, jnp.float32))
                            for g in leaves])
    red = C.allreduce(flat, op=ReduceOp.ADASUM, name="grad.adasum")
    out, off = [], 0
    for g in leaves:
        out.append(red[off:off + np.size(g)].reshape(np.shape(g)).astype(
            jnp.asarray(g).dtype))
        off += np.size(g)
    return jax.tree_util.tree_unflatten(treedef, out)


class DistributedOptimizer:
    """Wrap an optax optimizer so gradients are averaged across replicas
    before the update (≙ hvd.DistributedOptimizer in every reference
    frontend).  Usable exactly like the wrapped transformation:

        opt = hvd.DistributedOptimizer(optax.sgd(lr))
        opt_state = opt.init(params)
        updates, opt_state = opt.update(grads, opt_state, params)

    Inside a shard_map'd step the reduction is fused ``lax.psum``; outside,
    it is the eager async-handle path.  ``average=False`` sums instead
    (reference allreduce's average flag, tensorflow/__init__.py:49-60).
    """

    def __init__(self, optimizer, average: bool = True,
                 fusion_threshold: Optional[int] = None,
                 name: Optional[str] = None, sparse_as_dense: bool = False,
                 compression=None, op=None):
        self._inner = optimizer
        self._average = average
        # op=hvd.Adasum selects scale-insensitive whole-gradient combining
        # (the post-v0.13 DistributedOptimizer op= kwarg); validated here
        # so a bad op fails at construction, not mid-training.
        self._op = None if op is None else _resolve_grad_op(average, op)
        self._fusion_threshold = fusion_threshold
        self._name = name or "DistributedOptimizer"
        # ≙ the reference's device_dense/device_sparse per-op routing
        # choice (tensorflow/__init__.py:49-60): True forces sparse grads
        # through the dense psum path (cheaper when most rows are touched).
        self._sparse_as_dense = sparse_as_dense
        # hvd.Compression.{none,fp16,bf16}: cast dense grads down for the
        # wire, restore after (bf16 recommended on TPU).
        self._compression = compression

    def init(self, params):
        return self._inner.init(params)

    def _map_sparse(self, grads, fn):
        from ..ops.sparse import IndexedSlices

        return jax.tree_util.tree_map(
            lambda g: fn(g) if isinstance(g, IndexedSlices) else g, grads,
            is_leaf=lambda g: isinstance(g, IndexedSlices))

    def update(self, grads, opt_state, params=None, **kw):
        from ..ops import sparse as S

        if self._sparse_as_dense:
            grads = self._map_sparse(grads, S.as_dense)
        if _in_replica_context():
            grads = allreduce_gradients(
                grads, average=self._average,
                fusion_threshold=self._fusion_threshold,
                compression=self._compression, op=self._op)
        elif _state.is_initialized() and _state.size() > 1:
            if self._op == ReduceOp.ADASUM:
                grads = _eager_adasum_grads(grads)
            else:
                grads = _eager_allreduce_grads(grads,
                                               average=self._average,
                                               compression=self._compression)
        elif _state.is_initialized():
            pass  # size 1: reduction is the identity (reference behaves the
            #       same — collectives still run but are trivial).
        else:
            raise _state.NotInitializedError()
        # The exchange is sparse (the wire win); optax transformations are
        # dense, so scatter-sum the gathered slices before the update.
        # (The reference hands IndexedSlices to TF's sparse apply instead —
        # tensorflow/__init__.py:178-192 — optax has no sparse apply.)
        grads = self._map_sparse(grads, S.as_dense)
        return self._inner.update(grads, opt_state, params, **kw)

    # optax GradientTransformation duck-typing.
    def __iter__(self):
        yield self.init
        yield self.update


def broadcast_parameters(params, root_rank: int = 0):
    """Broadcast a pytree of parameters from ``root_rank`` so every replica
    starts identical (≙ hvd.broadcast_parameters, torch/__init__.py:125-152:
    launch all broadcasts async, then synchronize).

    In single-controller SPMD the parameters are already one logical copy;
    the broadcast re-materializes them with a fully-replicated sharding over
    the replica mesh — the operation that guarantees consistency when
    parameters arrive process-local in multi-process mode.
    """
    from ..ops import collective as C

    leaves, treedef = jax.tree_util.tree_flatten(params)
    handles = [
        C.broadcast_async(leaf, root_rank, name=f"broadcast.param.{i}")
        for i, leaf in enumerate(leaves)
    ]
    out = [C.synchronize(h) for h in handles]
    return jax.tree_util.tree_unflatten(treedef, out)


def broadcast_global_variables(params, root_rank: int = 0):
    """TF-style name for :func:`broadcast_parameters`
    (≙ hvd.broadcast_global_variables, tensorflow/__init__.py:88-96)."""
    return broadcast_parameters(params, root_rank)
