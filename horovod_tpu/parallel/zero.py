"""ZeRO-1 data parallelism: optimizer state sharded across replicas.

Beyond-parity extension (the reference — and Horovod generally — keeps
the full optimizer state on every worker; state sharding arrived in the
ecosystem later as ZeRO/FSDP).  On TPU the idiomatic construction is a
direct transcription of the allreduce decomposition: an allreduce IS a
reduce_scatter followed by an all_gather, so instead of

    psum(grads) -> full optimizer update on every replica   (plain DP)

each replica reduces only its 1/N contiguous slice of the flattened
gradient, applies the optimizer to that slice (holding only 1/N of the
optimizer state — for Adam that is 2/N of the model size instead of 2x),
and the updated parameter slices are all_gathered back into the full
replicated parameters:

    g_shard = psum_scatter(flat_grads)        # same bytes as psum
    p_shard, opt_shard = opt.update(g_shard)  # 1/N state, 1/N compute
    params = unravel(all_gather(p_shard))

Wire cost is identical to the fused allreduce (reduce_scatter +
all_gather move the same bytes over ICI); optimizer memory and update
FLOPs drop by the replica count.

Caveat (inherent to ZeRO-1, documented by every implementation): the
optimizer transformation must be *elementwise* (sgd, momentum, adam,
adamw, rmsprop, ... — anything that treats each parameter independently).
Transforms that aggregate across the whole tree (``clip_by_global_norm``)
would see only the local shard and silently train wrong.
:func:`make_zero_train_step` therefore probes the optimizer at build
time — it applies one update to a small vector and to its two halves
independently and requires identical results — and raises for
aggregating chains, naming the alternatives (clip per-element with
``optax.clip``, clip-then-ZeRO is not recoverable per-shard, or pass
``validate_elementwise=False`` to accept shard-local semantics).

Usage::

    zstep = make_zero_train_step(loss_fn, optax.adam(1e-3))
    opt_state = zstep.init(params)              # sharded state
    params, opt_state, loss = zstep.step(params, opt_state, batch)
"""

from __future__ import annotations

from typing import Any, Callable, NamedTuple

import jax
import jax.numpy as jnp
from jax.flatten_util import ravel_pytree
from jax.sharding import PartitionSpec as P

from ..core import state as _state
from ..core.state import REPLICA_AXIS
from .data import DistributedOptimizer
from .training import _throttle_on_cpu

try:
    import optax
except Exception:  # pragma: no cover - optax is baked into the image
    optax = None


class ZeroTrainStep(NamedTuple):
    """``init(params) -> opt_state`` (sharded) and
    ``step(params, opt_state, batch) -> (params, opt_state, loss)`` —
    or, from :func:`make_zero_train_step_with_state`,
    ``step(params, model_state, opt_state, batch) ->
    (params, model_state, opt_state, loss)``."""

    init: Callable[[Any], Any]
    step: Callable[..., Any]


def _replica_count(mesh) -> int:
    return mesh.shape[REPLICA_AXIS]


def _pad_flat(tree, n: int):
    """Flatten a pytree to one vector zero-padded to a multiple of n.
    Returns (flat, unravel, true_size).  The SINGLE place the layout is
    defined — gradient shards and parameter shards must slice the same
    way or replicas would update the wrong slices."""
    flat, unravel = ravel_pytree(tree)
    true_size = flat.size
    pad = (-flat.size) % n
    if pad:
        flat = jnp.pad(flat, (0, pad))
    return flat, unravel, true_size


def _flat_shard(tree, n: int):
    """This replica's contiguous (1/n) slice of the padded flat vector
    plus the unravel closure and true size.  Must run inside the
    replica-axis trace."""
    flat, unravel, true_size = _pad_flat(tree, n)
    chunk = flat.size // n
    idx = jax.lax.axis_index(REPLICA_AXIS)
    shard = jax.lax.dynamic_slice(flat, (idx * chunk,), (chunk,))
    return shard, unravel, true_size


def _sharded_state_specs(opt_state):
    """Per-leaf PartitionSpecs for a flat-sharded optimizer state:
    vector leaves (momentum/variance slices) shard over the replica
    axis, scalar leaves (e.g. Adam's step count) replicate.  Shared by
    the ZeRO-1 and FSDP builders."""
    return jax.tree_util.tree_map(
        lambda leaf: P(REPLICA_AXIS) if getattr(leaf, "ndim", 0)
        else P(), opt_state)


def _abstract_state_or_raise(optimizer, chunk: int, dtype,
                             feature: str = "ZeRO-1",
                             api_name: str = "make_zero_train_step"):
    """Abstract optimizer state for a (chunk,)-sized slice, refusing
    states whose non-scalar leaves are not per-parameter slices.

    :func:`_sharded_state_specs` shards every ndim>=1 state leaf over
    the replica axis, which is only correct for chunk-sized
    per-parameter vectors (momentum/variance slices).  A leaf of any
    other shape (an array hyperparameter from ``inject_hyperparams``, a
    non-elementwise transform's aggregate) would get silently wrong
    sharding — refuse at build time.  Shared by the ZeRO-1 and FSDP
    builders."""
    abstract = jax.eval_shape(
        optimizer.init, jax.ShapeDtypeStruct((chunk,), dtype))
    bad = [tuple(leaf.shape)
           for leaf in jax.tree_util.tree_leaves(abstract)
           if getattr(leaf, "ndim", 0) >= 1
           and tuple(leaf.shape) != (chunk,)]
    if bad:
        raise ValueError(
            f"{feature} shards every non-scalar optimizer-state "
            "leaf over the replica axis, so each such leaf must "
            f"be one ({chunk},)-shaped per-parameter slice; the "
            f"given optimizer's state has leaves of shape {bad}. "
            "This usually means a non-elementwise transform or "
            "an array-valued hyperparameter "
            "(optax.inject_hyperparams) — keep those outside "
            f"{api_name} (see parallel/zero.py docstring).")
    return abstract


def _check_elementwise(optimizer, feature: str = "ZeRO-1",
                       api_name: str = "make_zero_train_step") -> None:
    """Build-time probe for the elementwise-optimizer precondition.

    An elementwise transform updates a concatenated vector exactly as it
    updates the parts with independent states — which is precisely how
    the sharded builders run it (each replica updates its shard with its
    shard of state).  A transform that aggregates across the tree
    (``clip_by_global_norm``: the norm of a half differs from the norm
    of the whole) fails the probe and would silently train wrong.

    Probe values are large (~1e4) so norm-dependent transforms with any
    realistic threshold take their data-dependent branch.  Transforms
    whose ``update`` needs extra arguments (GradientTransformationExtraArgs)
    cannot be probed and are skipped with a warning.
    """
    import warnings

    import numpy as np

    probe = jnp.asarray(np.linspace(1.0e4, -3.0e4, 16, dtype=np.float32))
    try:
        full, _ = optimizer.update(probe, optimizer.init(probe), probe)
        parts = []
        for part in (probe[:8], probe[8:]):
            up, _ = optimizer.update(part, optimizer.init(part), part)
            parts.append(np.asarray(up))
        full = np.asarray(full)
    except TypeError as e:
        warnings.warn(
            f"{api_name} could not probe the optimizer for the "
            f"elementwise precondition ({e}); proceeding unchecked — "
            "ensure no transform aggregates across parameters "
            "(see horovod_tpu/parallel/zero.py docstring)")
        return
    if not np.allclose(full, np.concatenate(parts), rtol=1e-5, atol=1e-5):
        raise ValueError(
            f"{feature} requires an ELEMENTWISE optimizer: updating a "
            "vector must equal updating its parts independently, because "
            "each replica will only ever see its 1/N shard of the "
            "gradients and optimizer state.  The given optax chain "
            "failed that probe — it aggregates across parameters (e.g. "
            "optax.clip_by_global_norm computes the GLOBAL gradient "
            f"norm, but under {feature} each replica would clip by its "
            "shard's norm, silently training wrong).  Alternatives: "
            "clip per-element with optax.clip(delta); clip by global "
            "norm OUTSIDE the optimizer on the full gradient before "
            f"{feature} sees it; or pass validate_elementwise=False to "
            "accept shard-local semantics.")


def make_zero_train_step(
    loss_fn,
    optimizer,
    mesh=None,
    average: bool = True,
    compression=None,
    donate: bool = True,
    has_state: bool = False,
    validate_elementwise: bool = True,
) -> ZeroTrainStep:
    """Build a ZeRO-1 data-parallel train step over the replica mesh.

    Args:
      loss_fn: ``loss_fn(params, batch) -> scalar`` on the local shard —
        or, with ``has_state=True``, ``loss_fn(params, model_state,
        batch) -> (scalar, new_model_state)`` (BatchNorm-style models;
        the returned state is pmean-synchronized like
        :func:`~horovod_tpu.parallel.training.make_train_step_with_state`).
      optimizer: an elementwise optax ``GradientTransformation`` (or a
        :class:`DistributedOptimizer` wrapping one — its averaging flag
        and compression are honored; the reduction here is the
        reduce_scatter, so its ``fusion_threshold`` does not apply: the
        flattened gradient IS one maximal fusion bucket).
      mesh: replica mesh; defaults to the global one from ``init()``.
      average: average (True) or sum (False) gradients across replicas.
      compression: ``hvd.Compression.{bf16,fp16}`` casts the gradient
        down for the reduce_scatter wire (the parameter all_gather stays
        uncompressed — it carries the master weights).

    Returns:
      :class:`ZeroTrainStep` with sharded ``init`` and jitted ``step``.
      The optimizer state returned by ``init``/``step`` is laid out as
      flat vectors sharded over the replica axis — treat it as opaque
      (checkpoint it like any pytree; its sharding round-trips).
    """
    mesh = mesh or _state.mesh()
    n = _replica_count(mesh)

    if isinstance(optimizer, DistributedOptimizer):
        average = optimizer._average
        if optimizer._compression is not None:
            compression = optimizer._compression
        optimizer = optimizer._inner

    if validate_elementwise:
        _check_elementwise(optimizer)

    grad_fn = jax.value_and_grad(loss_fn, has_aux=has_state)

    def per_replica_init(params):
        p_shard, _, _ = _flat_shard(params, n)
        return optimizer.init(p_shard)

    def per_replica_step(params, model_state, opt_state, batch):
        if has_state:
            (loss, model_state), grads = grad_fn(params, model_state,
                                                 batch)
            # Synchronized BatchNorm: stats average over replicas on the
            # same compiled collective schedule as the gradients.
            model_state = jax.tree_util.tree_map(
                lambda x: jax.lax.pmean(x, REPLICA_AXIS), model_state)
        else:
            loss, grads = grad_fn(params, batch)
        flat_g, _, _ = _pad_flat(grads, n)
        ctx = None
        if compression is not None:
            flat_g, ctx = compression.compress(flat_g)
        # reduce_scatter: this replica reduces only its slice — same ICI
        # bytes as the psum in plain DP, 1/N of the optimizer work.
        g_shard = jax.lax.psum_scatter(
            flat_g.reshape(n, flat_g.size // n), REPLICA_AXIS,
            scatter_dimension=0)
        if compression is not None:
            g_shard = compression.decompress(g_shard, ctx)
        if average:
            g_shard = g_shard / n
        p_shard, unravel_p, true_size = _flat_shard(params, n)
        updates, opt_state = optimizer.update(g_shard, opt_state, p_shard)
        p_shard = optax.apply_updates(p_shard, updates)
        # all_gather the updated slices back into the full parameters.
        flat_p = jax.lax.all_gather(p_shard, REPLICA_AXIS, axis=0,
                                    tiled=True)
        params = unravel_p(flat_p[:true_size])
        loss = jax.lax.pmean(loss, REPLICA_AXIS)
        if has_state:
            return params, model_state, opt_state, loss
        return params, opt_state, loss

    # The per-leaf state specs (_sharded_state_specs) depend on the
    # state's structure, which optax only reveals given the
    # (chunk-sized) param slice, so the jitted programs are built
    # lazily and cached by state structure.
    _state_specs = _sharded_state_specs

    init_cache: dict = {}

    def init(params):
        leaves = jax.tree_util.tree_leaves(params)
        total = sum(l.size for l in leaves)
        chunk = -(-total // n)
        dtype = jnp.result_type(*[l.dtype for l in leaves])
        key = (chunk, str(dtype))
        if key not in init_cache:
            abstract = _abstract_state_or_raise(optimizer, chunk, dtype)
            init_cache[key] = jax.jit(jax.shard_map(
                per_replica_init, mesh=mesh,
                in_specs=(P(),), out_specs=_state_specs(abstract),
                check_vma=False))
        return init_cache[key](params)

    step_cache: dict = {}

    def _compiled(opt_state):
        specs = _state_specs(opt_state)
        key = jax.tree_util.tree_structure(specs), tuple(
            str(s) for s in jax.tree_util.tree_leaves(
                specs, is_leaf=lambda x: isinstance(x, P)))
        if key not in step_cache:
            if has_state:
                fn = per_replica_step
                in_specs = (P(), P(), specs, P(REPLICA_AXIS))
                out_specs = (P(), P(), specs, P())
                donate_argnums = (0, 1, 2) if donate else ()
            else:
                def fn(params, opt_state, batch):
                    return per_replica_step(params, None, opt_state,
                                            batch)
                in_specs = (P(), specs, P(REPLICA_AXIS))
                out_specs = (P(), specs, P())
                donate_argnums = (0, 1) if donate else ()
            jitted = jax.jit(
                jax.shard_map(fn, mesh=mesh, in_specs=in_specs,
                              out_specs=out_specs, check_vma=False),
                donate_argnums=donate_argnums)
            step_cache[key] = _throttle_on_cpu(jitted, mesh)
        return step_cache[key]

    if has_state:
        def step(params, model_state, opt_state, batch):
            return _compiled(opt_state)(params, model_state, opt_state,
                                        batch)
    else:
        def step(params, opt_state, batch):
            return _compiled(opt_state)(params, opt_state, batch)

    return ZeroTrainStep(init=init, step=step)


def make_zero_train_step_with_state(loss_fn, optimizer, mesh=None,
                                    average: bool = True,
                                    compression=None,
                                    donate: bool = True,
                                    validate_elementwise: bool = True,
                                    ) -> ZeroTrainStep:
    """Stateful-model spelling (BatchNorm etc.) of
    :func:`make_zero_train_step` — ``loss_fn(params, state, batch) ->
    (loss, state)``; ``step(params, model_state, opt_state, batch) ->
    (params, model_state, opt_state, loss)`` — mirroring
    :func:`~horovod_tpu.parallel.training.make_train_step_with_state`."""
    return make_zero_train_step(loss_fn, optimizer, mesh=mesh,
                                average=average, compression=compression,
                                donate=donate, has_state=True,
                                validate_elementwise=validate_elementwise)
