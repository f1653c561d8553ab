"""ZeRO-3 / FSDP-style data parallelism: parameters, gradients AND
optimizer state sharded across replicas.

Beyond-parity extension, one rung past :mod:`.zero` (ZeRO-1).  The
reference — and Horovod generally — replicates parameters on every
worker; fully-sharded storage arrived in the ecosystem later (DeepSpeed
ZeRO-3, PyTorch FSDP).  On TPU the idiomatic construction extends the
same allreduce decomposition ZeRO-1 uses:

    between steps : each replica stores only its contiguous 1/N slice
                    of the flattened parameters (plus 1/N of the
                    optimizer state) — resident memory for params +
                    Adam state drops from 3x model size to 3/N x.
    in the step   : all_gather(param shards) -> full params -> forward/
                    backward -> the gradient's reduce_scatter is the
                    TRANSPOSE of that all_gather -> each replica updates
                    only its slice.  Wire cost per step: one all_gather
                    + one reduce_scatter = the same bytes as plain DP's
                    fused allreduce.

Scope note (honest ZeRO-3 comparison): the full parameter vector is
gathered ONCE per step and lives for the duration of forward+backward —
peak memory includes one transient full-parameter copy (what DeepSpeed
calls ZeRO-3 with a single prefetch bucket; per-layer gather/release
needs model cooperation and is what the mesh-axis partition specs in
:mod:`.training`/`models.transformer` provide).  The *resident*
footprint between steps — where Adam's f32 moments dominate — is fully
sharded, which is the memory that limits model size in practice.

The elementwise-optimizer precondition and its build-time probe are
shared with ZeRO-1 (see :mod:`.zero`'s docstring): each replica applies
the optimizer to its flat slice with its slice of state.

Usage::

    fstep = make_fsdp_train_step(loss_fn, optax.adamw(3e-4))
    p_shard, opt_state = fstep.init(params)   # shard + free replicas
    for batch in data:
        p_shard, opt_state, loss = fstep.step(p_shard, opt_state, batch)
    params = fstep.full_params(p_shard)       # rank-0 checkpoint / eval
"""

from __future__ import annotations

from typing import Any, Callable, NamedTuple

import jax
import jax.numpy as jnp
from jax.sharding import NamedSharding, PartitionSpec as P

from ..core import state as _state
from ..core.state import REPLICA_AXIS
from .data import DistributedOptimizer
from .training import _throttle_on_cpu
from .zero import (_abstract_state_or_raise, _check_elementwise,
                   _pad_flat, _replica_count, _sharded_state_specs)

try:
    import optax
except Exception:  # pragma: no cover - optax is baked into the image
    optax = None


class FsdpTrainStep(NamedTuple):
    """``init(params) -> (param_shard, opt_state)`` (both sharded 1/N
    per replica), ``step(param_shard, opt_state, batch) ->
    (param_shard, opt_state, loss)`` (stateful variant threads
    ``model_state`` after ``param_shard``), ``full_params(param_shard)
    -> params`` (the unsharded pytree, for checkpointing and
    evaluation), and ``shard_params(params) -> param_shard`` (re-shard
    a full pytree without touching optimizer state — checkpoint restore,
    broadcast-then-reshard)."""

    init: Callable[[Any], Any]
    step: Callable[..., Any]
    full_params: Callable[[Any], Any]
    shard_params: Callable[[Any], Any]


def make_fsdp_train_step(
    loss_fn,
    optimizer,
    mesh=None,
    average: bool = True,
    compression=None,
    donate: bool = True,
    has_state: bool = False,
    validate_elementwise: bool = True,
) -> FsdpTrainStep:
    """Build a ZeRO-3/FSDP-style train step over the replica mesh.

    Args:
      loss_fn: ``loss_fn(params, batch) -> scalar`` on the local batch
        shard (or, with ``has_state=True``, ``loss_fn(params,
        model_state, batch) -> (scalar, new_model_state)``), with NO
        internal cross-replica reduction — the same contract as
        :func:`~horovod_tpu.parallel.training.make_train_step`.
      optimizer: an elementwise optax ``GradientTransformation`` (or a
        :class:`DistributedOptimizer` wrapping one — averaging flag and
        compression honored, as in :func:`.zero.make_zero_train_step`).
      compression: ``hvd.Compression.{bf16,fp16}`` casts the gradient
        for the reduce_scatter wire; the parameter all_gather stays
        uncompressed (it carries the master weights).

    Returns:
      :class:`FsdpTrainStep`.  ``init`` consumes the full (replicated)
      parameter pytree and returns the sharded flat parameter vector +
      sharded optimizer state; drop the original ``params`` reference
      afterwards or the memory saving never materializes.  One builder
      serves one parameter structure (the flat layout is captured at
      ``init``).
    """
    mesh = mesh or _state.mesh()
    n = _replica_count(mesh)

    if isinstance(optimizer, DistributedOptimizer):
        average = optimizer._average
        if optimizer._compression is not None:
            compression = optimizer._compression
        optimizer = optimizer._inner

    if validate_elementwise:
        _check_elementwise(optimizer, feature="FSDP",
                           api_name="make_fsdp_train_step")

    # Flat layout (unravel closure, true size, chunk) is fixed by the
    # parameter structure at init()/shard_params() time; step()/
    # full_params() read it.  One builder = one structure (enforced in
    # _capture_layout), so the jitted re-shard slicer is a single slot.
    layout: dict = {}

    def _capture_layout(params):
        # One builder serves one parameter structure: a later pytree
        # with the same element count but different leaf order would
        # silently misalign the already-sharded optimizer state, so any
        # structural change fails loudly here.
        sig = (jax.tree_util.tree_structure(params),
               tuple((tuple(leaf.shape), str(leaf.dtype)) for leaf in
                     jax.tree_util.tree_leaves(params)))
        if layout and layout["sig"] != sig:
            raise ValueError(
                "make_fsdp_train_step: parameter pytree structure "
                "differs from the one captured at init() — the sharded "
                "optimizer state is laid out for the original flat "
                "ordering, so re-sharding a different structure would "
                "silently apply wrong per-element state.  Build a new "
                "step for a new model structure.")
        flat, unravel, true_size = _pad_flat(params, n)
        layout["sig"] = sig
        layout["unravel"] = unravel
        layout["true_size"] = true_size
        layout["chunk"] = flat.size // n
        return flat, layout["chunk"]

    def _local_chunk(flat_padded, chunk):
        idx = jax.lax.axis_index(REPLICA_AXIS)
        return jax.lax.dynamic_slice(flat_padded, (idx * chunk,),
                                     (chunk,))

    def init(params):
        flat, chunk = _capture_layout(params)
        abstract = _abstract_state_or_raise(
            optimizer, chunk, flat.dtype, feature="FSDP",
            api_name="make_fsdp_train_step")

        def shard_and_init(flat_padded):
            p_chunk = _local_chunk(flat_padded, chunk)
            return p_chunk, optimizer.init(p_chunk)

        jitted = jax.jit(jax.shard_map(
            shard_and_init, mesh=mesh, in_specs=(P(),),
            out_specs=(P(REPLICA_AXIS), _sharded_state_specs(abstract)),
            check_vma=False), donate_argnums=(0,))
        return jitted(flat)

    def shard_params(params):
        """Re-shard a full parameter pytree (same structure as the one
        given to ``init``) without touching optimizer state — for
        checkpoint restore or broadcast-then-reshard."""
        flat, chunk = _capture_layout(params)
        if "shard_fn" not in layout:
            layout["shard_fn"] = jax.jit(jax.shard_map(
                lambda f: _local_chunk(f, chunk), mesh=mesh,
                in_specs=(P(),), out_specs=P(REPLICA_AXIS),
                check_vma=False), donate_argnums=(0,))
        return layout["shard_fn"](flat)

    def _layout():
        if not layout:
            raise RuntimeError(
                "make_fsdp_train_step: call init(params) before "
                "step()/full_params() — the flat parameter layout is "
                "captured there")
        return layout["unravel"], layout["true_size"], layout["chunk"]

    def per_replica_step(p_chunk, model_state, opt_state, batch):
        unravel, true_size, chunk = _layout()
        # One all_gather materializes the full parameters for the step;
        # its AD transpose is exactly the gradient reduce_scatter, but
        # the wire is kept explicit below so compression can ride it.
        flat_p = jax.lax.all_gather(p_chunk, REPLICA_AXIS, axis=0,
                                    tiled=True)

        if has_state:
            def flat_loss(fp):
                params = unravel(fp[:true_size])
                loss, new_state = loss_fn(params, model_state, batch)
                return loss, new_state

            loss, pull, new_model_state = jax.vjp(flat_loss, flat_p,
                                                  has_aux=True)
            # Synchronized BatchNorm, like the ZeRO-1/plain-DP builders.
            new_model_state = jax.tree_util.tree_map(
                lambda x: jax.lax.pmean(x, REPLICA_AXIS), new_model_state)
        else:
            def flat_loss(fp):
                return loss_fn(unravel(fp[:true_size]), batch)

            loss, pull = jax.vjp(flat_loss, flat_p)
            new_model_state = None
        (flat_g,) = pull(jnp.ones((), loss.dtype))

        ctx = None
        if compression is not None:
            flat_g, ctx = compression.compress(flat_g)
        g_chunk = jax.lax.psum_scatter(
            flat_g.reshape(n, chunk), REPLICA_AXIS, scatter_dimension=0)
        if compression is not None:
            g_chunk = compression.decompress(g_chunk, ctx)
        if average:
            g_chunk = g_chunk / n

        updates, opt_state = optimizer.update(g_chunk, opt_state, p_chunk)
        p_chunk = optax.apply_updates(p_chunk, updates)
        loss = jax.lax.pmean(loss, REPLICA_AXIS)
        if has_state:
            return p_chunk, new_model_state, opt_state, loss
        return p_chunk, opt_state, loss

    step_cache: dict = {}

    def _compiled(opt_state):
        specs = _sharded_state_specs(opt_state)
        key = jax.tree_util.tree_structure(specs), tuple(
            str(s) for s in jax.tree_util.tree_leaves(
                specs, is_leaf=lambda x: isinstance(x, P)))
        if key not in step_cache:
            if has_state:
                fn = per_replica_step
                in_specs = (P(REPLICA_AXIS), P(), specs, P(REPLICA_AXIS))
                out_specs = (P(REPLICA_AXIS), P(), specs, P())
                donate_argnums = (0, 1, 2) if donate else ()
            else:
                def fn(p_chunk, opt_state, batch):
                    return per_replica_step(p_chunk, None, opt_state,
                                            batch)
                in_specs = (P(REPLICA_AXIS), specs, P(REPLICA_AXIS))
                out_specs = (P(REPLICA_AXIS), specs, P())
                donate_argnums = (0, 1) if donate else ()
            jitted = jax.jit(
                jax.shard_map(fn, mesh=mesh, in_specs=in_specs,
                              out_specs=out_specs, check_vma=False),
                donate_argnums=donate_argnums)
            step_cache[key] = _throttle_on_cpu(jitted, mesh)
        return step_cache[key]

    if has_state:
        def step(p_shard, model_state, opt_state, batch):
            _layout()
            return _compiled(opt_state)(p_shard, model_state, opt_state,
                                        batch)
    else:
        def step(p_shard, opt_state, batch):
            _layout()
            return _compiled(opt_state)(p_shard, opt_state, batch)

    # Built once so repeat full_params calls hit the jit cache instead
    # of recompiling a fresh lambda every time.
    _gather = jax.jit(lambda x: x,
                      out_shardings=NamedSharding(mesh, P()))

    def full_params(p_shard):
        """The unsharded parameter pytree (device-gathered, replicated)
        — for rank-0 checkpointing (utils/checkpoint.py) or eval."""
        unravel, true_size, _ = _layout()
        return unravel(_gather(p_shard)[:true_size])

    return FsdpTrainStep(init=init, step=step, full_params=full_params,
                         shard_params=shard_params)


def make_fsdp_train_step_with_state(loss_fn, optimizer, mesh=None,
                                    average: bool = True,
                                    compression=None,
                                    donate: bool = True,
                                    validate_elementwise: bool = True,
                                    ) -> FsdpTrainStep:
    """Stateful-model spelling (BatchNorm etc.): ``loss_fn(params,
    model_state, batch) -> (loss, new_state)``; ``step(p_shard,
    model_state, opt_state, batch) -> (p_shard, model_state, opt_state,
    loss)`` — mirroring :func:`.zero.make_zero_train_step_with_state`."""
    return make_fsdp_train_step(loss_fn, optimizer, mesh=mesh,
                                average=average, compression=compression,
                                donate=donate, has_state=True,
                                validate_elementwise=validate_elementwise)
