"""SPMD training-step builder — the static fast path.

The reference has no trainer of its own (training loops live in user
scripts, e.g. examples/tensorflow_mnist.py:83-119); what it provides is the
wiring of collectives into the step.  On TPU the idiomatic wiring is a
single jitted SPMD program: batch sharded over the replica mesh axis,
parameters replicated, per-replica gradients reduced with fused ``psum``
(Tensor Fusion, ≙ docs/tensor-fusion.md), optimizer update computed
redundantly per replica — exactly the data-parallel semantics of
``hvd.DistributedOptimizer`` (tensorflow/__init__.py:170-192) with the
5 ms-tick negotiation replaced by compiler-scheduled ICI collectives.

``make_train_step`` is what the examples, benchmarks and the multi-chip
dryrun build on.
"""

from __future__ import annotations

import collections
import os
import time
from functools import partial
from typing import Any, Callable, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import NamedSharding, PartitionSpec as P

from .. import telemetry as _telemetry
from .. import trace as _trace
from ..core import state as _state
from ..core.state import REPLICA_AXIS
from ..memory import ledger as _mem
from ..memory import oom as _oom
from .data import DistributedOptimizer, allreduce_gradients

try:
    import optax
except Exception:  # pragma: no cover - optax is baked into the image
    optax = None

# Shared with parallel/input.py and frontends/loop.py (same registry
# entry): every place the loop blocks on the device/input feeds one
# histogram, so "is training host-bound?" is a single metric.
_M_HOST_STALL = _telemetry.histogram(
    "host.stall_seconds", "seconds",
    "time the training loop blocked waiting on the input queue")
# hvd-trace: one region per step call, named by what was built
# (step/stream, step/serial, step/monolithic, step/parallel).
_R_STEP = _trace.RegionFamily("step/", "step")


def batch_sharding(mesh=None) -> NamedSharding:
    """Sharding that splits the leading (batch) axis across replicas."""
    mesh = mesh or _state.mesh()
    return NamedSharding(mesh, P(REPLICA_AXIS))


def replicated_sharding(mesh=None) -> NamedSharding:
    mesh = mesh or _state.mesh()
    return NamedSharding(mesh, P())


def shard_batch(batch, mesh=None):
    """Place a host batch onto the mesh, leading axis split across replicas
    (the per-rank data sharding the reference gets from DistributedSampler /
    dataset shards, examples/pytorch_mnist.py:48-51).

    One batched ``jax.device_put`` over the whole pytree: a single
    transfer program per batch instead of one dispatch per leaf (the
    hvd-pipeline host-overlap contract; ``input.device_put_batch`` is
    the one implementation, which :func:`.input.prefetch_to_device`
    stages from a background thread)."""
    from .input import device_put_batch

    return device_put_batch(batch, mesh, sharding=batch_sharding(mesh))


def replicate(tree, mesh=None):
    from .input import device_put_batch

    return device_put_batch(tree, mesh, sharding=replicated_sharding(mesh))


def shard_local_batch(local_batch, mesh=None):
    """Assemble the global sharded batch from each process's LOCAL rows.

    The reference's input model: every rank loads only its own slice of
    the data (DistributedSampler / ``dataset.shard``, reference
    examples/pytorch_mnist.py:48-51) — no process ever materializes the
    global batch.  Each process passes its local leading-axis rows here;
    the processes' shards concatenate process-major into the global
    batch.  Complements :func:`shard_batch`, which expects the full
    global batch on every host (fine single-process; wasteful beyond).

    Every process MUST pass the same number of rows (the global leading
    axis is ``local_rows × process_count`` — drop or pad the dataset
    tail, as DistributedSampler does); the global shape is passed
    explicitly so a disagreement fails loudly instead of assembling
    inconsistent global arrays.
    """
    sh = batch_sharding(mesh)
    n_proc = _state.process_count()

    def put(x):
        x = np.asarray(x)
        return jax.make_array_from_process_local_data(
            sh, x, global_shape=(x.shape[0] * n_proc,) + x.shape[1:])

    return jax.tree_util.tree_map(put, local_batch)


def _is_cpu_mesh(mesh) -> bool:
    try:
        return mesh.devices.flat[0].platform == "cpu"
    except Exception:  # noqa: BLE001 — any exotic mesh: don't throttle
        return False


def _max_inflight_cpu() -> int:
    """In-flight step bound on CPU meshes (``HVD_TPU_MAX_INFLIGHT``,
    default 2 = dispatch step N+1 while step N executes)."""
    try:
        return max(1, int(os.environ.get("HVD_TPU_MAX_INFLIGHT", "2")))
    except ValueError:
        return 2


def _throttle_on_cpu(step_fn, mesh):
    """Bound async dispatch to a small in-flight window on CPU meshes.

    The host-platform backend (virtual devices for testing) runs every
    replica's collective on one shared thread pool; with unbounded async
    dispatch a long training loop stacks dozens of executions and the
    cross-replica rendezvous starves past XLA's 40 s abort
    (rendezvous.cc "Expected N threads to join").  Real TPU meshes are
    untouched — their pipelining is the performance model.

    The window defaults to 2 (``HVD_TPU_MAX_INFLIGHT``): calling the
    step for N+1 blocks on step N-1's outputs, so one step is always
    executing while the host dispatches the next — the pre-PR-5 hard
    per-step barrier (block on N before dispatching N+1) put a dispatch
    bubble between every pair of steps.  The blocked time is observed
    as ``host.stall_seconds``.
    """
    if not _is_cpu_mesh(mesh):
        return step_fn
    return _ThrottledStep(step_fn, _max_inflight_cpu())


class _ThrottledStep:
    """Callable wrapper keeping at most ``depth`` invocations in flight
    (see :func:`_throttle_on_cpu`); delegates the rest of the jit API
    (``lower``, ``trace``, ``clear_cache``, ...) to the wrapped step."""

    def __init__(self, step_fn, depth: int = 2):
        self._step_fn = step_fn
        self._depth = depth
        self._inflight = collections.deque()
        from ..tuning import actuation as _actuation

        _actuation.register_inflight_window(self)

    def resize(self, depth: int) -> None:
        """hvd-tune live retune: a shrink takes effect by draining down
        to the new depth on the next call — no flush here (the drain
        tick must never block on device results)."""
        self._depth = max(1, int(depth))

    def __call__(self, *args, **kw):
        while len(self._inflight) >= self._depth:
            popped = self._inflight.popleft()
            t0 = time.perf_counter()
            for leaf in jax.tree_util.tree_leaves(popped):
                # A leaf donated into a later dispatch is deleted; that
                # dispatch is ordered behind this one on every device,
                # so blocking on the surviving leaves suffices.
                deleted = getattr(leaf, "is_deleted", None)
                if deleted is not None and deleted():
                    continue
                jax.block_until_ready(leaf)
            _M_HOST_STALL.observe(time.perf_counter() - t0)
        out = self._step_fn(*args, **kw)
        self._inflight.append(out)
        return out

    def __getattr__(self, name):
        return getattr(self._step_fn, name)


class _TracedStep:
    """Per-step bookkeeping wrapper: advance the hvd-trace step id
    (trace/__init__.py) so every span carries the step that owns it,
    close the hvd-mem ledger's step window (the per-step high-watermark
    gauge), and — first call only — pre-flight-warn when the working
    set this step implies (params + gradients + optimizer slots +
    batch) exceeds the advertised HBM capacity (memory/oom.py).
    The call of the step itself is one ``step/<kind>`` region: the host
    time of a step, on the profiler's clock too.  ``kind`` None asks the
    overlap step what it built (its schedule, or ``monolithic`` after a
    fallback).  Arithmetic is untouched; the jit surface passes through
    like :class:`_ThrottledStep`'s."""

    def __init__(self, step_fn, kind: Optional[str]):
        self._step_fn = step_fn
        self._kind = kind
        self._preflighted = False

    def _preflight(self, args) -> None:
        self._preflighted = True
        if _oom.advertised_capacity() is None or not args:
            return
        try:
            params_b = _mem.tree_nbytes(args[0])
            batch_b = _mem.tree_nbytes(args[-1]) if len(args) > 1 else 0
            # params + grads + two optimizer slots (the adam-shaped
            # upper bound) + the batch: the static working-set model
            # of docs/memory.md.
            _oom.preflight_warn(
                4 * params_b + batch_b, "make_train_step",
                f"params {params_b} B x (1 grad + 2 opt slots) + "
                f"batch {batch_b} B")
        except Exception:  # noqa: BLE001 — sizing is observability
            pass

    def __call__(self, *args, **kw):
        if _trace.trace_enabled_env():
            _trace.on_step()
        if not self._preflighted:
            self._preflight(args)
        kind = self._kind
        if kind is None:
            fn = self._step_fn
            kind = fn.schedule if fn.overlap_active else "monolithic"
        with _R_STEP[kind]():
            out = self._step_fn(*args, **kw)
        if _mem.enabled():
            _mem.ledger.note_step()
        return out

    def __getattr__(self, name):
        return getattr(self._step_fn, name)


def _traced(step_fn, kind: Optional[str]):
    return _TracedStep(step_fn, kind)


def _make_step(loss_fn, optimizer, mesh, average, fusion_threshold,
               has_aux, donate, has_state, op=None, overlap=None):
    """Shared builder behind :func:`make_train_step` and
    :func:`make_train_step_with_state` — one place wires the reduction,
    pmean placement, shard_map specs and donation for both variants.

    ``overlap`` (default: the ``HVD_TPU_OVERLAP`` env knob) selects the
    backward/communication-overlap schedule (parallel/overlap.py):
    ``off`` keeps this monolithic single-program step (``auto``'s
    answer wherever this process owns the whole mesh); ``on``/``serial``
    build the bucketed-backward path whose gradient buckets ride the
    dynamic megakernel executor per bucket.
    """
    from . import overlap as _overlap
    from .data import _resolve_grad_op

    mesh = mesh or _state.mesh()

    compression = None
    if isinstance(optimizer, DistributedOptimizer):
        average = optimizer._average
        if op is None:
            op = optimizer._op
        if optimizer._fusion_threshold is not None:
            fusion_threshold = optimizer._fusion_threshold
        compression = optimizer._compression
        optimizer = optimizer._inner

    schedule = _overlap.resolve_mode(overlap, mesh)
    red_op = _resolve_grad_op(average, op)
    # Adasum never overlaps (its scale-insensitive combination is
    # defined on the WHOLE gradient vector) — but the overlap builder
    # owns that decision now, so the fallback is warned, counted
    # (overlap.fallbacks) and flight-recorded under its name like
    # every other unbucketable case.
    if schedule != "off":
        inner_optimizer = optimizer

        def fallback_builder():
            return _build_static_step(loss_fn, inner_optimizer, mesh,
                                      average, fusion_threshold, has_aux,
                                      donate, has_state, op, compression)

        step = _overlap.make_overlapped_step(
            loss_fn, optimizer, mesh, red_op, fusion_threshold, has_aux,
            donate, has_state, compression, stream=schedule == "stream",
            fallback_builder=fallback_builder)
        return _traced(
            _throttle_on_cpu(step, mesh),
            None if isinstance(step, _overlap._OverlapStep)
            else "monolithic")
    return _traced(_build_static_step(loss_fn, optimizer, mesh, average,
                                      fusion_threshold, has_aux, donate,
                                      has_state, op, compression),
                   "monolithic")


def _build_static_step(loss_fn, optimizer, mesh, average, fusion_threshold,
                       has_aux, donate, has_state, op, compression):
    """The pre-overlap monolithic step: forward + backward + in-program
    bucketed reduction + optimizer apply compiled as ONE SPMD program
    (exactly what ``HVD_TPU_OVERLAP=off`` must restore)."""
    # The stateful loss returns (loss, new_state) — an aux output.
    grad_fn = jax.value_and_grad(loss_fn, has_aux=has_aux or has_state)

    def per_replica(params, model_state, batch):
        args = (params, model_state, batch) if has_state else (params, batch)
        out, grads = grad_fn(*args)
        loss = out[0] if (has_aux or has_state) else out
        aux = out[1] if (has_aux or has_state) else None
        # Fused cross-replica gradient reduction (Tensor Fusion over psum;
        # op=Adasum swaps in the whole-gradient ppermute ladder).
        grads = allreduce_gradients(grads, average=average,
                                    fusion_threshold=fusion_threshold,
                                    compression=compression, op=op)
        # Report the global mean loss, like MetricAverageCallback would
        # (keras/callbacks.py:37-87).  Aux outputs — metrics, or the
        # updated BatchNorm statistics in the stateful variant — are
        # averaged the same way; for BN stats this is synchronized
        # BatchNorm riding the same compiled collective schedule as the
        # gradients (the reference instead leaves stats per-worker and
        # relies on rank-0 checkpointing, README.md:102-104).
        loss = jax.lax.pmean(loss, REPLICA_AXIS)
        aux = jax.tree_util.tree_map(
            lambda x: jax.lax.pmean(x, REPLICA_AXIS), aux)
        return loss, grads, aux

    sharded = jax.shard_map(
        per_replica, mesh=mesh,
        in_specs=(P(), P(), P(REPLICA_AXIS)),
        out_specs=(P(), P(), P()),
        check_vma=False)

    def apply(grads, opt_state, params):
        updates, opt_state = optimizer.update(grads, opt_state, params)
        return optax.apply_updates(params, updates), opt_state

    if has_state:
        def step(params, model_state, opt_state, batch):
            loss, grads, model_state = sharded(params, model_state, batch)
            params, opt_state = apply(grads, opt_state, params)
            return params, model_state, opt_state, loss

        donate_argnums = (0, 1, 2) if donate else ()
    else:
        def step(params, opt_state, batch):
            loss, grads, aux = sharded(params, None, batch)
            params, opt_state = apply(grads, opt_state, params)
            if has_aux:
                return params, opt_state, loss, aux
            return params, opt_state, loss

        donate_argnums = (0, 1) if donate else ()
    return _throttle_on_cpu(jax.jit(step, donate_argnums=donate_argnums),
                            mesh)


def make_train_step(
    loss_fn: Callable[..., Any],
    optimizer,
    mesh=None,
    average: bool = True,
    fusion_threshold: Optional[int] = None,
    has_aux: bool = False,
    donate: bool = True,
    op=None,
    overlap: Optional[str] = None,
):
    """Build the jitted data-parallel train step.

    Args:
      loss_fn: ``loss_fn(params, batch) -> scalar`` (or ``(scalar, aux)``
        with ``has_aux=True``).  Called per replica on the local shard.
        A :class:`~horovod_tpu.parallel.overlap.ChainedLoss` additionally
        lets the overlap mode segment the backward pass per stage.
      optimizer: an optax ``GradientTransformation`` or a
        :class:`DistributedOptimizer` (unwrapped — its averaging flags are
        honored; reduction happens once, inside the replica context).
      mesh: replica mesh; defaults to the global one from ``init()``.
      average: average (True) or sum (False) gradients across replicas.
      fusion_threshold: Tensor-Fusion bucket size in bytes; defaults to
        ``HOROVOD_FUSION_THRESHOLD`` (64 MB).  This is more than a
        wire-packing knob: under the overlap mode the SAME partition
        sets the dispatch-boundary granularity (each bucket = one
        megakernel streamed out of the backward pass,
        docs/performance.md).  ``op=Adasum`` ignores it entirely — the
        whole-gradient combination neither buckets nor overlaps.
      op: hvd.Average/Sum/Adasum (supersedes ``average``); Adasum compiles
        the whole-gradient ppermute ladder into the step.
      overlap: backward/communication-overlap schedule —
        ``auto``/``on``/``off``/``serial``; defaults to the
        ``HVD_TPU_OVERLAP`` env knob (parallel/overlap.py).  ``auto``
        is ``off`` wherever this process drives every device of the
        mesh (one chip, one host's chips, CPU meshes) and the stream
        schedule only on an accelerator mesh that spans processes.

    Returns:
      ``step(params, opt_state, batch) -> (params, opt_state, loss[, aux])``
      — one compiled SPMD program with the in-program bucketed psum
      (overlap off: what ``auto`` builds on a mesh one process owns),
      or the bucketed-backward sub-program pipeline (overlap on; same
      gradients, identity contract in parallel/overlap.py);
      batch's leading axis must be divisible by the replica count.
    """
    return _make_step(loss_fn, optimizer, mesh, average, fusion_threshold,
                      has_aux, donate, has_state=False, op=op,
                      overlap=overlap)


def make_train_step_with_state(
    loss_fn: Callable[..., Any],
    optimizer,
    mesh=None,
    average: bool = True,
    fusion_threshold: Optional[int] = None,
    donate: bool = True,
    op=None,
    overlap: Optional[str] = None,
):
    """Train-step builder for models carrying non-trained state (BatchNorm
    statistics): ``loss_fn(params, model_state, batch) -> (loss, new_state)``;
    the updated statistics are ``pmean``-ed every step (synchronized
    BatchNorm).  ``fusion_threshold`` and ``overlap`` behave exactly as
    in :func:`make_train_step` (the stateful variant overlaps through
    the single-backward streaming schedule).

    Returns ``step(params, model_state, opt_state, batch) ->
    (params, model_state, opt_state, loss)``.
    """
    return _make_step(loss_fn, optimizer, mesh, average, fusion_threshold,
                      has_aux=False, donate=donate, has_state=True, op=op,
                      overlap=overlap)


def make_parallel_train_step(loss_fn: Callable[..., Any], optimizer,
                             mesh, batch_spec, donate: bool = True):
    """Train-step builder for multi-axis (dp/tp/sp/pp/ep) parallelism.

    ``loss_fn(params, batch)`` is a *local-shard* loss (e.g. from
    ``models.transformer.make_loss_fn``) that pmean-reduces itself over
    every mesh axis, so the shard_map output is a replicated logical
    scalar and ``jax.grad`` outside the shard_map produces exact global
    gradients (the replicated-parameter transpose inserts the psum — no
    manual gradient reduction step, unlike the 1-axis DP builders above).

    ``batch_spec`` is the PartitionSpec (or pytree of specs) describing
    how the host batch is laid out over the mesh.
    """
    sharded_loss = jax.shard_map(
        loss_fn, mesh=mesh, in_specs=(P(), batch_spec), out_specs=P(),
        check_vma=False)

    def step(params, opt_state, batch):
        loss, grads = jax.value_and_grad(sharded_loss)(params, batch)
        updates, opt_state = optimizer.update(grads, opt_state, params)
        params = optax.apply_updates(params, updates)
        return params, opt_state, loss

    donate_argnums = (0, 1) if donate else ()
    return _traced(_throttle_on_cpu(
        jax.jit(step, donate_argnums=donate_argnums), mesh), "parallel")


def shard_parallel_batch(batch, mesh, batch_spec):
    """Place a host batch onto a multi-axis mesh per ``batch_spec``
    (a PartitionSpec, or a pytree of specs matching ``batch``) — one
    batched ``jax.device_put`` over the whole pytree, preserving the
    per-leaf shardings (same single-transfer contract as
    :func:`shard_batch`)."""
    from .input import device_put_batch

    return device_put_batch(batch, mesh, sharding=batch_spec)


# ---------------------------------------------------------------------------
# Completion fencing for the async-dispatch loop
# ---------------------------------------------------------------------------

@partial(jax.jit, donate_argnums=())
def _fence_program(x):
    return x + 1


def barrier_fence(*trees) -> None:
    """Block the host until previously dispatched device work completes.

    The async-dispatch loop (hvd-pipeline) returns un-fetched device
    arrays and defers metric fetches, so the Python loop runs ahead of
    the hardware.  Code that needs a completion point — wall-clock
    measurement, checkpoint-consistent reads, handing buffers to
    non-JAX code — calls this fence:

    * ``barrier_fence(tree, ...)`` blocks until every leaf of the given
      pytrees is computed (``jax.block_until_ready``).
    * ``barrier_fence()`` blocks until EVERY local device of the replica
      mesh has drained its execution stream: a trivial program is
      dispatched per device behind all queued work and blocked on
      (per-device programs execute in dispatch order).

    Host-side only — no collective, no control-plane traffic (unlike
    ``hvd.barrier()``, which synchronizes *ranks*).  The blocked time is
    recorded in ``host.stall_seconds``.
    """
    t0 = time.perf_counter()
    if trees:
        for t in trees:
            jax.block_until_ready(t)
    else:
        if _state.is_initialized():
            devices = [d for d in _state.global_state().devices
                       if d.process_index == jax.process_index()]
        else:
            devices = jax.local_devices()
        probes = [_fence_program(jax.device_put(jnp.zeros((), jnp.int32), d))
                  for d in devices]
        for p in probes:
            jax.block_until_ready(p)
    _M_HOST_STALL.observe(time.perf_counter() - t0)


def make_eval_step(metric_fn: Callable[..., Any], mesh=None):
    """Build a jitted eval step: per-replica metrics averaged across the
    mesh (≙ MetricAverageCallback's end-of-epoch allreduce,
    keras/callbacks.py:37-87)."""
    mesh = mesh or _state.mesh()

    def per_replica(params, batch):
        m = metric_fn(params, batch)
        return jax.tree_util.tree_map(
            lambda x: jax.lax.pmean(x, REPLICA_AXIS), m)

    sharded = jax.shard_map(
        per_replica, mesh=mesh, in_specs=(P(), P(REPLICA_AXIS)),
        out_specs=P(), check_vma=False)
    return _throttle_on_cpu(jax.jit(sharded), mesh)
