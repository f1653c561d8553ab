"""Eager (dynamic-path) collective operations.

TPU-native re-design of the reference's op layer: the TF/Torch adapters +
enqueue API (tensorflow/mpi_ops.cc, torch/mpi_ops.cc,
common/operations.cc:1543-1650) collapse into this module because JAX is the
only frontend tensor type and XLA owns async execution.

Semantics (Horovod parity):
  * ``allreduce(x)``  — sum (or average) across all replicas; every replica
    receives the reduced tensor (reference: operations.cc:941-1034).
  * ``allgather(x)``  — concatenate along dim 0 in rank order; every replica
    receives the full result; non-first dims must agree, dim 0 may differ
    per replica (reference: operations.cc:695-756, MPI_Allgatherv).
  * ``broadcast(x, root_rank)`` — every replica receives root's tensor
    (reference: operations.cc:1040-1059).
  * ``*_async`` / ``poll`` / ``synchronize`` — handle-based async API
    (reference: torch/mpi_ops.cc:206-332); backed by XLA async dispatch.

Input layouts:
  * a *per-replica* array created by :func:`shard` (leading axis == size,
    sharded over the ``"hvd"`` mesh axis): element ``i`` is replica ``i``'s
    contribution — the moral equivalent of each MPI rank passing its local
    tensor.
  * a plain (host or replicated) array: every replica contributes the same
    value — the common case for metrics and single-controller use.
  * a *list* of per-replica arrays (allgather only): contributions whose
    dim 0 differs per replica (the MPI_Allgatherv case).

Every eager call runs the full dynamic-path machinery for observability
parity: named request submitted to the coordinator per replica,
cross-replica validation (mismatch errors raised as
:class:`HorovodError`), timeline NEGOTIATE/QUEUE/XLA_* events, then a
compiled ``shard_map`` collective over the replica mesh.  Async calls are
*queued* and executed in fused buckets (Tensor Fusion,
reference: docs/tensor-fusion.md, operations.cc:1328-1374) when drained.
"""

from __future__ import annotations

import contextlib
import math
import sys
import threading
import time
from dataclasses import dataclass, field
from typing import Any, Callable, Dict, List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import NamedSharding, PartitionSpec as P

from .. import telemetry as _telemetry
from .. import trace as _trace
from ..analysis import lockorder as _lockorder
from ..analysis import program as _program
from ..analysis import threads as _athreads
from .. import chaos as _chaos
from ..core import state as _state
from ..core.state import REPLICA_AXIS
from . import compression as _compression
from . import megakernel as _megakernel
from . import wire
from ..analysis import races as _races
from .wire import ReduceOp, Request, RequestType, Response, ResponseType

# Public reduction-operator constants (≙ the post-v0.13 hvd.Average /
# hvd.Sum / hvd.Adasum / hvd.Min / hvd.Max / hvd.Product; the v0.13
# reference hard-codes MPI_SUM + the average divide).
Average = ReduceOp.AVERAGE
Sum = ReduceOp.SUM
Adasum = ReduceOp.ADASUM
Min = ReduceOp.MIN
Max = ReduceOp.MAX
Product = ReduceOp.PRODUCT

# Kernel-table prefix per reduce op ("psum" kernels serve both SUM and
# AVERAGE — average is a post-divide, reference mpi_ops.cc:57-62).
_OP_KERNEL = {
    ReduceOp.SUM: "psum", ReduceOp.AVERAGE: "psum",
    ReduceOp.MIN: "pmin", ReduceOp.MAX: "pmax",
    ReduceOp.PRODUCT: "pprod", ReduceOp.ADASUM: "adasum",
}


class HorovodError(RuntimeError):
    """Cross-replica validation failure (≙ the reference's
    FailedPreconditionError surfaced from ERROR responses,
    operations.cc:1060-1067)."""


# hvd-telemetry instrumentation (docs/metrics.md).  Event-granularity
# budget: _enqueue spends one ``time.monotonic`` read per op and the
# response executor one pair per response (its ``execute/<op>`` region's:
# the histogram and the span share them); the per-submit steady-state
# hot path (cache hits) is instrumented pull-side from CacheStats
# instead.
_M_SUBMITTED = _telemetry.counter(
    "collective.submitted", "eager collectives entering negotiation")
_M_COMPLETED = _telemetry.counter(
    "collective.completed", "eager collectives executed")
_M_ERRORS = _telemetry.counter(
    "collective.errors", "validation/shutdown errors surfaced")
_M_NEGOTIATE_S = _telemetry.histogram(
    "collective.negotiate_seconds", "seconds",
    "submit -> broadcast response (negotiate + queue phases)")
_M_EXECUTE_S = _telemetry.histogram(
    "collective.execute_seconds", "seconds",
    "response -> XLA dispatch complete (execute phase)")
_M_PAYLOAD_B = _telemetry.histogram(
    "collective.payload_bytes", "bytes", "per-tensor payload size")
_M_GROUP_WIDTH = _telemetry.histogram(
    "fusion.group_width", "count", "tensors per fused response")


# Error-message parity with the reference's SHUT_DOWN_ERROR
# (operations.cc:181-188); pending callbacks are flushed with it during
# shutdown and late arrivals raise it.
SHUT_DOWN_ERROR_MESSAGE = (
    "Horovod has been shut down. This was caused by an exception on one of "
    "the ranks or an attempt to allreduce, allgather or broadcast a tensor "
    "after one of the ranks finished execution.")


def _poison_pending(message: str = SHUT_DOWN_ERROR_MESSAGE) -> None:
    """Fail every queued-but-unlaunched collective (≙ the reference's
    SHUT_DOWN_ERROR callback flush, operations.cc:1377-1474)."""
    st = _state.global_state()
    ops = _queue.take(list(_queue.pending_meta()))
    err = HorovodError(message)
    for o in ops:
        st.handle_manager._get(o.handle).result = err


def _initiate_shutdown(message: str = SHUT_DOWN_ERROR_MESSAGE) -> None:
    """One rank decided to shut down (or died): mark the runtime, tell
    the workers (controller only), flush pending ops.  Callers must hold
    ``_drain_lock`` or have stopped the background drain first — the
    single shutdown-protocol step shared by ``hvd.shutdown()`` and the
    controller's drain loop (≙ operations.cc:1377-1403)."""
    st = _state.global_state()
    st.peer_shutdown = True
    if st.response_cache is not None:
        # Dead-peer / shutdown poisoning: cached cycles must never
        # replay across the teardown; orphans are dropped — everything
        # pending is about to be poisoned below anyway.
        st.response_cache.flush("shutdown")
    if (st.multiprocess and st.transport is not None
            and st.process_index == 0):
        st.transport.broadcast_responses(
            [Response(ResponseType.SHUTDOWN, error_message=message)])
    _poison_pending(message)


def _handle_lost_ranks(st, tp) -> None:
    """Controller-side dead-peer handling: EOF without the exit handshake
    = the process died.  It can never reach jax.distributed's exit
    barrier; don't let that block (then abort) any survivor — the marked
    diagnosis makes the workers disarm too.  Callers must hold
    ``_drain_lock`` or have stopped the background drain first (same
    contract as ``_initiate_shutdown``); called from the drain loop and
    from ``hvd.shutdown()`` when the death lands after the last tick."""
    from ..core import cluster as _cluster

    _cluster.disarm_distributed_shutdown()
    ranks = sorted(tp.lost_ranks)
    pending = bool(_queue.pending_meta()) or bool(
        st.coordinator.check_stalled(threshold=0.0))
    detail = " while collectives were pending" if pending else ""
    # hvd-chaos: a rank lost through the reconnect machinery (grace
    # expiry, replay-ring overflow) carries a reason naming the fault —
    # fold it into the diagnostic so operators see WHY, not just WHO.
    reasons = getattr(tp, "lost_reasons", {})
    why = "; ".join(f"rank {r}: {reasons[r]}" for r in ranks
                    if r in reasons)
    if why:
        detail += f" ({why})"
    _telemetry.dead_peer_event(
        f"rank(s) {ranks} {wire.DEAD_PEER_MARKER}{detail}")
    _initiate_shutdown(
        f"Horovod has been shut down: rank(s) {ranks} "
        f"{wire.DEAD_PEER_MARKER}{detail}.")
    print(f"ERROR: worker rank(s) {ranks} {wire.DEAD_PEER_MARKER};"
          f"{' pending collectives failed;' if pending else ''}"
          f" shutting down.", file=sys.stderr)


# Autogenerated op names (≙ torch/mpi_ops.cc:35-40 "prefix.noname.<n>").
_name_lock = _lockorder.make_lock("collective._name_lock")
_name_counters: Dict[str, int] = {}


def _auto_name(prefix: str, ps=None) -> str:
    """Generate a unique op name (≙ the reference's prefix.noname.<n>,
    torch/mpi_ops.cc:35-40).  Process-set ops get their own namespace
    AND counter: set members consume names non-members never see, so a
    shared counter would desync the ranks' auto-names for later GLOBAL
    ops (and a bare collision could misroute a set response into a
    non-member's global op of the same name)."""
    if ps is not None:
        prefix = f"ps{ps.process_set_id}.{prefix}"
    with _name_lock:
        n = _name_counters.get(prefix, 0) + 1
        _name_counters[prefix] = n
        return f"{prefix}.noname.{n}"


# ---------------------------------------------------------------------------
# Input classification and device placement
# ---------------------------------------------------------------------------

@dataclass
class _Contribution:
    """Normalized description of one eager collective's input."""

    per_replica: bool                 # leading axis is the replica axis
    shapes: List[Tuple[int, ...]]     # per-replica payload shapes
    dtype: Any
    devices: List[int]                # wire device ids per replica
    value: Any                        # canonical device array
    ragged: bool = False              # list input with differing dim-0
    orig_sizes: List[int] = field(default_factory=list)
    # True when ``value`` is a buffer the executor itself materialized
    # (host input converted by jnp.asarray / an _on_mesh copy) and the
    # caller can never observe again: the megakernel donates exactly
    # these (ops/megakernel.py) — user-held jax.Arrays are never donated.
    owned: bool = False


def _wire_device(x) -> int:
    if isinstance(x, jax.Array):
        try:
            dev = list(x.devices())[0]
            return dev.id
        except Exception:
            return wire.CPU_DEVICE_ID
    return wire.CPU_DEVICE_ID


def is_per_replica(x) -> bool:
    """True if ``x`` is laid out with its leading axis sharded over the
    replica mesh axis (the layout :func:`shard` produces)."""
    if not isinstance(x, jax.Array):
        return False
    sh = x.sharding
    if not isinstance(sh, NamedSharding):
        return False
    spec = sh.spec
    if len(spec) == 0:
        return False
    first = spec[0]
    if isinstance(first, tuple):
        return REPLICA_AXIS in first
    return first == REPLICA_AXIS


def shard(per_replica_values, axis: int = 0) -> jax.Array:
    """Build a per-replica array from stacked contributions.

    ``per_replica_values`` is an array (or list) whose leading axis indexes
    replicas (length == ``size()``).  The result is a global array with that
    axis sharded over the replica mesh — the TPU analogue of "each MPI rank
    holds its local tensor".
    """
    st = _state.global_state()
    _state._check_initialized()
    if st.multiprocess:
        raise ValueError(
            "shard() assembles all replicas' contributions from one host "
            "and is single-process only; in multi-process mode each "
            "process passes its own local tensor to the collective "
            "directly (the reference's per-rank calling convention).")
    x = jnp.asarray(per_replica_values) if not isinstance(
        per_replica_values, jax.Array) else per_replica_values
    if x.shape[0] != st.size:
        raise ValueError(
            f"Leading axis ({x.shape[0]}) must equal the replica count "
            f"({st.size}) for a per-replica array.")
    spec = [None] * x.ndim
    spec[axis] = REPLICA_AXIS
    sharding = NamedSharding(st.mesh, P(*spec))
    return jax.device_put(x, sharding)


def _on_mesh(xa, mesh):
    """Normalize an array COMMITTED to a different device set (e.g. a
    process-set collective's output fed into a global one, or vice
    versa) back to host so the target mesh's jitted kernel can place it
    — users naturally chain collectives across communicators.
    Uncommitted arrays are left alone (jit moves those freely)."""
    if isinstance(xa, jax.Array) and getattr(xa, "committed", False):
        try:
            devs = xa.sharding.device_set
        except Exception:  # noqa: BLE001 — conservative across jax versions
            return xa
        if devs != set(mesh.devices.flat):
            return jnp.asarray(np.asarray(xa))
    return xa


def _classify(x, op: RequestType, ps=None) -> _Contribution:
    st = _state.global_state()
    size = st.size
    if ps is not None and not st.multiprocess:
        # Single-process process-set contribution: replicated values (one
        # logical contribution per member) or a per-member list for the
        # ragged allgather.  A globally-sharded per-replica array has no
        # canonical sub-slicing onto the set, so it is rejected.
        k = ps.size()
        if isinstance(x, (list, tuple)) and op == RequestType.ALLGATHER:
            if len(x) != k:
                raise ValueError(
                    f"allgather over process set {ps.process_set_id} with "
                    f"a list input needs one contribution per member "
                    f"({k}), got {len(x)}.")
            arrs = [jnp.asarray(v) for v in x]
            shapes = [tuple(a.shape) for a in arrs]
            return _Contribution(
                per_replica=True, shapes=shapes, dtype=arrs[0].dtype,
                devices=[_wire_device(a) for a in arrs], value=arrs,
                ragged=len(set(shapes)) > 1,
                orig_sizes=[s[0] if s else 0 for s in shapes])
        xa = x if isinstance(x, jax.Array) else jnp.asarray(x)
        if is_per_replica(xa):
            raise ValueError(
                "process-set collectives take replicated values or "
                "per-member lists; a per-replica array sharded over the "
                "GLOBAL mesh has no canonical sub-slicing onto the set — "
                "use the static path with a mesh over the subset instead.")
        xa = _on_mesh(xa, ps.mesh_and_kernels()[0])
        payload = tuple(xa.shape)
        return _Contribution(
            per_replica=False, shapes=[payload] * k, dtype=xa.dtype,
            devices=[_wire_device(xa)] * k, value=xa,
            orig_sizes=[payload[0] if payload else 0] * k,
            owned=xa is not x)
    if st.multiprocess:
        # Reference layout: each process contributes exactly its own local
        # tensor (one MPI rank per process); the coordinator learns the
        # other ranks' shapes from their own requests.
        if isinstance(x, (list, tuple)) and op == RequestType.ALLGATHER:
            raise ValueError(
                "list-input allgather is the single-process spelling; in "
                "multi-process mode pass this process's own contribution "
                "(dim 0 may differ per rank).")
        xa = x if isinstance(x, jax.Array) else jnp.asarray(x)
        payload = tuple(xa.shape)
        return _Contribution(
            per_replica=True, shapes=[payload], dtype=xa.dtype,
            devices=[_wire_device(xa)], value=xa,
            orig_sizes=[payload[0] if payload else 0],
            owned=xa is not x)
    if isinstance(x, (list, tuple)) and op == RequestType.ALLGATHER:
        if len(x) != size:
            raise ValueError(
                f"allgather with a list input needs one contribution per "
                f"replica ({size}), got {len(x)}.")
        arrs = [jnp.asarray(v) for v in x]
        shapes = [tuple(a.shape) for a in arrs]
        sizes = [s[0] if s else 0 for s in shapes]
        ragged = len(set(shapes)) > 1
        return _Contribution(
            per_replica=True, shapes=shapes, dtype=arrs[0].dtype,
            devices=[_wire_device(a) for a in arrs], value=arrs,
            ragged=ragged, orig_sizes=sizes)
    dev = _wire_device(x)
    xa = x if isinstance(x, jax.Array) else jnp.asarray(x)
    xa = _on_mesh(xa, st.mesh)  # a set-collective output fed back in
    if is_per_replica(xa):
        payload = tuple(xa.shape[1:])
        return _Contribution(
            per_replica=True, shapes=[payload] * size, dtype=xa.dtype,
            devices=[d.id for d in st.devices],
            value=xa, orig_sizes=[payload[0] if payload else 0] * size,
            owned=xa is not x)
    payload = tuple(xa.shape)
    return _Contribution(
        per_replica=False, shapes=[payload] * size, dtype=xa.dtype,
        devices=[dev] * size, value=xa,
        orig_sizes=[payload[0] if payload else 0] * size,
        owned=xa is not x)


# ---------------------------------------------------------------------------
# Compiled collective kernels (cached per mesh via jit's shape/dtype cache)
# ---------------------------------------------------------------------------

def _build_kernels(mesh):
    """All jitted shard_map collective kernels for one mesh.

    Shared by the single-process replica mesh and the multi-process
    process mesh — the kernel bodies are identical; only the mesh (and
    which entries get used) differs.
    """

    def sm(fn, in_spec, out_spec, check_vma=True):
        # check_vma=False where the output is replicated by construction
        # (all_gather / masked-psum broadcast) but the static checker cannot
        # infer it.
        return jax.jit(jax.shard_map(
            fn, mesh=mesh, in_specs=in_spec, out_specs=out_spec,
            check_vma=check_vma))

    def _gather_block(x):
        x = jnp.squeeze(x, axis=0)
        return jax.lax.all_gather(x, REPLICA_AXIS, axis=0, tiled=True)

    def _psum_squeeze_block(x):
        return jax.lax.psum(jnp.squeeze(x, axis=0), REPLICA_AXIS)

    def _bcast_block(x, root):
        x = jnp.squeeze(x, axis=0)
        idx = jax.lax.axis_index(REPLICA_AXIS)
        contrib = jnp.where(idx == root, x, jnp.zeros_like(x))
        return jax.lax.psum(contrib, REPLICA_AXIS)

    n = mesh.shape[REPLICA_AXIS]

    def _rscatter_pr_block(x):
        # Per-replica [n, d0, ...]: reduce then keep this replica's
        # dim-0 chunk (the post-v0.13 hvd.reducescatter semantics) —
        # XLA's native ReduceScatter over ICI, not a psum + slice.
        v = jnp.squeeze(x, axis=0)
        return jax.lax.psum_scatter(v, REPLICA_AXIS, scatter_dimension=0,
                                    tiled=True)[None]

    def _rscatter_rep_block(x):
        return jax.lax.psum_scatter(x, REPLICA_AXIS, scatter_dimension=0,
                                    tiled=True)[None]

    def _a2a_block(x):
        # Per-sender [n(dest), M, rest] blocks → per-receiver
        # [n(sender), M, rest]: XLA's native AllToAll on ICI.  Ragged
        # splits ride pad-to-max M (the split matrix is negotiated, so
        # M is static at trace time), like the ragged allgather.
        v = jnp.squeeze(x, axis=0)
        return jax.lax.all_to_all(v, REPLICA_AXIS, split_axis=0,
                                  concat_axis=0, tiled=False)[None]

    def _prod_all(x):
        # No lax.pprod exists: gather every contribution and reduce
        # locally (XLA fuses the pointwise product into the gather's
        # consumer).
        return jnp.prod(jax.lax.all_gather(x, REPLICA_AXIS, axis=0), axis=0)

    def _adasum_ladder(x):
        """Adasum recursive-doubling ladder over the mesh axis.

        The post-v0.13 Horovod Adasum operator (scale-insensitive
        gradient combining, arXiv:2006.02924): for a pair (a, b),
        ``adasum(a,b) = (1 - a·b/2||a||²) a + (1 - a·b/2||b||²) b``,
        applied log2(n) times at doubling distances so every replica
        ends with the full combination — expressed TPU-natively as
        ``ppermute`` exchange rounds on ICI (instead of the reference
        era's MPI recursive halving).  The formula is symmetric, so
        both partners compute bit-identical results with no extra
        agreement round.  Requires power-of-two n (checked at enqueue).
        """
        shape = x.shape
        acc = jnp.promote_types(x.dtype, jnp.float32)
        v = x.reshape(-1).astype(acc)
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
        return v.astype(x.dtype).reshape(shape)

    def _adasum_vhdd(x):
        """Bandwidth-optimal Adasum: vector-halving distance-doubling
        (the VHDD scheme of the Adasum paper, arXiv:2006.02924 §4.2).

        The ladder above exchanges the FULL vector every round —
        log2(n)·|v| on the wire.  VHDD computes the SAME recursive
        pairwise tree with distributed fragments: at round r partners at
        distance 2^r swap complementary halves of their |v|/2^r working
        fragment (wire: |v|/2^(r+1)), combine, and recurse; after
        log2(n) rounds each replica owns the fully-combined |v|/n
        fragment, and a mirrored doubling phase allgathers the result —
        total wire ≈ 2·|v| plus 3 scalars per round.

        The level-r dot products span the level's full distributed
        vector: after the swap each replica in the 2^(r+1)-block holds a
        distinct sub-range of the block's (A, B) pair — partners keep
        complementary halves, sibling pairs cover the other ranges — so
        one grouped psum of the per-fragment partials yields the exact
        full-vector dot, each element counted once.  Results match the
        ladder (asserted in tests/test_allreduce.py)."""
        shape = x.shape
        acc = jnp.promote_types(x.dtype, jnp.float32)
        v = x.reshape(-1).astype(acc)
        orig = v.size
        padding = (-orig) % n
        if padding:
            v = jnp.concatenate([v, jnp.zeros((padding,), acc)])
        idx = jax.lax.axis_index(REPLICA_AXIS)
        logn = int(math.log2(n))
        frag = v
        for r in range(logn):
            dist = 1 << r
            half = frag.shape[0] // 2
            lo, hi = frag[:half], frag[half:]
            keep_lo = ((idx >> r) & 1) == 0
            mine = jnp.where(keep_lo, lo, hi)
            send = jnp.where(keep_lo, hi, lo)
            recv = jax.lax.ppermute(send, REPLICA_AXIS,
                                    [(i, i ^ dist) for i in range(n)])
            a = jnp.where(keep_lo, mine, recv)  # block-0's fragment
            b = jnp.where(keep_lo, recv, mine)  # block-1's fragment
            groups = [[g * 2 * dist + j for j in range(2 * dist)]
                      for g in range(n // (2 * dist))]
            dot, na, nb = jax.lax.psum(
                jnp.stack([jnp.sum(a * b), jnp.sum(a * a),
                           jnp.sum(b * b)]),
                REPLICA_AXIS, axis_index_groups=groups)
            ca = 1.0 - jnp.where(na > 0, dot / (2.0 * na), 0.0)
            cb = 1.0 - jnp.where(nb > 0, dot / (2.0 * nb), 0.0)
            frag = ca * a + cb * b
        for r in range(logn - 1, -1, -1):
            dist = 1 << r
            recv = jax.lax.ppermute(frag, REPLICA_AXIS,
                                    [(i, i ^ dist) for i in range(n)])
            keep_lo = ((idx >> r) & 1) == 0
            frag = jnp.where(keep_lo, jnp.concatenate([frag, recv]),
                             jnp.concatenate([recv, frag]))
        return frag[:orig].astype(x.dtype).reshape(shape)

    def _adasum(x):
        # Static (trace-time) dispatch: VHDD's ~2|v| wire beats the
        # ladder's log2(n)|v| once the vector amortizes its pad-to-n and
        # per-round scalar psum; at n=2 the two are the same wire cost
        # and the ladder is one collective per round instead of two.
        if n > 2 and x.size >= 2 * n:
            return _adasum_vhdd(x)
        return _adasum_ladder(x)

    def _pr_block(fn):
        # Per-replica [size, ...] layout: reduce this replica's squeezed
        # shard, emit one identical row per replica.
        def body(x):
            return fn(jnp.squeeze(x, axis=0))[None]
        return body

    def _fold_avg(fn):
        # AVERAGE's post-reduce divide folded INTO the compiled kernel
        # (one launch, not reduce + a separate eager _divide dispatch);
        # integer dtypes floor-divide exactly like _divide.  The mesh
        # extent n == the averaging denominator by construction (global
        # mesh: st.size; process-set sub-mesh: the set size).
        def body(x):
            out = fn(x)
            if jnp.issubdtype(out.dtype, jnp.inexact):
                return out / n
            return out // n
        return body

    extra = {}
    for key, fn in (("pmin", lambda x: jax.lax.pmin(x, REPLICA_AXIS)),
                    ("pmax", lambda x: jax.lax.pmax(x, REPLICA_AXIS)),
                    ("pprod", _prod_all)):
        extra[f"{key}_pr"] = sm(_pr_block(fn), P(REPLICA_AXIS),
                                P(REPLICA_AXIS), check_vma=False)
        extra[f"{key}_rep"] = sm(fn, P(), P(), check_vma=False)
        extra[f"{key}_out_rep"] = sm(
            lambda x, fn=fn: fn(jnp.squeeze(x, axis=0)),
            P(REPLICA_AXIS), P(), check_vma=False)
    if n & (n - 1) == 0:  # adasum needs a power-of-two axis
        extra["adasum_pr"] = sm(_pr_block(_adasum), P(REPLICA_AXIS),
                                P(REPLICA_AXIS), check_vma=False)
        extra["adasum_rep"] = sm(_adasum, P(), P(), check_vma=False)
        extra["adasum_out_rep"] = sm(
            lambda x: _adasum(jnp.squeeze(x, axis=0)),
            P(REPLICA_AXIS), P(), check_vma=False)

    _psum = lambda x: jax.lax.psum(x, REPLICA_AXIS)  # noqa: E731

    return {
        **extra,
        # Per-replica [size, ...] -> per-replica [size, ...] (each = sum).
        "psum_pr": sm(_psum, P(REPLICA_AXIS), P(REPLICA_AXIS)),
        # Replicated [...] -> replicated [...] (= x * size, honest
        # collective).
        "psum_rep": sm(_psum, P(), P()),
        # Per-replica [size, ...] -> replicated [...] (sum of shards).
        "psum_out_rep": sm(_psum_squeeze_block, P(REPLICA_AXIS), P(),
                           check_vma=False),
        # AVERAGE variants: the mean's divide folded into the compiled
        # program — no separate eager _divide launch after the
        # collective (the data-plane megakernel work, docs/tensor-fusion.md).
        "psum_pr_avg": sm(_fold_avg(_psum), P(REPLICA_AXIS),
                          P(REPLICA_AXIS)),
        "psum_rep_avg": sm(_fold_avg(_psum), P(), P()),
        "psum_out_rep_avg": sm(_fold_avg(_psum_squeeze_block),
                               P(REPLICA_AXIS), P(), check_vma=False),
        "rscatter_pr_avg": sm(_fold_avg(_rscatter_pr_block),
                              P(REPLICA_AXIS), P(REPLICA_AXIS),
                              check_vma=False),
        "rscatter_rep_avg": sm(_fold_avg(_rscatter_rep_block), P(),
                               P(REPLICA_AXIS), check_vma=False),
        # Replicated-input broadcast: the identity-with-execution-parity
        # psum(x)/n collapsed into one compiled program (inexact dtypes
        # only; integer replicated broadcasts stay the pure identity).
        "bcast_rep": sm(lambda x: jax.lax.psum(x, REPLICA_AXIS) / n,
                        P(), P()),
        # Per-replica [size, d0, ...] -> replicated [size*d0, ...].
        "gather_pr": sm(_gather_block, P(REPLICA_AXIS), P(),
                        check_vma=False),
        # Replicated [d0, ...] -> replicated [size*d0, ...].
        "gather_rep": sm(
            lambda x: jax.lax.all_gather(x, REPLICA_AXIS, axis=0,
                                         tiled=True),
            P(), P(), check_vma=False),
        # Per-replica [size, ...] + root -> replicated [...] = root's shard.
        "bcast_pr": jax.jit(jax.shard_map(
            _bcast_block, mesh=mesh, in_specs=(P(REPLICA_AXIS), P()),
            out_specs=P(), check_vma=False)),
        # Reducescatter: per-replica [n, d0, ...] -> per-replica
        # [n, d0/n, ...] (row r = rank r's chunk of the reduction).
        "rscatter_pr": sm(_rscatter_pr_block, P(REPLICA_AXIS),
                          P(REPLICA_AXIS), check_vma=False),
        # Replicated [d0, ...] -> per-replica [n, d0/n, ...].
        "rscatter_rep": sm(_rscatter_rep_block, P(), P(REPLICA_AXIS),
                           check_vma=False),
        # Alltoall: [n(sender), n(dest), M, ...] -> [n(recv), n(sender),
        # M, ...] (padded blocks; the host slices by the split matrix).
        "a2a_pr": sm(_a2a_block, P(REPLICA_AXIS), P(REPLICA_AXIS),
                     check_vma=False),
    }


# Compiled-kernel tables.  Previously unbounded lru_caches keyed on
# Device OBJECTS: a restarted backend mints fresh Devices that never
# compare equal to the dead ones, so the old entries became immortal,
# pinning dead meshes and their jitted kernels forever.  This bounded
# cache keeps the useful property (same-backend re-inits — every test —
# share one compilation because live Devices compare equal) while, on
# every miss, evicting entries whose Device objects no longer appear in
# ``jax.devices()``, plus insertion-order overflow eviction as a
# backstop.
_KERNEL_CACHE_CAPACITY = 16
_kernel_cache_lock = _lockorder.make_lock("collective._kernel_cache")
# table name -> {device-tuple key -> built kernels}
_kernel_caches: Dict[str, dict] = {
    "replica": {}, "subset": {}, "mp": {}}  # guarded_by: _kernel_cache_lock


def _cached_kernels(table: str, key: tuple, build):
    with _kernel_cache_lock:
        hit = _kernel_caches[table].get(key)
    if hit is not None:
        return hit
    # Miss: evict stale-device and overflow entries first; the build
    # itself runs OUTSIDE the lock (jit construction must never happen
    # under a runtime lock), and a concurrent builder's entry wins via
    # setdefault.
    try:
        live = set(jax.devices())
    except Exception:  # noqa: BLE001 — backend down; skip eviction
        live = None
    with _kernel_cache_lock:
        if live is not None:
            # Stale-device entries are dead in EVERY table (the backend
            # restarted) — sweep them all.
            for cache in _kernel_caches.values():
                for k in [k for k in cache if not set(k) <= live]:
                    del cache[k]
        # The overflow backstop applies only to the table receiving
        # this insert: another table's live at-capacity entries must
        # not lose compilations to an unrelated miss.
        target = _kernel_caches[table]
        while len(target) >= _KERNEL_CACHE_CAPACITY:
            del target[next(iter(target))]  # oldest insertion first
    built = build()
    with _kernel_cache_lock:
        return _kernel_caches[table].setdefault(key, built)


def _kernels(mesh_key):
    """Kernels over the replica mesh; ``mesh_key`` is the tuple of
    Device OBJECTS (not ids) so the replica set changing (tests re-init
    with device subsets) or the backend restarting rebuilds them."""
    return _cached_kernels(
        "replica", mesh_key,
        lambda: _build_kernels(_state.global_state().mesh))


def _mesh_kernels():
    st = _state.global_state()
    return _kernels(tuple(st.devices))


def _subset_kernels(devs: tuple):
    """Mesh + kernels over an arbitrary device subset, cached by the
    device tuple so process sets over identical subsets (or the same set
    re-registered across re-inits) share one compilation."""

    def build():
        mesh = jax.sharding.Mesh(np.asarray(devs), (REPLICA_AXIS,))
        return mesh, _build_kernels(mesh)

    return _cached_kernels("subset", devs, build)


# ---------------------------------------------------------------------------
# Multi-process eager path (reference: one MPI rank per process)
# ---------------------------------------------------------------------------
# Negotiation runs at process granularity and each process holds only its
# own contribution.  Collectives execute over a one-device-per-process mesh
# (the lowest-id local device of every process), mirroring the reference's
# one-GPU-per-rank binding; any extra local devices serve the static pjit
# path instead.

def _mp_mesh_and_kernels(mesh_key):
    # mesh_key is the tuple of local Device objects (see _kernels on why
    # object identity, not ids; bounded + stale-evicting like _kernels).
    def build():
        by_proc: Dict[int, Any] = {}
        for d in jax.devices():
            if d.process_index not in by_proc \
                    or d.id < by_proc[d.process_index].id:
                by_proc[d.process_index] = d
        devs = [by_proc[p] for p in sorted(by_proc)]
        mesh = jax.sharding.Mesh(np.asarray(devs), (REPLICA_AXIS,))
        return mesh, _build_kernels(mesh)

    return _cached_kernels("mp", mesh_key, build)


def _mp_kernels():
    st = _state.global_state()
    return _mp_mesh_and_kernels(tuple(st.devices))


def _mp_global(x: jax.Array, ps=None):
    """Local contribution → global ``[P, ...]`` array sharded over the
    process mesh (this process supplies shard ``process_index``; for a
    process set, the SET mesh with this process at its set-local slot)."""
    st = _state.global_state()
    if ps is None:
        mesh, _ = _mp_kernels()
        count = st.process_count
    else:
        mesh, _ = ps.mesh_and_kernels()
        count = ps.size()
    if isinstance(x, jax.Array) and not x.is_fully_addressable:
        # A previous collective's (replicated) output — or eager math on
        # one — fed straight back in: take this process's full local
        # copy so device_put gets an addressable array (users naturally
        # chain collectives, e.g. allreduce(f(broadcast(w)))).
        x = np.asarray(x.addressable_data(0))
    # The shard this process owns lives on its device in the process mesh.
    mine = [d for d in mesh.devices.flat
            if d.process_index == st.process_index][0]
    local = jax.device_put(jnp.asarray(x), mine)[None]
    gshape = (count,) + tuple(local.shape[1:])
    spec = [None] * (local.ndim)
    spec[0] = REPLICA_AXIS
    sharding = NamedSharding(mesh, P(*spec))
    return jax.make_array_from_single_device_arrays(gshape, sharding, [local])


def _divide(x, denom: int):
    """Post-reduce division for ``average=True``; integer dtypes use floor
    division like the reference's in-place integer divide
    (torch/tensor_util.h DivideTensorInPlace)."""
    if jnp.issubdtype(x.dtype, jnp.inexact):
        return x / denom
    return x // denom


# ---------------------------------------------------------------------------
# Megakernel launches (ops/megakernel.py): one donated pack→reduce→unpack
# executable per fusion group instead of the per-tensor eager choreography
# ---------------------------------------------------------------------------

def _group_digest_fn(group: List["_QueuedOp"], psid: int, quant=None):
    """Lazy fusion-plan digest of one response group — the PR 2 cycle
    digest (ops/cache.cycle_digest scheme) the compiled executable is
    recorded under; only evaluated on a cold compile.  The quantization
    spec is folded into the digest (ops/megakernel.plan_digest)."""
    def digest() -> str:
        entries = [_program.SignatureEntry(
            seq=0, op=o.op.name.lower(), name=o.name,
            dtype=wire.dtype_name(wire.dtype_of(o.contrib.dtype)),
            shape=tuple(o.contrib.shapes[0]),
            reduce_op=wire.reduce_op_name(o.red_op),
            process_set_id=psid) for o in group]
        return _megakernel.plan_digest(entries, quant)
    return digest


def _megakernel_eligible(group: List["_QueuedOp"]) -> bool:
    return (_megakernel.enabled()
            and group[0].red_op != ReduceOp.ADASUM)


def _tensor_wire_format(name: str, psid: int, red_op: ReduceOp, dtype,
                        shape) -> Optional["_compression.WireFormat"]:
    """The compression policy's wire format for ONE tensor, or None for
    full precision.  Only the psum family quantizes (SUM/AVERAGE — the
    gradient path); min/max/prod and Adasum always ride uncompressed."""
    if _OP_KERNEL.get(red_op) != "psum":
        return None
    numel = int(np.prod(shape, dtype=np.int64)) if shape else 1
    return _compression.policy_format_for(name, psid, dtype, numel)


def _partition_by_wire(group: List["_QueuedOp"], psid: int):
    """Split one coordinator fusion group by per-tensor wire format
    (the policy registry's selection surface: embeddings int8,
    layernorm/scalars uncompressed, ...).  Deterministic across ranks:
    keyed only on negotiated fields (name/dtype/shape/op) plus the
    policy, which the env-uniformity contract pins fleet-wide.
    Preserves first-appearance order."""
    buckets: Dict[Any, List["_QueuedOp"]] = {}
    order: List[Any] = []
    for o in group:
        fmt = _tensor_wire_format(o.name, psid, o.red_op,
                                  o.contrib.dtype, o.contrib.shapes[0])
        if fmt not in buckets:
            buckets[fmt] = []
            order.append(fmt)
        buckets[fmt].append(o)
    return [(fmt, buckets[fmt]) for fmt in order]


def _tl_group_start(tl, group: List["_QueuedOp"]) -> None:
    for o in group:
        _tl_start(tl, o, "ALLREDUCE")
        tl.activity_start(o.name, "FUSED_KERNEL")


def _tl_group_end(tl, group: List["_QueuedOp"], hier) -> None:
    for o in group:
        tl.activity_end(o.name)
        if hier is not None:
            tl.instant(o.name, "DCN_ALLREDUCE", args={
                "slices": hier.topo.n_slices, "ici": hier.topo.ici_size,
                "wire_dtype": hier.wire_dtype or str(o.contrib.dtype)})
        tl.end(o.name, dtype=str(o.contrib.dtype))


def _quant_group_key(variant: str, psid: int, names: Sequence[str],
                     fmt) -> tuple:
    """The ONE tick/noise-stream key scheme for every executor path
    (fused sp/mp and the eager reference fallback) — the bitwise
    fused≡eager contract depends on all of them counting steps under
    the same key.  Flat tuple of scalars only: it round-trips through
    JSON in compression_state() (a nested tuple would come back as an
    unhashable list)."""
    return (variant, psid, fmt.name if fmt is not None else "") \
        + tuple(names)


def _launch_group_megakernel(group: List["_QueuedOp"], layout: bool,
                             denom: int, ps, mesh, tl, hm,
                             fmt=None) -> bool:
    """Single-process fused-group launch: ONE jitted donated executable
    packs the group, reduces once (hierarchically on multi-slice
    meshes, quantized when the compression policy says so), folds the
    AVERAGE divide and unpacks — exactly one XLA dispatch per fusion
    group.  Returns False to fall back to the per-tensor eager path
    (unbuildable spec)."""
    o0 = group[0]
    op_kernel = _OP_KERNEL[o0.red_op]
    mesh_key = tuple(mesh.devices.flat)
    variant = "sp_pr" if layout else "sp_rep"
    psid = 0 if ps is None else ps.process_set_id
    spec = _megakernel.GroupSpec(
        mesh_key=mesh_key, variant=variant,
        op=op_kernel, average=o0.red_op == ReduceOp.AVERAGE, denom=denom,
        dtype=jnp.dtype(o0.contrib.dtype).name,
        shapes=tuple(tuple(o.contrib.shapes[0]) for o in group),
        donate=tuple(bool(o.contrib.owned) for o in group),
        hier=_megakernel.hierarchy_for(mesh_key, op_kernel,
                                       o0.contrib.dtype, group_fmt=fmt),
        quant=fmt)
    values = [o.contrib.value for o in group]
    donate_mask = list(spec.donate)
    res_keys: List[tuple] = []
    if _megakernel._needs_quant_build(spec):
        use_ef = (fmt is not None and fmt.kind == "quant"
                  and fmt.error_feedback and spec.hier is None)
        if use_ef:
            # Error-feedback residual: executor-owned flat group buffer
            # fed back in (and donated) each step, replaced by the
            # kernel's residual output after the launch.  take_
            # semantics: once donated, the store must not reference it.
            res_keys = [("g", psid) + tuple(o.name for o in group)]
            T = sum(int(np.prod(s, dtype=np.int64)) if s else 1
                    for s in spec.shapes)
            res_shape = (len(mesh_key), T) if layout else (T,)
            stored = _megakernel.take_residual(
                res_keys[0], o0.contrib.dtype, [res_shape])
            values.append(stored if stored is not None
                          else np.zeros(res_shape,
                                        jnp.dtype(o0.contrib.dtype)))
            donate_mask.append(True)
        tick = _megakernel.next_tick(_quant_group_key(
            variant, psid, [o.name for o in group], fmt))
        values.append(np.asarray(
            [_compression.quant_seed(), tick], np.uint32))
        donate_mask.append(False)
    if tl: _tl_group_start(tl, group)
    try:
        outs = _megakernel.launch(
            spec, mesh, values,
            digest_fn=_group_digest_fn(group, psid, fmt),
            donate_mask=donate_mask)
    except Exception as e:  # noqa: BLE001 — unbuildable spec
        import traceback

        traceback.print_exc(file=sys.stderr)
        if tl:
            for o in group:
                tl.activity_end(o.name)
                tl.end(o.name, dtype=str(o.contrib.dtype))
        consumed = any(d and isinstance(v, jax.Array) and v.is_deleted()
                       for v, d in zip(values, donate_mask))
        if res_keys and consumed:
            # The stored residual buffers were donated into a launch
            # that died: they reference deleted memory — restart them
            # from zero rather than poison the next launch.
            _megakernel.drop_residuals(res_keys)
        if not consumed:
            return False  # inputs intact: per-tensor eager fallback
        # A RUNTIME failure after XLA already consumed the donated
        # inputs (trace/compile errors leave them intact): an eager
        # retry would read deleted buffers — fail the group loudly at
        # synchronize instead (mirrors _launch_mp_megakernel).
        err = HorovodError(
            f"megakernel launch failed after its inputs were donated "
            f"({type(e).__name__}: {e}); the group cannot fall back to "
            f"the per-tensor path.")
        for o in group:
            hm._get(o.handle).result = err
        return True
    if res_keys:
        _megakernel.store_residuals(res_keys, [outs[-1]])
        outs = outs[:len(group)]
    for o, out in zip(group, outs):
        # Donated (or simply consumed) input: nothing may read it after
        # dispatch — drop the reference so use-after-donate is
        # impossible by construction (tests/test_megakernel.py probes
        # this with weakrefs).
        o.contrib.value = None
        hm._get(o.handle).result = out
    if tl: _tl_group_end(tl, group, spec.hier)
    return True


def _eager_quantized_group(group: List["_QueuedOp"], layout: bool,
                           denom: int, ps, mesh, tl, hm, fmt) -> None:
    """Per-tensor-executor fallback for a quantized group
    (HVD_TPU_MEGAKERNEL=0, or an unbuildable fused spec): the
    eager-quantized REFERENCE math (ops/compression.reference_allreduce
    — the function the megakernel is tested bitwise against), driven by
    the same residual store and tick counter as the fused path.  Always
    the flat two-phase formulation — the hierarchical per-leg pipeline
    exists only inside the fused executable."""
    n = len(tuple(mesh.devices.flat))
    psid = 0 if ps is None else ps.process_set_id
    variant = "sp_pr" if layout else "sp_rep"
    dtype = jnp.dtype(group[0].contrib.dtype)
    use_ef = fmt.error_feedback
    res_key = ("g", psid) + tuple(o.name for o in group)
    if tl: _tl_group_start(tl, group)
    if layout:
        rows = jnp.concatenate(
            [jnp.asarray(o.contrib.value).reshape(n, -1) for o in group],
            axis=1)
    else:
        flat = jnp.concatenate(
            [jnp.ravel(jnp.asarray(o.contrib.value)) for o in group])
        rows = jnp.broadcast_to(flat[None], (n, flat.shape[0]))
    T = rows.shape[1]
    residuals = None
    if use_ef:
        res_shape = (n, T) if layout else (T,)
        stored = _megakernel.take_residual(res_key, dtype, [res_shape])
        residuals = jnp.asarray(
            stored if stored is not None
            else np.zeros(res_shape, dtype))
        if not layout:
            residuals = jnp.broadcast_to(residuals[None], (n, T))
    tick = _megakernel.next_tick(_quant_group_key(
        variant, psid, [o.name for o in group], fmt))
    red, r_new = _compression.reference_allreduce(
        rows, fmt, tick, residuals=residuals, shared_noise=not layout)
    if r_new is not None:
        _megakernel.store_residuals(
            [res_key], [r_new if layout else r_new[0]])
    offs = 0
    for o in group:
        cnt = int(np.prod(o.contrib.shapes[0], dtype=np.int64)) \
            if o.contrib.shapes[0] else 1
        shape = tuple(o.contrib.shapes[0])
        piece = red[offs:offs + cnt].reshape(shape)
        if o.red_op == ReduceOp.AVERAGE:
            piece = _divide(piece, denom)
        if layout:
            piece = jnp.broadcast_to(piece[None], (n,) + shape)
        offs += cnt
        o.contrib.value = None
        hm._get(o.handle).result = piece
    if tl: _tl_group_end(tl, group, None)


def _launch_mp_megakernel(resp: Response, ops: List["_QueuedOp"], ps,
                          mesh, denom: int, tl, hm) -> bool:
    """Multi-process fused launch of one coordinator response,
    sub-partitioned by the compression policy's per-tensor wire format
    (the partition is a pure function of negotiated fields + the
    rank-uniform policy, so every process splits the response
    identically).  A bucket whose fused spec is unbuildable falls back
    to the per-bucket eager path — deterministically on every rank.
    Returns True once the whole response is handled."""
    by_name = {o.name: o for o in ops}
    dtype = (jnp.dtype(ops[0].contrib.dtype) if ops
             else jnp.dtype(wire.np_dtype_of(resp.tensor_type)))
    red_op = ops[0].red_op if ops else resp.reduce_op
    psid = 0 if ps is None else ps.process_set_id
    shapes = []
    for pos, name in enumerate(resp.tensor_names):
        o = by_name.get(name)
        if o is not None:
            shapes.append(tuple(o.contrib.shapes[0]))
        else:
            shapes.append(tuple(resp.tensor_shapes[pos])
                          if pos < len(resp.tensor_shapes)
                          else tuple(resp.tensor_shapes[0]))
    buckets: Dict[Any, List[int]] = {}
    order: List[Any] = []
    for pos, name in enumerate(resp.tensor_names):
        fmt = _tensor_wire_format(name, psid, red_op, dtype, shapes[pos])
        if fmt not in buckets:
            buckets[fmt] = []
            order.append(fmt)
        buckets[fmt].append(pos)
    for fmt in order:
        idxs = buckets[fmt]
        names_sub = [resp.tensor_names[i] for i in idxs]
        shapes_sub = [shapes[i] for i in idxs]
        if not _launch_mp_megakernel_sub(
                names_sub, shapes_sub, by_name, ps, mesh, denom, tl, hm,
                fmt, red_op, dtype, psid):
            _eager_mp_subset(names_sub, shapes_sub, by_name, ps, denom,
                             red_op, dtype, tl, hm)
    return True


def _launch_mp_megakernel_sub(names: List[str], shapes: List[tuple],
                              by_name: Dict[str, "_QueuedOp"], ps, mesh,
                              denom: int, tl, hm, fmt, red_op, dtype,
                              psid: int) -> bool:
    """One wire-format bucket of a multi-process response: one jitted
    local pack (donating executor-owned contributions) → one donated
    reduce+divide+unpack executable over the process mesh — quantized
    in-kernel when ``fmt`` says so.  Handles the joined-rank case
    transparently: ``names`` may include tensors this rank never
    submitted — they contribute zeros and their outputs are discarded,
    exactly like the peers' buffer."""
    values = []
    donate = []
    for name, shp in zip(names, shapes):
        o = by_name.get(name)
        if o is not None:
            values.append(o.contrib.value)
            donate.append(bool(o.contrib.owned))
        else:
            values.append(jnp.zeros(shp, dtype))  # joined: zero slot
            donate.append(True)
    avg = red_op == ReduceOp.AVERAGE
    op_kernel = _OP_KERNEL[red_op]
    mesh_key = tuple(mesh.devices.flat)
    spec = _megakernel.GroupSpec(
        mesh_key=mesh_key, variant="mp", op=op_kernel, average=avg,
        denom=denom, dtype=dtype.name, shapes=tuple(shapes),
        donate=(True,),  # the packed buffer is always executor-owned
        hier=_megakernel.hierarchy_for(mesh_key, op_kernel, dtype,
                                       group_fmt=fmt),
        quant=fmt)
    group = [by_name[n] for n in names if n in by_name]
    if tl: _tl_group_start(tl, group)
    consumed = False
    res_key = None
    try:
        pack = _megakernel.packer(tuple(shapes), dtype.name,
                                  tuple(donate), mesh_key)
        flat = pack(*values)
        # Fallback is only off the table if the pack REALLY donated a
        # contribution the eager path would need (mirrors the
        # is_deleted probe of _launch_group_megakernel; all-user-held
        # groups donate nothing and stay recoverable).
        consumed = any(d and isinstance(v, jax.Array) and v.is_deleted()
                       for v, d in zip(values, donate))
        buf = _mp_global(flat, ps)
        launch_values = [buf]
        donate_mask = [True]
        if _megakernel._needs_quant_build(spec):
            use_ef = (fmt is not None and fmt.kind == "quant"
                      and fmt.error_feedback and spec.hier is None)
            if use_ef:
                T = sum(int(np.prod(s, dtype=np.int64)) if s else 1
                        for s in shapes)
                Pn = len(mesh_key)
                res_key = ("g", psid) + tuple(names)
                # The live residual is the previous launch's [P, T]
                # global OUTPUT, reused on-device (no per-step
                # device→host→device round trip); a checkpoint-restored
                # local [T] numpy shard re-uploads once.
                stored = _megakernel.take_residual(
                    res_key, dtype, [(Pn, T), (T,)])
                if isinstance(stored, jax.Array) \
                        and stored.shape == (Pn, T):
                    res_buf = stored
                elif stored is not None:
                    res_buf = _mp_global(jnp.asarray(stored), ps)
                else:
                    res_buf = _mp_global(jnp.zeros((T,), dtype), ps)
                launch_values.append(res_buf)
                donate_mask.append(True)
            tick = _megakernel.next_tick(
                _quant_group_key("mp", psid, names, fmt))
            launch_values.append(np.asarray(
                [_compression.quant_seed(), tick], np.uint32))
            donate_mask.append(False)
        outs = _megakernel.launch(
            spec, mesh, launch_values,
            digest_fn=_group_digest_fn(group, psid, fmt)
            if group else None,
            donate_mask=donate_mask)
    except Exception as e:  # noqa: BLE001 — unbuildable spec
        import traceback

        traceback.print_exc(file=sys.stderr)
        if tl:
            for o in group:
                tl.activity_end(o.name)
                tl.end(o.name, dtype=str(o.contrib.dtype))
        if res_key is not None:
            _megakernel.drop_residuals([res_key])
        if not consumed:
            return False  # inputs intact: per-tensor eager fallback
        # The pack already donated the executor-owned inputs; an eager
        # retry would read deleted buffers.  Fail the group loudly at
        # synchronize instead of silently wedging it.
        err = HorovodError(
            f"megakernel launch failed after the fusion buffer was "
            f"packed ({type(e).__name__}: {e}); the group cannot fall "
            f"back to the per-tensor path.")
        for o in group:
            hm._get(o.handle).result = err
        return True
    if res_key is not None:
        # Store the residual output — a P(hvd)-sharded [P, T] global —
        # AS the device array: the next launch donates it straight back
        # in (compression_state() exports the addressable shard when a
        # snapshot is taken).
        _megakernel.store_residuals([res_key], [outs[-1]])
        outs = outs[:-1]
    for name, out in zip(names, outs):
        o = by_name.get(name)
        if o is not None:
            o.contrib.value = None  # consumed: see _launch_group_megakernel
            hm._get(o.handle).result = out
    if tl: _tl_group_end(tl, group, spec.hier)
    return True


def _eager_mp_subset(names: List[str], shapes: List[tuple],
                     by_name: Dict[str, "_QueuedOp"], ps, denom: int,
                     red_op, dtype, tl, hm) -> None:
    """Eager (uncompressed) execution of one wire-format bucket of a
    multi-process response — the deterministic per-bucket fallback when
    its fused spec is unbuildable.  A quantized bucket landing here
    loses its compression for the step, never its correctness (every
    rank takes the same branch, so the SPMD programs still match)."""
    _, ks = (_mp_kernels() if ps is None else ps.mesh_and_kernels())
    group = [by_name[n] for n in names if n in by_name]
    for o in group:
        if tl: _tl_start(tl, o, "ALLREDUCE")
        if tl: tl.activity_start(o.name, "MEMCPY_IN_FUSION_BUFFER")

    def numel(s):
        return int(np.prod(s, dtype=np.int64)) if s else 1

    parts = [jnp.ravel(by_name[n].contrib.value) if n in by_name
             else jnp.zeros((numel(s),), dtype)
             for n, s in zip(names, shapes)]
    buf = jnp.concatenate(parts) if len(parts) > 1 else parts[0]
    for o in group:
        if tl: tl.activity_end(o.name)
        if tl: tl.activity_start(o.name, "XLA_ALLREDUCE")
    red = ks[_OP_KERNEL[red_op] + "_out_rep"](_mp_global(buf, ps))
    offs = 0
    for n, s in zip(names, shapes):
        o = by_name.get(n)
        cnt = numel(s)
        if o is not None:
            if tl: tl.activity_end(o.name)
            if tl: tl.activity_start(o.name, "MEMCPY_OUT_FUSION_BUFFER")
            piece = red[offs:offs + cnt].reshape(s)
            if o.red_op == ReduceOp.AVERAGE:
                piece = _divide(piece, denom)
            if tl: tl.activity_end(o.name)
            if tl: tl.end(o.name, dtype=str(o.contrib.dtype))
            hm._get(o.handle).result = piece
        offs += cnt


# ---------------------------------------------------------------------------
# Async op queue with Tensor Fusion execution
# ---------------------------------------------------------------------------

@dataclass
class _QueuedOp:
    name: str
    op: RequestType
    contrib: _Contribution
    red_op: ReduceOp
    root_rank: int
    handle: int
    nbytes: int
    ps: Any = None  # ProcessSet for non-global ops
    # This rank's wire Request (multi-process; rank 0's in
    # single-process), retained so the response cache can store the
    # exact negotiated request at insertion time (ops/cache.py).
    request: Any = None
    # True when negotiation was served from the response cache — rides
    # the timeline EXECUTE span so cache wins are visible per tensor.
    cache_hit: bool = False
    # time.monotonic at enqueue, the one clock read this op spends
    # before execution: the telemetry negotiate-latency stamp AND the
    # start of the hvd-trace negotiate.wait span (the clock the offset
    # estimator aligns); 0.0 = telemetry and tracing both off.
    t_submit: float = 0.0


@_races.race_checked
class _OpQueue:
    """Pending async collectives awaiting (possibly fused) execution.

    Plays the role of the reference's message_queue + fusion loop
    (operations.cc:1226-1374): async calls enqueue; ``drain`` polls the
    coordinator for (fused) responses and launches the XLA collectives.
    """

    def __init__(self) -> None:
        self._lock = _lockorder.make_lock("OpQueue._lock")
        self._ops: Dict[str, _QueuedOp] = {}  # guarded_by: _lock

    def put(self, op: _QueuedOp) -> None:
        with self._lock:
            if op.name in self._ops:
                raise ValueError(
                    f"A collective named {op.name!r} is already pending; "
                    f"tensor names must be unique among in-flight ops "
                    f"(reference keys its TensorTable the same way, "
                    f"operations.cc:1568-1572).")
            self._ops[op.name] = op

    def take(self, names: Sequence[str]) -> List[_QueuedOp]:
        with self._lock:
            out = []
            for n in names:
                op = self._ops.pop(n, None)
                if op is not None:
                    out.append(op)
            return out

    def pending_meta(self) -> Dict[str, int]:
        with self._lock:
            return {n: o.nbytes for n, o in self._ops.items()}

    def peek_ps(self, name: str):
        """The ProcessSet of a pending op (None = global / unknown) —
        lets synchronize route a withdrawal to the right coordinator."""
        with self._lock:
            op = self._ops.get(name)
            return None if op is None else op.ps


_queue = _OpQueue()
_drain_lock = _lockorder.make_lock("collective._drain_lock")

# Background tick cadence — same 5 ms as the reference's coordinator loop
# (operations.cc:1221).  The thread only serves *async* eager ops; sync ops
# and the static path never wait on it.
TICK_SECONDS = 0.005


def _background_loop(stop_event: threading.Event) -> None:  # thread: drain
    """≙ BackgroundThreadLoop (operations.cc:1167-1475): drain the async op
    queue on a fixed tick so ``*_async`` collectives make progress even if
    the caller never polls.  The period is runtime-adjustable
    (HOROVOD_CYCLE_TIME / the autotuner)."""
    _athreads.set_role("drain")
    import traceback

    st = _state.global_state()
    while not stop_event.wait(st.tick_seconds or TICK_SECONDS):
        try:
            # hvd-chaos coord.tick_delay: a starved/descheduled drain
            # thread — the runtime must tolerate arbitrary tick jitter
            # (stall warnings may fire; results must not change).
            if _chaos.active():
                _chaos.sleep_site("coord.tick_delay")
            _drain()
        except Exception:
            # Validation errors never propagate here (they are stored on
            # handles); anything that does is a runtime bug — report it
            # rather than silently dropping queued ops, but keep ticking.
            # The flight ring dumps too: the drain thread IS the control
            # plane, and the events before the exception are the
            # diagnosis.
            _telemetry.exception_event("drain", traceback.format_exc())
            traceback.print_exc(file=sys.stderr)


def _submit_requests(name: str, op: RequestType, c: _Contribution,
                     root_rank: int = -1,
                     red_op: ReduceOp = ReduceOp.SUM, ps=None,
                     splits: Tuple[int, ...] = (),
                     queued_op: Optional[_QueuedOp] = None) -> bool:
    """Submit the negotiation request(s) for one collective; returns
    True when negotiation was served from the response cache (the
    steady-state fast path, ops/cache.py)."""
    st = _state.global_state()
    psid = 0 if ps is None else ps.process_set_id
    if st.timeline is not None:
        st.timeline.negotiate_start(name, op.name)
    if st.multiprocess:
        # One request per process, carrying only THIS process's metadata —
        # cross-rank validation happens on real information at the rank-0
        # coordinator (≙ the MPI_Gatherv of MPIRequests,
        # operations.cc:1240-1288).  Set requests carry SET-LOCAL ranks.
        rank = st.process_index if ps is None else ps.rank()
        req = Request(
            request_rank=rank, request_type=op,
            tensor_type=wire.dtype_of(c.dtype), tensor_name=name,
            root_rank=root_rank, device=c.devices[0],
            tensor_shape=c.shapes[0], reduce_op=red_op,
            process_set_id=psid, splits=splits)
        if queued_op is not None:
            # Set BEFORE the send: once the request is on the wire a
            # response may arrive any time, and the cache insertion
            # reads it from the queued op.
            queued_op.request = req
        return bool(st.transport.submit(req))
    coord = st.coordinator if ps is None else ps.coordinator
    hit_any = False
    for r in range(st.size if ps is None else ps.size()):
        req = Request(
            request_rank=r, request_type=op,
            tensor_type=wire.dtype_of(c.dtype), tensor_name=name,
            root_rank=root_rank, device=c.devices[r],
            tensor_shape=c.shapes[r], reduce_op=red_op,
            process_set_id=psid, splits=splits)
        if queued_op is not None and r == 0:
            queued_op.request = req
        _, hit = coord.submit_ex(req)
        hit_any = hit_any or hit
    return hit_any


def _tl_start(tl, o: _QueuedOp, op_name: str) -> None:
    """Open the tensor's top-level EXECUTE-phase span, tagged with
    whether its negotiation was served from the response cache (the
    NEGOTIATE span carries phase=NEGOTIATE symmetrically, so cache wins
    are visible per tensor in the Chrome trace)."""
    tl.start(o.name, op_name,
             args={"phase": "EXECUTE",
                   "cache": "hit" if o.cache_hit else "miss"})


_DATA_RESPONSES = (ResponseType.ALLREDUCE, ResponseType.ALLGATHER,
                   ResponseType.BROADCAST, ResponseType.REDUCESCATTER,
                   ResponseType.ALLTOALL)

# hvd-trace regions (trace/__init__.py): built once, like the _M_*
# handles.  ``execute/<op>`` is timed: collective.execute_seconds reads
# the region's own pair of clock reads.
_R_EXECUTE = _trace.RegionFamily("execute/", "dispatch", timed=True)
_R_TICK = _trace.region("negotiate.tick", "negotiate")


def _close_tick(tick, resps) -> None:
    """End of one coordinator drain tick inside its ``negotiate.tick``
    region: a tick that produced responses advances the fleet-wide
    cycle id BEFORE the broadcast (the frame's trace trailer and every
    rank's execution spans then share it) and keeps its span; an empty
    tick — 200 a second on the background thread — keeps nothing."""
    if resps and _trace.enabled():
        _trace.next_cycle()
        tick.note(responses=len(resps))
    else:
        tick.cancel()


def _execute_response(resp: Response, ops: List[_QueuedOp]) -> None:
    """Telemetry shell around :func:`_execute_response_inner`: the
    ``execute/<op>`` region's one pair of clock reads per response
    feeds the span, the negotiate- and execute-latency histograms,
    payload bytes and fusion-group width;
    ERROR and dead-peer SHUTDOWN responses additionally dump the flight
    ring — the forensic record of the 2000 control-plane events that
    led here."""
    tracing = _trace.enabled()
    if not _telemetry.enabled() and not tracing:
        return _execute_response_inner(resp, ops)
    is_data = resp.response_type in _DATA_RESPONSES
    # hvd-trace: the dispatch span — the response execution (pack +
    # launch + unpack); the launch span it contains
    # (ops/megakernel.launch) lets the analyzer carve it into pack /
    # collective / dcn / unpack legs.  ERROR responses trace too (the
    # error path is real work and the control-plane-only tests ride
    # it); the completed counter below stays data-only.
    with _R_EXECUTE[resp.response_type.name.lower()](
            tensors=len(resp.tensor_names),
            first=resp.tensor_names[0] if resp.tensor_names else "") as r:
        if not (ops and (is_data
                         or resp.response_type == ResponseType.ERROR)):
            r.cancel()
        t0 = r.t0
        if _telemetry.enabled():
            for o in ops:
                if o.t_submit:
                    _M_NEGOTIATE_S.observe(t0 - o.t_submit)
                _M_PAYLOAD_B.observe(o.nbytes)
            if is_data:
                _M_GROUP_WIDTH.observe(len(resp.tensor_names))
            elif resp.response_type == ResponseType.ERROR:
                _M_ERRORS.inc(max(len(ops), 1))
                _telemetry.error_event(resp.error_message or "")
            elif resp.response_type == ResponseType.SHUTDOWN and \
                    wire.DEAD_PEER_MARKER in (resp.error_message or ""):
                # Worker-side dead-peer poison (the controller side dumps
                # in _handle_lost_ranks before broadcasting this
                # diagnosis).
                _telemetry.dead_peer_event(resp.error_message or "")
        if tracing and ops and (is_data or resp.response_type
                                == ResponseType.ERROR):
            # The negotiate.wait span — this rank's local submit up to
            # execution; it starts in the past, so it stays a plain
            # span.  Every participating rank's wait span for one
            # collective CONTAINS the shared window [last submit,
            # broadcast], so same-(step, cycle) spans are guaranteed to
            # overlap across ranks once clocks are aligned — the fleet
            # -trace acceptance property.
            t_neg = min((o.t_submit for o in ops if o.t_submit > 0.0),
                        default=0.0)
            if t_neg:
                _trace.span("negotiate.wait", "negotiate", t_neg, t0,
                            args={"tensors": len(resp.tensor_names)})
        out = _execute_response_inner(resp, ops)
    # Counted AFTER a successful data launch only: an ERROR/SHUTDOWN
    # response (or an exception from the executor) must not inflate the
    # success counter — "failed = submitted - completed" has to read
    # true during a failure storm.
    if ops and is_data and _telemetry.enabled():
        _M_COMPLETED.inc(len(ops))
        _M_EXECUTE_S.observe(r.seconds)
    return out


def _execute_response_inner(resp: Response, ops: List[_QueuedOp]) -> None:
    """Launch the XLA collective(s) for one coordinator response.

    A fused ALLREDUCE response concatenates its tensors into one flat
    buffer (MEMCPY_IN_FUSION_BUFFER), reduces once, and splits results back
    (MEMCPY_OUT_FUSION_BUFFER) — the reference's Tensor Fusion
    (operations.cc:941-1034) expressed as XLA ops so the compiler can fuse
    the copies into the collective.
    """
    st = _state.global_state()
    tl = st.timeline
    hm = st.handle_manager

    if resp.response_type == ResponseType.CACHE_FLUSH:
        return  # response-cache epoch marker; handled by observe_response

    if resp.response_type == ResponseType.RETUNE:
        # hvd-tune knob marker: every rank applies the carried knob
        # values HERE — the same response-stream position fleet-wide —
        # so env knobs, compiled-kernel caches and cache replicas flip
        # at one cycle boundary (tuning/actuation.py).
        from ..tuning import actuation as _actuation

        _actuation.apply_marker(resp, st)
        return

    if resp.response_type == ResponseType.ERROR:
        err = HorovodError(resp.error_message)
        for o in ops:
            hm._get(o.handle).result = err  # surfaced at synchronize/poll
        return

    if resp.response_type == ResponseType.JOIN:
        # Release from hvd.join(): every rank joined; tensor_sizes
        # carries the last joining rank (join()'s return value).
        st.join_result = resp.tensor_sizes[0] if resp.tensor_sizes else -1
        return

    if resp.response_type == ResponseType.SHUTDOWN:
        # A rank initiated shutdown (or died): flush everything pending
        # with the shut-down error — carrying the initiator's diagnosis
        # when present — and refuse new work (operations.cc:1377-1403).
        # A diagnosis naming a dead process means that process can never
        # reach jax.distributed's exit barrier — every survivor (not just
        # the controller) must skip it or block 300 s and abort.  Clean
        # cooperative shutdowns carry no marker and keep the barrier.
        if wire.DEAD_PEER_MARKER in (resp.error_message or ""):
            from ..core.cluster import disarm_distributed_shutdown

            disarm_distributed_shutdown()
        st.peer_shutdown = True
        _poison_pending(resp.error_message or SHUT_DOWN_ERROR_MESSAGE)
        return

    if st.multiprocess:
        _execute_response_mp(resp, ops)
        return

    # Process-set responses execute over the set's sub-mesh with the
    # set's member count as the averaging denominator.
    ps = _state.get_process_set(resp.process_set_id) \
        if resp.process_set_id else None
    denom = st.size if ps is None else ps.size()

    if resp.response_type == ResponseType.ALLREDUCE:
        ks = _mesh_kernels() if ps is None else ps.mesh_and_kernels()[1]
        mesh = st.mesh if ps is None else ps.mesh_and_kernels()[0]
        # Sub-group by layout: per-replica vs replicated inputs reduce with
        # different shardings and cannot share one flat buffer.  The group
        # is homogeneous in red_op (the coordinator fuses like-op only).
        psid = 0 if ps is None else ps.process_set_id
        for layout in (True, False):
            lgroup = [o for o in ops if o.contrib.per_replica == layout]
            if not lgroup:
                continue
            # Sub-partition by the compression policy's per-tensor wire
            # format (embeddings int8, layernorm/scalars uncompressed,
            # ...): tensors with different codecs cannot share one
            # fused executable.  With the default policy (none) this is
            # a single bucket — the pre-quantization behavior.
            for fmt, group in _partition_by_wire(lgroup, psid):
                # Megakernel path (default): one donated
                # pack→reduce→unpack executable per fusion group — a
                # single XLA dispatch, with the AVERAGE divide (and the
                # quantize/dequantize pipeline) folded in and a
                # hierarchical ICI×DCN reduction on multi-slice meshes
                # (ops/megakernel.py).
                if _megakernel_eligible(group) \
                        and _launch_group_megakernel(
                            group, layout, denom, ps, mesh, tl, hm, fmt):
                    continue
                if fmt is not None and fmt.kind == "quant":
                    # Eager fallback keeps the quantized semantics via
                    # the reference math (same residuals, same ticks).
                    _eager_quantized_group(group, layout, denom, ps,
                                           mesh, tl, hm, fmt)
                    continue
                # Eager fallback (HVD_TPU_MEGAKERNEL=0): the per-tensor
                # choreography — the reference tests/test_megakernel.py
                # compares the fused path against.
                avg = group[0].red_op == ReduceOp.AVERAGE
                kernel = ks[_OP_KERNEL[group[0].red_op]
                            + ("_pr" if layout else "_rep")]
                wire_dt = jnp.dtype(fmt.wire_dtype) if fmt is not None \
                    else None
                if len(group) == 1 and fmt is None:
                    o = group[0]
                    if tl: _tl_start(tl, o, "ALLREDUCE")
                    if tl: tl.activity_start(o.name, "XLA_ALLREDUCE")
                    if avg:
                        # Single-tensor AVERAGE: divide folded into the
                        # compiled kernel, not a separate eager dispatch.
                        out = ks["psum_pr_avg" if layout
                                 else "psum_rep_avg"](o.contrib.value)
                    else:
                        out = kernel(o.contrib.value)
                    if tl: tl.activity_end(o.name)
                    if tl: tl.end(o.name, dtype=str(o.contrib.dtype))
                    hm._get(o.handle).result = out
                    continue
                # Fused path (also the cast-wire path: compress the
                # flat buffer, reduce in the wire dtype, decompress
                # BEFORE the divide — the compression.py order).
                for o in group:
                    if tl: _tl_start(tl, o, "ALLREDUCE")
                    if tl: tl.activity_start(o.name,
                                             "MEMCPY_IN_FUSION_BUFFER")
                if layout:
                    # per-replica: flatten payload per replica, concat
                    # axis 1.
                    parts = [o.contrib.value.reshape(st.size, -1)
                             for o in group]
                    buf = jnp.concatenate(parts, axis=1)
                else:
                    buf = jnp.concatenate(
                        [jnp.ravel(o.contrib.value) for o in group])
                for o in group:
                    if tl: tl.activity_end(o.name)
                    if tl: tl.activity_start(o.name, "XLA_ALLREDUCE")
                if wire_dt is not None:
                    red = kernel(buf.astype(wire_dt)).astype(buf.dtype)
                else:
                    red = kernel(buf)
                offs = 0
                for o in group:
                    n = int(np.prod(o.contrib.shapes[0],
                                    dtype=np.int64)) if \
                        o.contrib.shapes[0] else 1
                    if tl: tl.activity_end(o.name)
                    if tl: tl.activity_start(o.name,
                                             "MEMCPY_OUT_FUSION_BUFFER")
                    if layout:
                        piece = red[:, offs:offs + n].reshape(
                            (st.size,) + tuple(o.contrib.shapes[0]))
                    else:
                        piece = red[offs:offs + n].reshape(
                            o.contrib.shapes[0])
                    offs += n
                    if o.red_op == ReduceOp.AVERAGE:
                        piece = _divide(piece, denom)
                    if tl: tl.activity_end(o.name)
                    if tl: tl.end(o.name, dtype=str(o.contrib.dtype))
                    hm._get(o.handle).result = piece
        return

    if resp.response_type == ResponseType.ALLTOALL:
        ks = _mesh_kernels() if ps is None else ps.mesh_and_kernels()[1]
        n = denom
        matrix = np.asarray(resp.tensor_sizes,
                            dtype=np.int64).reshape(n, n)
        M = int(matrix.max()) if matrix.size else 0
        # Pad-to-max staging runs ON DEVICE as one vectorized gather
        # (round-4 verdict: the previous host double loop built an
        # O(n²·M) numpy matrix with per-element copies).  The index
        # plan is O(n²·M) int32 built with numpy broadcasting — the
        # payload itself never round-trips through the host.
        starts = np.zeros((n, n), np.int64)
        if matrix.size:
            starts[:, 1:] = np.cumsum(matrix, axis=1)[:, :-1]
        Mp = max(M, 1)
        m_idx = np.arange(Mp)
        row_last = np.maximum(matrix.sum(axis=1), 1)[:, None, None] - 1
        gather_idx = jnp.asarray(np.minimum(  # [sender, dest, M]; the
            starts[:, :, None] + m_idx[None, None, :],  # clamp keeps
            row_last).astype(np.int32))                 # padding legal
        pad_mask = jnp.asarray(m_idx[None, None, :] < matrix[:, :, None])
        for o in ops:
            c = o.contrib
            if tl: _tl_start(tl, o, "ALLTOALL")
            if tl: tl.activity_start(o.name, "XLA_ALLTOALL")
            rest = tuple(c.shapes[0][1:])
            x = jnp.asarray(c.value)
            per_sender = (x if c.per_replica
                          else jnp.broadcast_to(x[None], (n,) + x.shape))
            L = int(per_sender.shape[1])
            if L == 0:  # nobody sends anything
                send = jnp.zeros((n, n, Mp) + rest, x.dtype)
            else:
                flat = per_sender.reshape(n, L, -1)
                g = jnp.take_along_axis(
                    flat, gather_idx.reshape(n, n * Mp)[:, :, None],
                    axis=1)
                send = jnp.where(
                    pad_mask.reshape(n, n, Mp, *([1] * len(rest))),
                    g.reshape((n, n, Mp) + rest),
                    jnp.zeros((), g.dtype))  # keep bool/int dtypes
            if ps is None:
                placed = shard(send)
            else:
                mesh_ps, _ = ps.mesh_and_kernels()
                spec = [None] * send.ndim
                spec[0] = REPLICA_AXIS
                placed = jax.device_put(
                    send, NamedSharding(mesh_ps, P(*spec)))
            recv = ks["a2a_pr"](placed)  # [recv, sender, M, ...]
            outs = [
                jnp.concatenate([recv[r, s, :int(matrix[s, r])]
                                 for s in range(n)], axis=0)
                for r in range(n)
            ]
            if tl: tl.activity_end(o.name)
            if tl: tl.end(o.name, dtype=str(c.dtype))
            hm._get(o.handle).result = outs
        return

    if resp.response_type == ResponseType.REDUCESCATTER:
        ks = _mesh_kernels() if ps is None else ps.mesh_and_kernels()[1]
        for o in ops:  # never fused: each op owns its chunk layout
            if tl: _tl_start(tl, o, "REDUCESCATTER")
            if tl: tl.activity_start(o.name, "XLA_REDUCESCATTER")
            # AVERAGE folds its divide into the compiled kernel — one
            # launch instead of reduce + a separate eager _divide.
            avg = "_avg" if o.red_op == ReduceOp.AVERAGE else ""
            kernel = ks[("rscatter_pr" if o.contrib.per_replica
                         else "rscatter_rep") + avg]
            out = kernel(o.contrib.value)
            if tl: tl.activity_end(o.name)
            if tl: tl.end(o.name, dtype=str(o.contrib.dtype))
            hm._get(o.handle).result = out
        return

    if resp.response_type == ResponseType.ALLGATHER:
        ks = _mesh_kernels() if ps is None else ps.mesh_and_kernels()[1]
        for o in ops:
            c = o.contrib
            if tl: _tl_start(tl, o, "ALLGATHER")
            if tl: tl.activity_start(o.name, "XLA_ALLGATHER")
            if c.ragged or isinstance(c.value, list):
                sizes = list(resp.tensor_sizes or c.orig_sizes)
                dmax = max(sizes)
                rest = tuple(c.shapes[0][1:])
                total = int(sum(sizes))
                k = len(c.value)
                if total == 0 or dmax == 0:
                    out = jnp.zeros((0,) + rest, c.dtype)
                else:
                    # Vectorized pad/stack (round-4 alltoall treatment
                    # applied here): the padded [k, dmax, rest] staging
                    # buffer is built with ONE device-side gather over
                    # the concatenated contributions instead of a
                    # per-tensor host loop of jnp.concatenate zero-pads
                    # — the O(k) eager-dispatch chain becomes 2
                    # launches.  The index plan is host-side int32;
                    # clamped duplicate rows stand in for the zero
                    # padding (both are sliced off by the unpad below,
                    # so the values never surface).
                    sz = np.asarray(sizes, np.int64)
                    starts = np.zeros(k, np.int64)
                    starts[1:] = np.cumsum(sz)[:-1]
                    j = np.arange(dmax)
                    gather_idx = starts[:, None] + np.minimum(
                        j[None, :], np.maximum(sz[:, None] - 1, 0))
                    gather_idx = np.clip(gather_idx, 0,
                                         total - 1).astype(np.int32)
                    flat = jnp.concatenate(
                        [jnp.asarray(v) for v in c.value], axis=0)
                    padded = jnp.take(flat, jnp.asarray(gather_idx),
                                      axis=0)  # [k, dmax, rest...]
                    if ps is None:
                        padded = shard(padded)
                    else:
                        mesh_ps, _ = ps.mesh_and_kernels()
                        spec = [None] * padded.ndim
                        spec[0] = REPLICA_AXIS
                        padded = jax.device_put(
                            padded, NamedSharding(mesh_ps, P(*spec)))
                    gathered = ks["gather_pr"](padded)  # [k*dmax, ...]
                    # Unpad with one gather too: row plan of each
                    # rank's first s_i rows, in rank order.
                    unpad_idx = np.concatenate(
                        [i * dmax + np.arange(s)
                         for i, s in enumerate(sizes)]).astype(np.int32)
                    out = jnp.take(gathered, jnp.asarray(unpad_idx),
                                   axis=0)
            elif c.per_replica:
                out = ks["gather_pr"](c.value)
            else:
                out = ks["gather_rep"](c.value)
            if tl: tl.activity_end(o.name)
            if tl: tl.end(o.name, dtype=str(c.dtype))
            hm._get(o.handle).result = out
        return

    if resp.response_type == ResponseType.BROADCAST:
        ks = _mesh_kernels() if ps is None else ps.mesh_and_kernels()[1]
        for o in ops:
            c = o.contrib
            if tl: _tl_start(tl, o, "BROADCAST")
            if tl: tl.activity_start(o.name, "XLA_BCAST")
            if c.per_replica:
                out = ks["bcast_pr"](c.value, jnp.int32(o.root_rank))
            else:
                # Replicated input: broadcast is the identity, but still run
                # a collective for execution parity with the reference's
                # unconditional MPI_Bcast (operations.cc:1053-1055) —
                # psum(x)/n compiled as ONE kernel, not psum + an eager
                # divide launch.
                out = ks["bcast_rep"](c.value) \
                    if jnp.issubdtype(c.value.dtype, jnp.inexact) \
                    else c.value
            if tl: tl.activity_end(o.name)
            if tl: tl.end(o.name, dtype=str(c.dtype))
            hm._get(o.handle).result = out
        return


def _execute_response_mp(resp: Response, ops: List[_QueuedOp]) -> None:
    """Multi-process execution of one broadcast response.

    Every process receives the same response list in the same order and
    calls the same jitted collective over the process mesh with its own
    shard — the SPMD property the reference gets from executing MPI ops in
    MPI_Bcast order (operations.cc:1290-1326).
    """
    st = _state.global_state()
    tl = st.timeline
    hm = st.handle_manager
    ps = _state.get_process_set(resp.process_set_id) \
        if resp.process_set_id else None
    if ps is not None:
        if not ops:
            # Not a member of this set (or a member with nothing pending,
            # e.g. after shutdown poisoning): this process takes no part
            # in the sub-mesh collective.
            return
        _, ks = ps.mesh_and_kernels()
        denom = ps.size()
    else:
        _, ks = _mp_kernels()
        denom = st.process_count

    if st.joining and ps is None and resp.tensor_type is not None \
            and len(ops) < len(resp.tensor_names):
        # This process called hvd.join(): participate in the peers'
        # collective with ZERO contributions so the SPMD program still
        # runs on every process (Horovod's Join semantics — post-v0.13;
        # the v0.13 reference could only hang on uneven workloads).
        # ``ops`` may be a PARTIAL subset: an async op this rank
        # submitted before joining can fuse with tensors completed by
        # its JOIN — the mixed buffer must still match the peers'.
        _execute_response_mp_joined(resp, ops)
        return

    if not ops:
        # The local op is gone (shutdown poisoning, or the local-fallback
        # withdrawal after the controller never answered a WITHDRAW
        # frame): skip this response rather than crash mid-list.  In the
        # normal timeout path this cannot happen anymore — a timed-out
        # rank withdraws through the coordinator, which broadcasts an
        # ERROR response (handled above) instead of ever constructing a
        # collective response missing a participant.
        return

    if resp.response_type == ResponseType.ALLREDUCE:
        mesh = (_mp_kernels()[0] if ps is None
                else ps.mesh_and_kernels()[0])
        # Megakernel path (default): one jitted local pack → one donated
        # reduce+divide+unpack executable over the process mesh
        # (ops/megakernel.py) instead of the per-tensor slice/divide
        # chain below.
        if _megakernel_eligible(ops) and _launch_mp_megakernel(
                resp, ops, ps, mesh, denom, tl, hm):
            return
        if len(ops) == 1:
            o = ops[0]
            if tl: _tl_start(tl, o, "ALLREDUCE")
            if tl: tl.activity_start(o.name, "XLA_ALLREDUCE")
            if o.red_op == ReduceOp.AVERAGE:
                # Divide folded into the compiled kernel, not a
                # separate eager dispatch after it.
                out = ks["psum_out_rep_avg"](
                    _mp_global(o.contrib.value, ps))
            else:
                out = ks[_OP_KERNEL[o.red_op] + "_out_rep"](
                    _mp_global(o.contrib.value, ps))
            if tl: tl.activity_end(o.name)
            if tl: tl.end(o.name, dtype=str(o.contrib.dtype))
            hm._get(o.handle).result = out
            return
        # Fused eager fallback (HVD_TPU_MEGAKERNEL=0): one flat buffer
        # per response (≙ MEMCPY_IN_FUSION_BUFFER).  Homogeneous in
        # red_op — the coordinator fuses like-op only (and never fuses
        # adasum, whose dots are per-tensor).
        for o in ops:
            if tl: _tl_start(tl, o, "ALLREDUCE")
            if tl: tl.activity_start(o.name, "MEMCPY_IN_FUSION_BUFFER")
        buf = jnp.concatenate([jnp.ravel(o.contrib.value) for o in ops])
        for o in ops:
            if tl: tl.activity_end(o.name)
            if tl: tl.activity_start(o.name, "XLA_ALLREDUCE")
        red = ks[_OP_KERNEL[ops[0].red_op] + "_out_rep"](
            _mp_global(buf, ps))
        offs = 0
        for o in ops:
            n = int(np.prod(o.contrib.shapes[0], dtype=np.int64)) if \
                o.contrib.shapes[0] else 1
            if tl: tl.activity_end(o.name)
            if tl: tl.activity_start(o.name, "MEMCPY_OUT_FUSION_BUFFER")
            piece = red[offs:offs + n].reshape(o.contrib.shapes[0])
            offs += n
            if o.red_op == ReduceOp.AVERAGE:
                piece = _divide(piece, denom)
            if tl: tl.activity_end(o.name)
            if tl: tl.end(o.name, dtype=str(o.contrib.dtype))
            hm._get(o.handle).result = piece
        return

    if resp.response_type == ResponseType.ALLTOALL:
        st_me = (st.process_index if ps is None else ps.rank())
        n = denom
        matrix = np.asarray(resp.tensor_sizes,
                            dtype=np.int64).reshape(n, n)
        M = int(matrix.max()) if matrix.size else 0
        for o in ops:
            c = o.contrib
            if tl: _tl_start(tl, o, "ALLTOALL")
            if tl: tl.activity_start(o.name, "XLA_ALLTOALL")
            rest = tuple(c.shapes[0][1:])
            local = np.asarray(c.value)
            send = np.zeros((n, M) + rest, local.dtype)
            off = 0
            for d in range(n):
                cnt = int(matrix[st_me, d])
                send[d, :cnt] = local[off:off + cnt]
                off += cnt
            res = ks["a2a_pr"](_mp_global(jnp.asarray(send), ps))
            mine = np.asarray(res.addressable_data(0))[0]  # [sender, M,..]
            out = jnp.concatenate(
                [mine[s, :int(matrix[s, st_me])] for s in range(n)],
                axis=0)
            if tl: tl.activity_end(o.name)
            if tl: tl.end(o.name, dtype=str(c.dtype))
            hm._get(o.handle).result = out
        return

    if resp.response_type == ResponseType.REDUCESCATTER:
        for o in ops:
            if tl: _tl_start(tl, o, "REDUCESCATTER")
            if tl: tl.activity_start(o.name, "XLA_REDUCESCATTER")
            # AVERAGE folds its divide into the compiled kernel (no
            # separate eager dispatch on the extracted chunk).
            kernel = ks["rscatter_pr_avg"
                        if o.red_op == ReduceOp.AVERAGE else "rscatter_pr"]
            res = kernel(_mp_global(o.contrib.value, ps))
            # This process's chunk: its addressable row of the P(A)
            # output (Horovod returns only the caller's chunk).
            mine = jnp.squeeze(jnp.asarray(res.addressable_data(0)),
                               axis=0)
            if tl: tl.activity_end(o.name)
            if tl: tl.end(o.name, dtype=str(o.contrib.dtype))
            hm._get(o.handle).result = mine
        return

    if resp.response_type == ResponseType.ALLGATHER:
        for o in ops:
            c = o.contrib
            if tl: _tl_start(tl, o, "ALLGATHER")
            if tl: tl.activity_start(o.name, "XLA_ALLGATHER")
            # The coordinator's response carries every rank's dim-0 extent
            # (≙ MPIResponse.tensor_sizes, mpi_message.h:48-51).
            sizes = resp.tensor_sizes or [c.orig_sizes[0]] * denom
            dmax = max(sizes)
            v = c.value
            if v.shape[0] < dmax:
                pad = jnp.zeros((dmax - v.shape[0],) + tuple(v.shape[1:]),
                                v.dtype)
                v = jnp.concatenate([v, pad], axis=0)
            gathered = ks["gather_pr"](_mp_global(v, ps))  # [P*dmax, ...]
            if any(s != dmax for s in sizes):
                pieces = [gathered[i * dmax:i * dmax + s]
                          for i, s in enumerate(sizes)]
                out = jnp.concatenate(pieces, axis=0)
            else:
                out = gathered
            if tl: tl.activity_end(o.name)
            if tl: tl.end(o.name, dtype=str(c.dtype))
            hm._get(o.handle).result = out
        return

    if resp.response_type == ResponseType.BROADCAST:
        for o in ops:
            c = o.contrib
            if tl: _tl_start(tl, o, "BROADCAST")
            if tl: tl.activity_start(o.name, "XLA_BCAST")
            out = ks["bcast_pr"](_mp_global(c.value, ps),
                                 jnp.int32(o.root_rank))
            if tl: tl.activity_end(o.name)
            if tl: tl.end(o.name, dtype=str(c.dtype))
            hm._get(o.handle).result = out
        return


def _execute_response_mp_joined(resp: Response,
                                ops: List["_QueuedOp"] = ()) -> None:
    """Joined-rank execution of one data response: same jitted collective
    over the process mesh, zero contributions built from the response's
    dtype + shapes (wire fields added for exactly this).  ``ops`` holds
    any of the rank's OWN outstanding async ops that rode the same fused
    response — they contribute their real values (exactly like the live
    path) and receive their slice of the result."""
    st = _state.global_state()
    hm = st.handle_manager
    _, ks = _mp_kernels()
    dtype = wire.np_dtype_of(resp.tensor_type)
    shapes = [tuple(s) for s in resp.tensor_shapes]
    by_name = {o.name: o for o in ops}

    if resp.response_type == ResponseType.ALLREDUCE:
        # Megakernel path: the zero-contribution slots are packed into
        # the identical fused program the live ranks run —
        # _launch_mp_megakernel fills zeros for tensors this rank never
        # submitted and discards their outputs.
        if (_megakernel.enabled()
                and (not ops or ops[0].red_op != ReduceOp.ADASUM)
                and _launch_mp_megakernel(
                    resp, ops, None, _mp_kernels()[0],
                    st.process_count, st.timeline, hm)):
            return

        def numel(s):
            return int(np.prod(s, dtype=np.int64)) if s else 1

        if len(resp.tensor_names) == 1:
            o = by_name.get(resp.tensor_names[0])
            val = o.contrib.value if o is not None \
                else jnp.zeros(shapes[0], dtype)
            # Only SUM/AVERAGE can reach a joined rank (the coordinator
            # errors other reduce ops once a rank has joined).
            out = ks["psum_out_rep"](_mp_global(val))
            if o is not None:
                if o.red_op == ReduceOp.AVERAGE:
                    out = _divide(out, st.process_count)
                hm._get(o.handle).result = out
            return
        # Fused: the peers reduce ONE flat buffer — build the identical
        # buffer with zeros in the slots this rank never submitted.
        parts = [jnp.ravel(by_name[n].contrib.value) if n in by_name
                 else jnp.zeros((numel(s),), dtype)
                 for n, s in zip(resp.tensor_names, shapes)]
        red = ks["psum_out_rep"](_mp_global(jnp.concatenate(parts)))
        offs = 0
        for n, s in zip(resp.tensor_names, shapes):
            o = by_name.get(n)
            cnt = numel(s)
            if o is not None:
                piece = red[offs:offs + cnt].reshape(s)
                if o.red_op == ReduceOp.AVERAGE:
                    piece = _divide(piece, st.process_count)
                hm._get(o.handle).result = piece
            offs += cnt
        return
    if resp.response_type == ResponseType.ALLGATHER:
        dmax = max(resp.tensor_sizes) if resp.tensor_sizes else 0
        rest = shapes[0][1:]
        ks["gather_pr"](_mp_global(jnp.zeros((dmax,) + rest, dtype)))
        return
    if resp.response_type == ResponseType.BROADCAST:
        root = resp.tensor_sizes[0] if resp.tensor_sizes else 0
        ks["bcast_pr"](_mp_global(jnp.zeros(shapes[0], dtype)),
                       jnp.int32(root))


def join() -> int:
    """Barrier for uneven workloads (the post-v0.13 ``hvd.join()`` API).

    A process that has run out of data calls ``join()``; until every
    process joins, it keeps participating in the others' collectives
    with ZERO contributions (allreduce adds zeros and still divides by
    the full size — Horovod's documented Join semantics; allgather
    contributes 0 rows).  Returns the rank of the LAST process to join,
    so callers can e.g. pick a rank that saw every batch.  The v0.13
    reference predates Join and could only hang on uneven workloads.

    Single-process mode is trivially a no-op returning this rank: all
    replicas advance in lockstep inside one program.
    """
    import os as _os
    import time as _time

    _state._check_initialized()
    st = _state.global_state()
    if not st.multiprocess:
        return st.process_index
    if st.peer_shutdown:
        raise HorovodError(SHUT_DOWN_ERROR_MESSAGE)
    req = wire.Request(st.process_index, wire.RequestType.JOIN,
                       wire.DataType.UINT8, "hvd.join")
    st.join_result = None
    st.joining = True
    try:
        if st.process_index == 0:
            st.coordinator.submit(req)
        else:
            st.transport.submit(req)
        timeout = float(_os.environ.get("HOROVOD_TPU_JOIN_TIMEOUT", "600"))
        deadline = _time.monotonic() + timeout
        while st.join_result is None and _time.monotonic() < deadline:
            if st.peer_shutdown:
                raise HorovodError(SHUT_DOWN_ERROR_MESSAGE)
            _drain()
            _time.sleep(0.001)
    finally:
        st.joining = False
    if st.join_result is None:
        raise HorovodError(
            f"hvd.join() timed out after {timeout:.0f}s waiting for the "
            f"remaining processes to join (HOROVOD_TPU_JOIN_TIMEOUT).")
    return st.join_result


def _threshold_snapshot(st):
    """psid -> fusion threshold of the owning coordinator, snapshotted
    BEFORE entering the cache (ResponseCache._lock is a leaf lock; the
    take_ready callback must therefore be pure — resolving process sets
    from inside it would acquire st.lock under the cache lock).  The
    replay plan uses the same packing budget the live negotiation
    would; a psid not in the snapshot (set removed this tick — its
    entries are flushed anyway) falls back to the global threshold."""
    default = (st.coordinator.fusion_threshold
               if st.coordinator is not None
               else st.fusion_threshold_bytes)
    thresholds = {0: default}
    for set_ps in _state.process_sets_snapshot():
        if set_ps.coordinator is not None:
            thresholds[set_ps.process_set_id] = \
                set_ps.coordinator.fusion_threshold
    return lambda psid: thresholds.get(psid, default)


def _resubmit_orphans(st, orphans) -> None:
    """Route cached submissions downgraded by a flush back into the real
    negotiation path (each carries its process-set id)."""
    for req in orphans:
        coord = st.coordinator if req.process_set_id == 0 else None
        if coord is None:
            ps = _state.get_process_set(req.process_set_id)
            coord = None if ps is None else ps.coordinator
        if coord is None:
            continue  # set removed meanwhile; submitter times out/report
        try:
            coord.submit(req)
        except ValueError:
            pass  # duplicate: the rank re-submitted meanwhile


def _coordinator_tick(st):
    """One rank-0 (or single-process) negotiation tick: cache replay +
    flush markers + freshly negotiated responses, in the stream order
    every replica relies on.  Returns (responses, replay groups, epoch,
    compact_ok, n_non_replay, replay_ids) — the groups let the
    transport broadcast a pure-replay cycle compactly, and replay_ids
    identifies the replayed responses so observation never re-inserts
    them (the worker-side equivalent is the name-presence check)."""
    cache = st.response_cache
    meta = _queue.pending_meta()
    marker = None
    replayed: List[Response] = []
    groups: List[List[int]] = []
    epoch = 0
    compact = True
    if cache is not None:
        _resubmit_orphans(st, cache.check_capacity())
        marker = cache.take_flush_marker()
        replayed, groups, epoch, compact = cache.take_ready(
            _threshold_snapshot(st))
        if replayed and st.timeline is not None:
            # The one NEGOTIATE-span closer for cache-served tensors:
            # submit-side hits deliberately leave the span open (a
            # remote bit may be the completing hit, which submit never
            # sees), and this runs exactly once per replayed tensor.
            for r in replayed:
                for n in r.tensor_names:
                    st.timeline.negotiate_end(n)
    # hvd-tune: pending retune decisions become stream markers HERE, on
    # the coordinator tick that owns stream ordering — after the flush
    # marker (flush-before-anything), before replay/negotiation (so the
    # knob flip never splits a cycle's responses).  They count as
    # non-replay traffic below, forcing a full-frame broadcast.
    retunes: List[Response] = []
    if st.tuner is not None:
        retunes = st.tuner.take_markers()
    negotiated = st.coordinator.poll_responses(meta)
    for set_ps in _state.process_sets_snapshot():
        if set_ps.coordinator is not None:
            negotiated += set_ps.coordinator.poll_responses(meta)
    # hvd-chaos coord.reorder: permute ONLY the freshly negotiated
    # responses of this tick (never across the marker/replay prefix —
    # that ordering is load-bearing for replica alignment).  Responses
    # within one tick carry no cross-response ordering contract, so a
    # recovered run must stay bitwise-identical under the permutation.
    if _chaos.active():
        negotiated = _chaos.maybe_reorder("coord.reorder", negotiated)
    # Marker FIRST: replicas must flush before inserting anything this
    # tick's negotiations produce; replayed responses reference live
    # (post-flush) entries whenever a marker is present, so the order
    # [marker, replays, negotiated] is safe in every interleaving.
    resps = ([marker] if marker is not None else []) + retunes \
        + replayed + negotiated
    return resps, groups, epoch, compact, \
        (1 if marker is not None else 0) + len(retunes) + len(negotiated), \
        frozenset(id(r) for r in replayed)


def _drain() -> None:
    """Poll the coordinator and execute every ready (fused) response
    (≙ one background-loop tick, operations.cc:1219-1374).  Validation
    errors are stored on their handles and surfaced at synchronize/poll,
    matching the reference's callback-with-error-Status flow
    (operations.cc:1060-1067)."""
    st = _state.global_state()
    with _drain_lock:
        cache = st.response_cache
        if st.multiprocess:
            tp = st.transport
            if tp is None:
                return
            if st.process_index == 0:
                # A worker asked for shutdown: broadcast it and poison
                # local pending ops (≙ operations.cc:1377-1403).
                if tp.shutdown_requested.is_set() and not st.peer_shutdown:
                    _initiate_shutdown()
                # hvd-chaos reconnect: a disconnected worker whose
                # grace window expired without a session resume becomes
                # a lost rank (with a diagnostic naming the fault).
                tp.expire_grace()
                # A worker's connection dropped without a shutdown frame:
                # the process died (or exited without calling shutdown()).
                # With collectives pending this is fatal — fail them with
                # a message naming the rank (the reference can only hang
                # here); otherwise it is an implicit shutdown.
                if tp.lost_ranks and not st.peer_shutdown:
                    _handle_lost_ranks(st, tp)
                # Coordinator: poll, broadcast the fused responses to every
                # worker, then execute locally in the same order
                # (≙ MPI_Bcast of the response list, operations.cc:1290).
                tp.flush_unrouted()  # set requests that beat registration
                tp.maybe_ping()  # hvd-trace clock probes (trace/clock.py)
                with _R_TICK() as tick:
                    resps, groups, epoch, compact, n_other, replay_ids \
                        = _coordinator_tick(st)
                    _close_tick(tick, resps)
                if resps:
                    # The controller reaches its own cache stream
                    # position BEFORE publishing the stream: a fast
                    # worker can observe the frame, hit its fresh
                    # replica entry and ship the hit bit back before
                    # this thread returns from the send — the bit must
                    # find the entry already inserted, or it is dropped
                    # as unresolvable and the op stalls into a withdraw
                    # (the roaming fault-free chaos-cp abandonment).
                    if cache is not None:
                        for resp in resps:
                            cache.observe_response(
                                resp, replay=id(resp) in replay_ids)
                    if compact and groups and n_other == 0:
                        # Pure cache replay: the steady-state frame —
                        # entry-index groups instead of full payloads.
                        tp.broadcast_replay(groups, epoch)
                    else:
                        tp.broadcast_responses(resps)
                for resp in resps:
                    ops = _queue.take(resp.tensor_names)
                    _execute_response(resp, ops)
                    if st.autotuner is not None:
                        st.autotuner.record_bytes(
                            sum(o.nbytes for o in ops))
                if st.autotuner is not None:
                    st.autotuner.maybe_step()
            else:
                tp.flush_requests()  # the tick's coalesced control frame
                while True:
                    resps = tp.poll_responses()
                    if resps is None:
                        break
                    # Adopt the controller's cycle id (the batch's
                    # trace trailer) before executing, so this rank's
                    # spans land under the same fleet-wide cycle.
                    ctx = tp.last_trace_ctx
                    if ctx is not None and _trace.enabled():
                        _trace.observe_ctx(*ctx)
                    for resp in resps:
                        ops = _queue.take(resp.tensor_names)
                        if cache is not None:
                            cache.observe_response(resp, own_requests={
                                st.process_index: {
                                    o.name: o.request for o in ops
                                    if o.request is not None}})
                        _execute_response(resp, ops)
            return
        with _R_TICK() as tick:
            resps, _groups, _epoch, _compact, _n, replay_ids = \
                _coordinator_tick(st)
            # Single-process cycles advance the same counter so the
            # local trace analyzes identically to a fleet's.
            _close_tick(tick, resps)
        for resp in resps:
            ops = _queue.take(resp.tensor_names)
            if cache is not None:
                cache.observe_response(resp,
                                       replay=id(resp) in replay_ids)
            _execute_response(resp, ops)
            if st.autotuner is not None:
                st.autotuner.record_bytes(sum(o.nbytes for o in ops))
        if st.autotuner is not None:
            st.autotuner.maybe_step()


@contextlib.contextmanager
def quiesce():
    """Hold the drain lock across a group of ``*_async`` submissions so
    the background 5 ms tick cannot negotiate a partial group, then run
    one explicit drain on exit.

    This is the sanctioned fix for the submission-split race: without
    it, a tick that fires between two submissions of one logical cycle
    negotiates them as two fused responses, which perturbs anything
    that asserts on fusion granularity (bench dataplane legs, ledger
    accounting tests).  Same pattern as
    ``overlap.dispatch_bucket_segment``::

        with C.quiesce():
            h1 = C.allreduce_async(a, name="cycle.a")
            h2 = C.allreduce_async(b, name="cycle.b")
        C.synchronize(h1); C.synchronize(h2)

    The body must only *submit* — calling :func:`synchronize` (or
    anything that waits on a response) inside the block deadlocks,
    because progress requires the drain the block is deferring.
    """
    with _drain_lock:
        yield
    _drain()


# ---------------------------------------------------------------------------
# Public API
# ---------------------------------------------------------------------------

def _resolve_op(average, op) -> ReduceOp:
    """Resolve the (average, op) pair into one ReduceOp.

    Mirrors the post-v0.13 Horovod contract: ``op`` and ``average`` are
    mutually exclusive — passing both raises ValueError; with neither,
    the default is Average (the reference's allreduce default,
    tensorflow/__init__.py:49, torch/mpi_ops.py:58)."""
    if op is not None:
        if average is not None:
            raise ValueError(
                "specify either average= or op=, not both "
                "(they are mutually exclusive).")
        return ReduceOp(op)
    if average is None or average:
        return ReduceOp.AVERAGE
    return ReduceOp.SUM


def _check_reduce_op(red_op: ReduceOp, dtype, process_set=None) -> None:
    st = _state.global_state()
    if red_op == ReduceOp.ADASUM:
        n = (_state.contributor_count() if process_set is None
             else process_set.size())
        if n & (n - 1) != 0:
            raise ValueError(
                f"op=Adasum requires a power-of-two contributor count for "
                f"its recursive-doubling ppermute ladder; got {n}.")
        if not jnp.issubdtype(jnp.dtype(dtype), jnp.inexact):
            raise ValueError(
                f"op=Adasum is defined on floating-point gradients; got "
                f"dtype {dtype}.")
        if st.joining:
            raise HorovodError(
                "op=Adasum cannot run while this rank has joined: a zero "
                "contribution is only an identity for sum/average.")


def _enqueue(x, op: RequestType, name: Optional[str],
             red_op: ReduceOp = ReduceOp.SUM,
             root_rank: int = -1, prefix: str = "",
             process_set=None, splits: Tuple[int, ...] = (),
             owned: Optional[bool] = None) -> int:
    _state._check_initialized()
    st = _state.global_state()
    if st.peer_shutdown:
        raise HorovodError(SHUT_DOWN_ERROR_MESSAGE)
    if process_set is not None and process_set.process_set_id == 0:
        process_set = None  # hvd.global_process_set() ≡ the world
    if process_set is not None and \
            _state.get_process_set(process_set.process_set_id) is None:
        raise HorovodError(
            f"process set {process_set.process_set_id} is not registered "
            f"(was it removed, or created before a re-init?).")
    if process_set is not None and not process_set.included():
        raise HorovodError(
            f"rank {st.process_index} is not a member of process set "
            f"{process_set.process_set_id} (ranks "
            f"{list(process_set.ranks)}) and cannot submit collectives "
            f"into it (the post-v0.13 process-set contract).")
    c = _classify(x, op, ps=process_set)
    if owned is not None and not isinstance(c.value, (list, tuple)):
        # Caller-declared ownership (donate_inputs=True): the submitter
        # promises never to observe the array again, so the megakernel
        # may donate it even though _classify saw a caller-held
        # jax.Array.  The overlap path's gradient buffers ride this —
        # they are step-internal producer outputs nothing else reads.
        c.owned = bool(owned)
    if op == RequestType.ALLREDUCE:
        _check_reduce_op(red_op, c.dtype, process_set)
    name = name or _auto_name(prefix or op.name.lower(), process_set)
    # Payload bytes of ONE replica's tensor — the quantity the reference's
    # fusion accounting uses (tensor->size(), operations.cc:1341-1352).
    item = wire.dtype_size(wire.dtype_of(c.dtype))
    s0 = c.shapes[0]
    nbytes = int(np.prod(s0, dtype=np.int64)) * item if s0 else item
    # hvd-analyze signature capture (analysis/program.py): one record
    # per collective, before negotiation, so verify_program can prove
    # cross-rank agreement of the traced program ahead of the data
    # plane.  Every frontend funnels through this point.
    _program.record_collective(
        op.name.lower(), name,
        wire.dtype_name(wire.dtype_of(c.dtype)), s0,
        reduce_op=(wire.reduce_op_name(red_op)
                   if op in (RequestType.ALLREDUCE,
                             RequestType.REDUCESCATTER) else ""),
        process_set_id=0 if process_set is None
        else process_set.process_set_id)
    handle = st.handle_manager.allocate(None, name=name)
    # Clock stamp gated like every other instrument: disabled telemetry
    # must cost a flag check and nothing else.
    qop = _QueuedOp(name=name, op=op, contrib=c, red_op=red_op,
                    root_rank=root_rank, handle=handle, nbytes=nbytes,
                    ps=process_set,
                    t_submit=(time.monotonic()
                              if _telemetry.enabled() or _trace.enabled()
                              else 0.0))
    _M_SUBMITTED.inc()
    _queue.put(qop)
    # The execute paths read split info from the NEGOTIATED response
    # matrix, never from the local op — splits ride the request only.
    hit = _submit_requests(name, op, c, root_rank, red_op=red_op,
                           ps=process_set, splits=tuple(splits),
                           queued_op=qop)
    qop.cache_hit = hit
    st.handle_manager._get(handle).cache_hit = hit
    return handle


def allreduce_async(tensor, average=None, name: Optional[str] = None,
                    op=None, process_set=None) -> int:
    """Queue an allreduce; returns a handle for poll/synchronize
    (≙ horovod_torch_allreduce_async_*, torch/mpi_ops.cc:206-253).
    Averages by default for parity with the reference API
    (torch/mpi_ops.py:58, tensorflow/__init__.py:49); ``op`` takes any
    of hvd.Average/Sum/Adasum/Min/Max/Product (the post-v0.13 API) and
    is mutually exclusive with ``average`` (passing both raises
    ValueError); ``process_set`` (from :func:`add_process_set`)
    restricts the collective to a rank subset."""
    return _enqueue(tensor, RequestType.ALLREDUCE, name,
                    red_op=_resolve_op(average, op), prefix="allreduce",
                    process_set=process_set)


def grouped_allreduce_async(tensors, average=None,
                            name: Optional[str] = None,
                            op=None, donate_inputs: bool = False) -> List[int]:
    """Queue a group of allreduces in one call; returns one handle per
    tensor (≙ the post-v0.13 hvd.grouped_allreduce API).  The group
    enters the request queue back-to-back, so Tensor Fusion batches it
    — normally into one wire collective; a concurrent background tick
    can split a group across two fused responses, which changes wire
    batching, never results.  The default base name is unique per call
    so overlapping anonymous groups never collide.

    ``donate_inputs=True`` declares the tensors executor-owned: the
    caller promises never to observe them again, and the fused
    megakernel donates their buffers (the backward/communication-overlap
    step passes its gradient buffers this way — on TPU the reduction
    then reuses the gradients' memory instead of allocating)."""
    base = name or _auto_name("grouped.allreduce")
    red_op = _resolve_op(average, op)
    return [
        _enqueue(t, RequestType.ALLREDUCE, f"{base}.{i}", red_op=red_op,
                 prefix="allreduce",
                 owned=True if donate_inputs else None)
        for i, t in enumerate(tensors)
    ]


def grouped_allreduce(tensors, average=None, name: Optional[str] = None,
                      op=None) -> List:
    """Synchronous grouped allreduce: fused under the hood, one result
    per input tensor, input order preserved."""
    return [synchronize(h)
            for h in grouped_allreduce_async(tensors, average, name, op)]


def grouped_allgather_async(tensors, name: Optional[str] = None,
                            process_set=None) -> List[int]:
    """Queue a group of allgathers (≙ the post-v0.13
    hvd.grouped_allgather): one handle per tensor, back-to-back enqueue
    so every gather negotiates in the same coordinator tick."""
    base = name or _auto_name("grouped.allgather", process_set)
    return [_enqueue(t, RequestType.ALLGATHER, f"{base}.{i}",
                     prefix="allgather", process_set=process_set)
            for i, t in enumerate(tensors)]


def grouped_allgather(tensors, name: Optional[str] = None,
                      process_set=None) -> List:
    return [synchronize(h)
            for h in grouped_allgather_async(tensors, name, process_set)]


def grouped_reducescatter_async(tensors, average=None,
                                name: Optional[str] = None, op=None,
                                process_set=None) -> List[int]:
    """Queue a group of reducescatters (≙ the post-v0.13
    hvd.grouped_reducescatter): one handle per tensor."""
    base = name or _auto_name("grouped.reducescatter", process_set)
    return [reducescatter_async(t, average, f"{base}.{i}", op, process_set)
            for i, t in enumerate(tensors)]


def grouped_reducescatter(tensors, average=None,
                          name: Optional[str] = None, op=None,
                          process_set=None) -> List:
    return [synchronize(h) for h in grouped_reducescatter_async(
        tensors, average, name, op, process_set)]


def allgather_async(tensor, name: Optional[str] = None,
                    process_set=None) -> int:
    return _enqueue(tensor, RequestType.ALLGATHER, name, prefix="allgather",
                    process_set=process_set)


def remove_process_set(process_set) -> bool:
    """Deregister a process set (≙ the post-v0.13
    ``hvd.remove_process_set``).  Collective in multi-process mode (every
    process must call it for the same set, like registration); returns
    False when the set was already removed.  The global set cannot be
    removed."""
    _state._check_initialized()
    st = _state.global_state()
    psid = process_set.process_set_id
    if psid == 0:
        raise ValueError("the global process set cannot be removed")
    if _state.get_process_set(psid) is None:
        return False
    if st.multiprocess:
        # The registration allgather is itself a blocking collective, so
        # it must run OUTSIDE st.lock (blocking-under-lock lint rule).
        from .objects import allgather_object

        regs = allgather_object(psid, name=f"process_set.remove.{psid}")
        if any(r != psid for r in regs):
            raise HorovodError(
                f"remove_process_set must be called by every process for "
                f"the same set; this process removed {psid} but the job "
                f"removed {regs}.")
    with st.lock:
        ps = st.process_sets.pop(psid, None)
    if ps is not None:
        ps.close()
    if not st.multiprocess and st.response_cache is not None:
        # Multi-process mode flushes deterministically when every rank
        # observes the process_set.remove.* allgather in the response
        # stream (ops/cache.py); single-process has no such collective,
        # so flush directly — a cached cycle must never replay a
        # response into a removed set.
        _resubmit_orphans(st, st.response_cache.flush(
            f"remove_process_set({psid})"))
    return True


def global_process_set():
    """The implicit world communicator as a :class:`ProcessSet`
    (≙ ``hvd.global_process_set``; a function here because the world is
    only known after ``init()``).  Passing it (or ``None``) to a
    collective's ``process_set=`` is equivalent."""
    from .process_set import ProcessSet

    _state._check_initialized()
    return ProcessSet(0, tuple(range(_state.contributor_count())))


def alltoall_async(tensor, splits=None, name: Optional[str] = None,
                   process_set=None) -> int:
    """Queue an alltoall (the post-v0.13 ``hvd.alltoall``): rank r's
    dim-0 rows are scattered to every rank by ``splits`` (one count per
    destination; ``None`` = even split), and the rows received from all
    ranks concatenate in rank order.

    Multi-process mode returns the caller's received tensor;
    single-process mode returns the LIST of per-replica received
    tensors (row counts may differ per receiver).  The negotiated split
    matrix rides the response, so ragged exchanges work like the ragged
    allgather (pad-to-max around XLA's native AllToAll on ICI).
    """
    n = (_state.contributor_count() if process_set is None
         else process_set.size())
    if isinstance(tensor, (list, tuple)):
        raise ValueError("alltoall takes one tensor per rank, not a list.")
    shape = tuple(jnp.shape(tensor))
    if not shape:
        raise ValueError("An alltoall tensor needs at least one dimension.")
    st = _state.global_state()
    d0 = (shape[0] if (st.multiprocess or not (
        isinstance(tensor, jax.Array) and is_per_replica(tensor)))
        else (shape[1] if len(shape) > 1 else 0))
    if splits is None:
        if not shape or d0 % n != 0:
            raise ValueError(
                f"alltoall without splits needs dim 0 divisible by the "
                f"rank count ({n}); got shape {list(shape)}.")
        splits = ()
    else:
        splits = tuple(int(s) for s in splits)
        if len(splits) != n or any(s < 0 for s in splits) or \
                sum(splits) != d0:
            raise ValueError(
                f"alltoall splits {list(splits)} must have one "
                f"non-negative entry per rank ({n}) summing to dim 0 "
                f"({d0}).")
    return _enqueue(tensor, RequestType.ALLTOALL, name, prefix="alltoall",
                    process_set=process_set, splits=splits)


def alltoall(tensor, splits=None, name: Optional[str] = None,
             process_set=None):
    """Synchronous alltoall — see :func:`alltoall_async`."""
    return synchronize(alltoall_async(tensor, splits, name, process_set))


def barrier(process_set=None) -> None:
    """Block until every rank reaches the barrier (the post-v0.13
    ``hvd.barrier``): one tiny named allreduce through the full
    negotiation path, so it also surfaces peer failures/stalls like any
    other collective."""
    synchronize(allreduce_async(
        np.zeros((1,), np.float32), average=False,
        name=_auto_name("barrier", process_set),
        process_set=process_set))


def reducescatter_async(tensor, average=None, name: Optional[str] = None,
                        op=None, process_set=None) -> int:
    """Queue a reducescatter (the post-v0.13 ``hvd.reducescatter``):
    reduce across ranks, then split dim 0 — rank r receives chunk r.
    Multi-process mode returns only the caller's chunk;
    single-process mode returns the per-replica stack ``[n, d0/n, ...]``
    (row r = replica r's chunk).  ``op`` ∈ {Average, Sum}."""
    red = _resolve_op(average, op)
    if red not in (ReduceOp.SUM, ReduceOp.AVERAGE):
        raise ValueError(
            f"reducescatter supports op=Average/Sum (Horovod's contract "
            f"for this collective); got {wire.reduce_op_name(red)}.")
    if isinstance(tensor, (list, tuple)):
        raise ValueError(
            "reducescatter takes one tensor (identical shape on every "
            "rank), not a list.")
    n = (_state.contributor_count() if process_set is None
         else process_set.size())
    shape = tuple(jnp.shape(tensor))
    # is_per_replica can only be True for an already-sharded jax.Array —
    # don't transfer host inputs to device just to learn that.
    if _state.global_state().multiprocess or not (
            isinstance(tensor, jax.Array) and is_per_replica(tensor)):
        d0 = shape[0] if shape else 0
    else:
        d0 = shape[1] if len(shape) > 1 else 0  # [n, d0, ...] shard
    if not shape or d0 % n != 0 or d0 == 0:
        raise ValueError(
            f"reducescatter needs dim 0 divisible by the rank count "
            f"({n}); got shape {list(shape)}.")
    return _enqueue(tensor, RequestType.REDUCESCATTER, name, red_op=red,
                    prefix="reducescatter", process_set=process_set)


def reducescatter(tensor, average=None, name: Optional[str] = None,
                  op=None, process_set=None):
    """Synchronous reducescatter — see :func:`reducescatter_async`."""
    return synchronize(reducescatter_async(tensor, average, name, op,
                                           process_set))


def broadcast_async(tensor, root_rank: int,
                    name: Optional[str] = None, process_set=None) -> int:
    # In multi-process mode ranks are processes (the bcast mask compares
    # against the process-mesh axis index), not devices.  For a process
    # set the API takes the GLOBAL rank (Horovod's convention) and
    # translates it to the set-local index used on the wire.
    if process_set is not None:
        root_rank = process_set.local_rank_of(root_rank)
    else:
        bound = _state.contributor_count()
        if not (0 <= root_rank < bound):
            raise ValueError(f"root_rank {root_rank} outside [0, {bound}).")
    return _enqueue(tensor, RequestType.BROADCAST, name, root_rank=root_rank,
                    prefix="broadcast", process_set=process_set)


def add_process_set(ranks):
    """Register a process set (≙ the post-v0.13 ``hvd.add_process_set``).

    ``ranks`` are GLOBAL rank numbers — replica indices in
    single-process mode, process ranks in multi-process mode.  In
    multi-process mode this is a COLLECTIVE call: every process must
    call it with the identical ranks, in the same registration order
    (Horovod's contract); registration is validated with an
    allgather_object round over the global set and diverging
    registrations raise on every rank.  Returns the
    :class:`~horovod_tpu.ops.process_set.ProcessSet` to pass as
    ``process_set=`` on collectives.
    """
    from .process_set import ProcessSet

    _state._check_initialized()
    st = _state.global_state()
    ranks = tuple(sorted({int(r) for r in ranks}))
    if not ranks:
        raise ValueError("a process set needs at least one rank")
    bound = st.process_count if st.multiprocess else st.size
    bad = [r for r in ranks if not 0 <= r < bound]
    if bad:
        raise ValueError(
            f"process-set ranks {bad} outside [0, {bound}).")
    with st.lock:  # id counter + registry shared with drain/serve threads
        psid = st.next_process_set_id
        st.next_process_set_id = psid + 1
    if st.multiprocess:
        # The registration allgather is itself a blocking collective, so
        # it must run OUTSIDE st.lock (blocking-under-lock lint rule);
        # a failed registration burns the id identically on every rank.
        from .objects import allgather_object

        regs = allgather_object((psid, ranks),
                                name=f"process_set.register.{psid}")
        if any(reg != (psid, ranks) for reg in regs):
            raise HorovodError(
                f"add_process_set must be called by every process with "
                f"identical ranks in the same order; this process "
                f"registered set {psid} as {list(ranks)} but the job "
                f"registered {regs}.")
    ps = ProcessSet(psid, ranks)
    # Per-set coordinator wherever negotiation happens: the rank-0
    # controller in multi-process mode, the in-process coordinator
    # single-process.  It shares the one response-cache replica (entry
    # indices span every set — insertion order is the broadcast stream)
    # and carries the set's global-rank table for hit accounting.
    if st.coordinator is not None:
        from .coordinator import Coordinator

        ps.coordinator = Coordinator(
            size=ps.size(), fusion_threshold=st.fusion_threshold_bytes,
            timeline=st.timeline, cache=st.response_cache, ranks=ranks)
    with st.lock:
        st.process_sets[psid] = ps
    if not st.multiprocess and st.response_cache is not None:
        # Same rationale as remove_process_set: multi-process flushes on
        # the registration allgather; single-process flushes here.
        _resubmit_orphans(st, st.response_cache.flush(
            f"add_process_set({psid})"))
    return ps


def poll(handle: int) -> bool:
    """Non-blocking completion check (≙ horovod_torch_poll,
    torch/mpi_ops.cc:322-324).  Returns False while the op is still queued
    (awaiting the background tick) or its XLA execution is in flight."""
    st = _state.global_state()
    h = st.handle_manager._get(handle)
    if h.result is None:
        return False
    if isinstance(h.result, HorovodError):
        return True
    return st.handle_manager.poll(handle)


def _wait_mp_result(st, h) -> None:
    """Drain until a multi-process collective's response has been
    executed locally (``h.result`` set) — completion depends on the
    other processes, so this waits (with the background tick also
    draining) up to a timeout, then withdraws GROUP-WIDE (round 4):
    tell the coordinator we gave up so it broadcasts an ERROR response
    and every rank fails this op within the grace window — instead of
    each peer serially eating its own full timeout, or (the SPMD
    hazard) this rank later skipping a broadcast response its peers
    execute and block on.  Shared by :func:`synchronize` (which then
    blocks on device completion) and :func:`take_async` (which
    returns the in-flight array — the overlap path's mp partial
    cycles ride this)."""
    import os as _os
    import time as _time

    timeout = float(_os.environ.get("HOROVOD_TPU_SYNC_TIMEOUT", "300"))
    deadline = _time.monotonic() + timeout
    while h.result is None and _time.monotonic() < deadline:
        _drain()
        _time.sleep(0.001)
    if h.result is None:
        try:
            w_ps = _queue.peek_ps(h.name)
            if st.process_index == 0:
                coord = (st.coordinator if w_ps is None
                         else w_ps.coordinator)
                coord.withdraw(h.name, 0)
            else:
                st.transport.withdraw(
                    h.name,
                    0 if w_ps is None else w_ps.process_set_id)
        except (OSError, AttributeError):
            pass  # controller unreachable: fall back to local
        grace_dl = _time.monotonic() + float(_os.environ.get(
            "HOROVOD_TPU_WITHDRAW_GRACE", "10"))
        while h.result is None and _time.monotonic() < grace_dl:
            _drain()
            _time.sleep(0.001)
    if h.result is None:
        # Controller never answered the withdrawal: error locally
        # so the name can be reused and the handle doesn't pin
        # the contribution forever.
        _queue.take([h.name])
        h.result = HorovodError(
            f"Collective {h.name} timed out after {timeout:.0f}s "
            f"waiting for the remaining processes (see the "
            f"coordinator's stall warnings for which ranks are "
            f"missing).")


def synchronize(handle: int):
    """Block until the collective completes and return its output
    (≙ horovod_torch_wait_and_clear + synchronize, torch/mpi_ops.py:328-344).
    Raises :class:`HorovodError` if cross-replica validation failed."""
    st = _state.global_state()
    h = st.handle_manager._get(handle)
    if h.result is None:
        if st.multiprocess:
            _wait_mp_result(st, h)
        else:
            _drain()
            h = st.handle_manager._get(handle)
    if h.result is None:
        raise HorovodError(
            f"Collective {h.name} cannot complete: not all replica requests "
            f"were submitted (it would stall).")
    if isinstance(h.result, HorovodError):
        err = h.result
        h.result = ()  # release without re-running the finalizer
        st.handle_manager.synchronize(handle)
        raise err
    return st.handle_manager.synchronize(handle)


def take_async(handle: int):
    """Take a collective's result WITHOUT blocking on device completion.

    :func:`synchronize` calls ``jax.block_until_ready`` — the right
    contract for user code handing buffers to non-JAX consumers, but a
    pipeline bubble for a consumer that immediately feeds the result
    into another XLA program (the backward/communication-overlap step:
    blocking on the reduced buckets before dispatching the optimizer
    apply would serialize exactly the work the overlap hides).  This
    variant drains until the op's kernel is *dispatched* and returns
    the in-flight ``jax.Array`` future; XLA's per-device program order
    guarantees the consumer reads it after the reduction wrote it.

    Multi-process callers keep :func:`synchronize`'s full
    wait-with-withdraw semantics for the CONTROL plane (the response
    must have been broadcast and executed locally — that depends on
    the other processes) but skip the device-completion block, so an
    overlapped mp step can feed each bucket's in-flight reduction
    straight into the optimizer apply.  Raises :class:`HorovodError`
    exactly like synchronize.
    """
    st = _state.global_state()
    h = st.handle_manager._get(handle)
    if h.result is None:
        if st.multiprocess:
            _wait_mp_result(st, h)
        else:
            _drain()
    if h.result is None:
        raise HorovodError(
            f"Collective {h.name} cannot complete: not all replica requests "
            f"were submitted (it would stall).")
    if isinstance(h.result, HorovodError):
        err = h.result
        h.result = ()  # release without re-running the finalizer
        st.handle_manager.synchronize(handle)
        raise err
    return st.handle_manager.take(handle)


def allreduce(tensor, average=None, name: Optional[str] = None, op=None,
              process_set=None):
    """Synchronous allreduce — mean by default, sum with ``average=False``
    (defaults match the reference: tensorflow/__init__.py:49,
    torch/mpi_ops.py:58), or any reduction via ``op`` —
    hvd.Average/Sum/Adasum/Min/Max/Product (the post-v0.13 API; ``op``
    and ``average`` are mutually exclusive — passing both raises);
    ``process_set`` restricts to a rank subset.

    :class:`~horovod_tpu.ops.sparse.IndexedSlices` inputs dispatch to the
    sparse gather-of-(values, indices) path transparently, exactly like
    the reference's IndexedSlices branch (tensorflow/__init__.py:67-78).
    """
    from . import sparse as _sparse

    if isinstance(tensor, _sparse.IndexedSlices) or (
            isinstance(tensor, (list, tuple)) and tensor
            and all(isinstance(t, _sparse.IndexedSlices) for t in tensor)):
        red = _resolve_op(average, op)
        if red not in (ReduceOp.AVERAGE, ReduceOp.SUM):
            raise ValueError(
                f"sparse (IndexedSlices) allreduce supports only "
                f"sum/average — it is a gather of (values, indices), "
                f"reference tensorflow/__init__.py:67-78; got op="
                f"{wire.reduce_op_name(red)}.")
        return _sparse.allreduce(tensor, average=red == ReduceOp.AVERAGE,
                                 name=name, process_set=process_set)
    return synchronize(allreduce_async(tensor, average=average, name=name,
                                       op=op, process_set=process_set))


def allgather(tensor, name: Optional[str] = None, process_set=None):
    """Synchronous allgather along dim 0, rank order."""
    return synchronize(allgather_async(tensor, name=name,
                                       process_set=process_set))


def broadcast(tensor, root_rank: int, name: Optional[str] = None,
              process_set=None):
    """Synchronous broadcast from ``root_rank``."""
    return synchronize(broadcast_async(tensor, root_rank, name=name,
                                       process_set=process_set))
