"""Backward/communication overlap: bucketed-backward training path.

The monolithic train step (``parallel/training.py``) runs
``jax.value_and_grad`` to completion and hands the WHOLE gradient pytree
to one in-program reduction — every collective fires only after the
backward pass has materialized every gradient.  Overlapping the
reduction of layer N's gradients with the backward compute of layer N-1
is the original Horovod throughput story (arXiv:1802.05799) and the
core of fused computation-collective scheduling (arXiv:2305.06942).

This module is that overlap, built on the repo's own steady-state
machinery instead of a new runtime:

* **Bucket plan** — gradient leaves partition into dtype/size buckets
  with the SAME greedy rule as the static path's wire packing
  (:func:`.data.partition_fusion_buckets`, bounded by the coordinator's
  fusion threshold), so each bucket is exactly one coordinator fusion
  group: one pack→reduce→unpack megakernel launch (PR 3), one response
  cache entry group replayed without negotiation (PR 2), one
  error-feedback residual key under quantized wire formats (PR 6).
* **Segmented backward** — when the loss is a :class:`ChainedLoss`
  (a sequence of stages), the step compiles one forward program that
  saves the stage-boundary activations plus one backward program per
  stage (``jax.vjp`` with in-segment rematerialization — the
  ``jax.checkpoint`` decomposition made explicit so the host owns the
  segment boundaries).  Each stage's gradient buckets are handed to the
  dynamic reduction path the moment that stage's backward program is
  *dispatched* — reduce-of-bucket-K pipelines under
  backward-of-bucket-K+1 in the device stream, and the per-bucket
  control plane (negotiation on step one, cache replay after) runs on
  the host while the device is still inside earlier backward segments.
  A plain callable loss keeps one backward program and streams its
  buckets afterwards (control-plane + apply overlap only).
* **Partial cycles** — a training step is now a SEQUENCE of per-bucket
  sub-programs, not one fused cycle.  The response cache needed no
  schema change for this: entries are per-tensor and ``take_ready``
  replays whatever subset is fully hit, so each bucket replays as its
  own fusion plan (memoized per bucket).  Each bucket's submission is
  made atomic against the 5 ms background drain tick
  (``collective._drain_lock``) so a tick can never split one bucket
  into two fused responses — the per-bucket launch count, and under
  int8/int4 the per-bucket quantization blocks and EF residual keys,
  stay deterministic.

**Identity contract** (tests/test_overlap.py): the streamed schedule's parameters are
bitwise identical to the ``serial`` schedule's after any number of
steps — the SAME per-bucket sub-programs dispatched strictly after the
full backward, so only the interleaving differs.  That also covers the
quantized wire formats (``HVD_TPU_COMPRESSION=int8``/``int4``), which
the monolithic static path does not run at all: same bucket partition
⇒ same pow2-scale blocks, same stochastic-rounding ticks, same
per-bucket EF residual keys.  Against the monolithic
``HVD_TPU_OVERLAP=off`` step the gradients are the same bits (the
segmented VJP chain is the same jaxpr AD produces, and the megakernel's
flat psum is the same reduction the in-program bucketed psum runs), so
the first optimizer step is bitwise; from the second step a float32
optimizer with non-zero moments may differ in the last bits, because
the apply is its own XLA program: XLA:CPU (jax 0.9.0) contracts Adam's
``b*m + (1-b)*g`` into FMAs differently when ``g`` is computed in the
program than when it is a program input.  Measured on the 8-device CPU
mesh: at most 3.6e-8 on weights of magnitude 0.47 after 3 Adam steps
and 1.0e-7 after 30 (1-3 ulp of the leaf's largest magnitude);
bfloat16 leaves and SGD stay bitwise.  Pinning the bits would take an
``optimization_barrier`` on the gradients in BOTH programs — i.e. in
the monolithic hot path — which a CPU-only identity does not justify.

Env contract (docs/performance.md, validated at ``hvd.init`` and
carried in the control-plane HELLO env fingerprint like the
compression/topology knobs — the knob selects which compiled programs
a rank runs, so it must be uniform fleet-wide):

  HVD_TPU_OVERLAP=auto|on|off|serial
      auto (default): off wherever this process drives every device
      of the step's mesh — one chip, the chips of one host under one
      process, CPU/virtual-device meshes: the single-program step
      with the in-program bucketed psum has one dispatch a step and
      nothing for the host to pace (measured on four chips: PERF.md
      section 6, PR 32).  Streaming only on accelerator meshes that
      span processes.
      on: bucketed-backward streaming dispatch.
      serial: the same bucketed sub-programs with hard fences —
      reduction strictly after backward (the measurement/identity
      comparator; what a non-overlapped dynamic path would do).
      off: the pre-overlap monolithic static step, unchanged.

Scope: single-process (single-controller SPMD) AND multi-process
builds.  Multi-process negotiation runs at process granularity with
process-local contributions, and the overlapped mp step rides exactly
that contract: the forward/backward programs are the same global-mesh
SPMD programs the single-process schedule compiles, and each bucket's
fusion group is submitted as this process's LOCAL gradient rows —
negotiated over the TCP control plane as a partial cycle (one
coalesced request frame per bucket, atomic against the drain tick),
replayed per-tensor from the response cache on the steady state, and
executed by the mp megakernel (one donated reduce+unpack over the
process mesh per bucket).  ``take_async`` waits for the broadcast
response (control plane) but NOT for device completion, so the
optimizer apply consumes in-flight reductions exactly like
single-process.  The mp overlapped step holds the same identity
contract as the sp one (≡ serial bitwise; monolithic to the measured
bound): same backward jaxprs, and the per-bucket psum over the process
mesh reduces the same contributions the in-program psum reduces.

Named fallbacks (each warns once, increments ``overlap.fallbacks``
and flight-records an ``overlap_fallback`` event carrying the
reason): ``adasum`` (whole-gradient by definition), ``sparse``
(IndexedSlices leaves ship a negotiated-size payload the bucket
planner cannot size), ``sub-mesh`` (a subset mesh must keep its
in-program reduction), ``mp-local-replicas`` (a process holding >1
local replica has no per-process contribution the mp data plane can
carry), ``mp-mesh-order`` (process-mesh/global-mesh device order
skew), ``grad-tree`` and ``nonstatic-compression``.  Plain
multi-process mode is NOT a fallback anymore.
"""

from __future__ import annotations

import collections
import os
import sys
import threading
import time
from dataclasses import dataclass, field
from types import SimpleNamespace
from typing import Any, Callable, List, Optional, Sequence

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import PartitionSpec as P

from .. import telemetry as _telemetry
from .. import trace as _trace
from ..core import state as _state
from ..core.state import REPLICA_AXIS
from ..ops import collective as C
from ..ops.wire import ReduceOp
from .data import _fusion_threshold_bytes, partition_fusion_buckets

try:
    import optax
except Exception:  # pragma: no cover - optax is baked into the image
    optax = None

OVERLAP_ENV = "HVD_TPU_OVERLAP"
_VALID_MODES = ("auto", "on", "off", "serial")

# hvd-telemetry (docs/metrics.md "Backward/communication overlap").
_M_BUCKETS = _telemetry.counter(
    "overlap.buckets_dispatched",
    "gradient buckets handed to the dynamic reduction path")
_M_MP_BUCKETS = _telemetry.counter(
    "overlap.mp_buckets_dispatched",
    "gradient buckets negotiated as multi-process partial cycles "
    "(subset of overlap.buckets_dispatched)")
_M_FALLBACKS = _telemetry.counter(
    "overlap.fallbacks",
    "overlap-mode steps that fell back to the monolithic path")
# Same registry entry as parallel/training.py / parallel/input.py: every
# place the loop blocks feeds one histogram.
_M_HOST_STALL = _telemetry.histogram(
    "host.stall_seconds", "seconds",
    "time the training loop blocked waiting on the input queue")

# hvd-trace regions of one stream step (docs/tracing.md): where the
# host is between two programs of the schedule.  Per program dispatch
# and per bucket, never per tensor.
_R_BACKWARD = _trace.region("stream.backward", "stream")
_R_SUBMIT = _trace.region("stream.submit", "stream")
_R_DRAIN = _trace.region("stream.drain", "stream")
_R_TAKE = _trace.region("stream.take", "stream")
_R_APPLY = _trace.region("stream.apply", "stream")


def overlap_mode() -> str:
    """Normalized ``HVD_TPU_OVERLAP`` value (``1``/``0`` accepted as
    on/off aliases, like the other runtime gates)."""
    v = os.environ.get(OVERLAP_ENV, "auto").strip().lower()
    if v == "1":
        return "on"
    if v == "0":
        return "off"
    return v or "auto"


def validate_env() -> None:
    """Fail ``hvd.init()`` — not the first training step — on a
    malformed overlap knob (same contract as the compression/topology
    knobs; cross-rank uniformity is checked by the HELLO env
    fingerprint, ops/transport.py)."""
    v = os.environ.get(OVERLAP_ENV)
    if v and overlap_mode() not in _VALID_MODES:
        raise ValueError(
            f"{OVERLAP_ENV}={v!r}: expected one of "
            f"{'|'.join(_VALID_MODES)} (1/0 alias on/off)")


def resolve_mode(override: Optional[str], mesh) -> str:
    """Resolve the step builder's overlap schedule: ``"stream"``,
    ``"serial"`` or ``"off"``.  ``auto`` decides from the mesh alone:
    where this process addresses every device of it (one chip, one
    host's chips under one process, any CPU/virtual mesh) the
    monolithic single-program step wins — one dispatch a step with
    the psum inside it, where the stream schedule's program boundaries
    and per-tensor submissions are host time the device waits for.
    Only an accelerator mesh that spans processes keeps the stream
    schedule."""
    mode = (override or overlap_mode()).strip().lower()
    if mode == "1":
        mode = "on"
    elif mode == "0":
        mode = "off"
    if mode not in _VALID_MODES:
        raise ValueError(
            f"overlap={mode!r}: expected one of {'|'.join(_VALID_MODES)}")
    if mode == "auto":
        try:
            devs = list(mesh.devices.flat)
            if len(devs) < 2 or devs[0].platform == "cpu":
                return "off"
            me = jax.process_index()
            if all(d.process_index == me for d in devs):
                return "off"
        except Exception:  # noqa: BLE001 — exotic mesh: stay monolithic
            return "off"
        return "stream"
    if mode == "on":
        return "stream"
    return mode  # "off" | "serial"


def stage_boundary(carry):
    """Bucket-boundary marker: an identity whose forward AND cotangent
    materialize at an ``optimization_barrier`` (its AD rule barriers the
    cotangent too) — the boundary the overlap schedule cuts the
    backward at.  In the monolithic evaluation it reproduces exactly
    the materialization points the segmented schedule gets for free
    from its program boundaries (without it, XLA fuses stage K+1's
    cotangent into stage K's gradient contractions and drifts a ULP
    from the per-program backward)."""
    return jax.lax.optimization_barrier(carry)


class ChainedLoss:
    """Sequentially staged loss — the segmentable form the overlap path
    streams buckets out of.

    ``stages`` is a sequence of ``stage(stage_params, carry, batch)``
    functions: stage 0 receives ``carry=None`` and builds the first
    activation from ``batch``; every later stage maps its predecessor's
    carry (a batch-leading array or pytree of them) to its own; the
    LAST stage returns the scalar per-replica loss.  ``params`` passed
    to the step must be a sequence with one entry (an arbitrary pytree)
    per stage.

    Calling the object evaluates the chain monolithically — exactly
    what the ``HVD_TPU_OVERLAP=off`` step differentiates — with each
    stage wrapped in ``jax.checkpoint``.  The checkpointing is
    load-bearing for the gradients' identity, not just a memory policy:
    the segmented backward programs rematerialize their stage's forward
    from the boundary carry (that is what makes per-stage backward
    programs possible), and XLA:CPU contracts a *saved* activation
    against a cotangent with different fusion decisions than a
    *recomputed* one — observed as 1-ULP drift in ``wo``/``w_out``-style
    gradients.  Checkpointing the monolithic evaluation gives both
    schedules the identical per-stage backward jaxpr, so the two
    schedules' gradients are the same bits.
    """

    def __init__(self, stages: Sequence[Callable]):
        self.stages = list(stages)
        if not self.stages:
            raise ValueError("ChainedLoss needs at least one stage")

    def _check_params(self, params) -> list:
        if not isinstance(params, (list, tuple)) \
                or len(params) != len(self.stages):
            raise ValueError(
                f"ChainedLoss expects params as a sequence with one "
                f"entry per stage ({len(self.stages)}); got "
                f"{type(params).__name__} of length "
                f"{len(params) if isinstance(params, (list, tuple)) else 'n/a'}")
        return list(params)

    def __call__(self, params, batch):
        params = self._check_params(params)
        carry = None
        for i, (f, p) in enumerate(zip(self.stages, params)):
            if i:
                carry = stage_boundary(carry)
            # The params boundary materializes each stage's GRADIENTS
            # at the stage cut (its transpose barriers the param
            # cotangents) — in the segmented schedule they are program
            # outputs, i.e. materialized buffers, and the monolithic
            # backward must pin the same layout to stay bitwise.
            carry = jax.checkpoint(f)(stage_boundary(p), carry, batch)
        return carry


# ---------------------------------------------------------------------------
# Bucket plan
# ---------------------------------------------------------------------------

@dataclass
class _Bucket:
    gi: int                 # global bucket index (stable wire names)
    local_pos: List[int]    # positions within the segment's leaf list
    global_idx: List[int]   # positions within the full flattened tree
    nbytes: int


@dataclass
class _Segment:
    buckets: List[_Bucket] = field(default_factory=list)


@dataclass
class _Plan:
    threshold: int
    segments: List[_Segment]
    n_leaves: int

    @property
    def n_buckets(self) -> int:
        return sum(len(s.buckets) for s in self.segments)


def _build_plan(seg_leaf_avals: List[List[Any]], threshold: int) -> _Plan:
    """Partition each segment's (wire-dtype) leaf avals into dispatch
    buckets with the shared fusion rule.  Buckets never span segments —
    a bucket dispatches the moment its segment's cotangents exist."""
    segments: List[_Segment] = []
    gi = 0
    offset = 0
    for avals in seg_leaf_avals:
        seg = _Segment()
        for local in partition_fusion_buckets(avals, threshold):
            nbytes = sum(
                (int(np.prod(avals[p].shape, dtype=np.int64))
                 if avals[p].shape else 1)
                * jnp.dtype(avals[p].dtype).itemsize for p in local)
            seg.buckets.append(_Bucket(
                gi=gi, local_pos=list(local),
                global_idx=[offset + p for p in local], nbytes=nbytes))
            gi += 1
        segments.append(seg)
        offset += len(avals)
    return _Plan(threshold=threshold, segments=segments, n_leaves=offset)


# ---------------------------------------------------------------------------
# CPU in-flight window (intra-step analogue of training._ThrottledStep)
# ---------------------------------------------------------------------------

def _max_inflight() -> int:
    try:
        return max(1, int(os.environ.get("HVD_TPU_MAX_INFLIGHT", "2")))
    except ValueError:
        return 2


class _InflightWindow:
    """Bound the overlapped step's in-flight sub-programs on CPU meshes
    (same rendezvous-starvation rationale as ``_throttle_on_cpu``:
    the host-platform backend runs every replica's collective on one
    shared pool; stacking unbounded dispatches starves the rendezvous).
    Real TPU meshes never construct one — their pipelining is the
    performance model."""

    def __init__(self, depth: int):
        self._depth = depth
        self._q: collections.deque = collections.deque()
        from ..tuning import actuation as _actuation

        _actuation.register_inflight_window(self)

    def resize(self, depth: int) -> None:
        """hvd-tune live retune: a shrink drains down to the new depth
        on the next ``admit`` — no flush here (the drain tick must never
        block on device results)."""
        self._depth = max(1, int(depth))

    def admit(self, tree) -> None:
        self._q.append(tree)
        while len(self._q) > self._depth:
            popped = self._q.popleft()
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


# ---------------------------------------------------------------------------
# Partial-cycle dispatch (shared with parallel/pipeline.py)
# ---------------------------------------------------------------------------

def dispatch_bucket_segment(prefix: str, seg: _Segment, seg_leaves: List,
                            handles: List[Optional[int]], tl,
                            mp: bool = False) -> None:
    """Hand one gradient segment's buckets to the dynamic path.
    Submission is atomic against the background drain tick, and the
    explicit drain right after dispatches each bucket's megakernel
    immediately — before the next (earlier) backward segment.  The
    1F1B pipeline schedule (parallel/pipeline.py) streams each stage's
    buckets through this same choreography the moment that stage's
    last microbatch backward is dispatched.

    Multi-process: each leaf's contribution is this process's LOCAL
    row of the per-replica gradient (``addressable_data(0)`` — a
    zero-copy view of the shard this process computed; the
    ``mp-local-replicas`` guard pinned one replica per process).  The
    bucket's requests buffer under the drain lock and the drain
    flushes them as ONE coalesced control frame — the partial cycle
    the coordinator negotiates (and, on the steady state, the
    response cache replays) independently of the other buckets still
    inside the backward.  Inputs are not declared donated in mp: the
    local rows share their buffers with the live global gradient
    arrays, and the mp executor's local pack copies them into the
    fusion buffer anyway."""
    for b in seg.buckets:
        tensors = [seg_leaves[p] for p in b.local_pos]
        base = f"{prefix}.g{b.gi}"
        if mp:
            tensors = [t.addressable_data(0) for t in tensors]
        with _R_SUBMIT(bucket=b.gi, tensors=len(tensors),
                       bytes=b.nbytes):
            with C._drain_lock:
                hs = C.grouped_allreduce_async(
                    tensors, op=ReduceOp.SUM, name=base,
                    donate_inputs=not mp)
        with _R_DRAIN(bucket=b.gi):
            C._drain()
        for idx, h in zip(b.global_idx, hs):
            handles[idx] = h
        _M_BUCKETS.inc()
        if mp:
            _M_MP_BUCKETS.inc()
        if tl is not None:
            tl.instant(base, "BUCKET_DISPATCH",
                       args={"bucket": b.gi, "tensors": len(hs),
                             "bytes": b.nbytes})


# ---------------------------------------------------------------------------
# The overlapped step
# ---------------------------------------------------------------------------

_prefix_lock = threading.Lock()
_prefix_counter = 0


def _next_prefix() -> str:
    """Stable per-builder wire-name prefix.  Collective names must be
    identical across steps (the response-cache key) and unique across
    step builders in one process; construction order is part of the
    SPMD program and — like every compiled-program knob — must match
    across ranks: a multi-process build's bucket names negotiate over
    the control plane, so every rank must construct its overlapped
    steps in the same order (user training scripts are SPMD, so they
    do; a divergence is caught by the coordinator's name/shape
    mismatch diagnostics on the first step)."""
    global _prefix_counter
    with _prefix_lock:
        _prefix_counter += 1
        return f"overlap.p{_prefix_counter}"


def _is_cpu_mesh(mesh) -> bool:
    try:
        return mesh.devices.flat[0].platform == "cpu"
    except Exception:  # noqa: BLE001 — exotic mesh: no throttle
        return False


class _OverlapStep:
    """The bucketed-backward train step: a host-driven sequence of
    compiled sub-programs (forward / per-segment backward / per-bucket
    megakernel reduction / optimizer apply) replacing the single jitted
    program of the monolithic path.  Drop-in call signature; builds its
    programs and bucket plan lazily on the first call (the fallback
    checks need concrete trees) and re-plans when the fusion threshold
    changes (the same event that flushes the coordinator's fusion-plan
    memo and the megakernel cache)."""

    def __init__(self, loss_fn, optimizer, mesh, red_op: ReduceOp,
                 fusion_threshold: Optional[int], has_aux: bool,
                 donate: bool, has_state: bool, compression,
                 stream: bool, fallback_builder: Callable[[], Callable]):
        self._loss_fn = loss_fn
        self._optimizer = optimizer
        self._mesh = mesh
        self._red_op = red_op
        self._fusion_threshold = fusion_threshold
        self._has_aux = has_aux
        self._donate = donate
        self._has_state = has_state
        self._compression = compression
        self._stream = stream
        self._fallback_builder = fallback_builder
        self._prefix = _next_prefix()
        self._cpu_mesh = _is_cpu_mesh(mesh)
        self._built = False
        self._fallback_step: Optional[Callable] = None
        self._fallback_reason: Optional[str] = None
        self._plan: Optional[_Plan] = None
        self._segmented = False
        self._mp = False
        self._treedef = None
        self._ctxs: Optional[list] = None  # per-leaf decompress contexts

    # -- introspection (tests / bench) ------------------------------------
    @property
    def overlap_active(self) -> bool:
        return self._fallback_step is None

    @property
    def schedule(self) -> str:
        return "stream" if self._stream else "serial"

    @property
    def bucket_count(self) -> Optional[int]:
        return None if self._plan is None else self._plan.n_buckets

    @property
    def segment_count(self) -> Optional[int]:
        return None if self._plan is None else len(self._plan.segments)

    # -- fallback ----------------------------------------------------------
    def _fall_back(self, reason: str, detail: str):
        """Build the monolithic step instead, leaving the standard
        triple-entry record — one warn line, one ``overlap.fallbacks``
        counter tick and one ``overlap_fallback`` flight event, all
        carrying the NAMED reason (tests assert the lockstep)."""
        print(f"[hvd-overlap] falling back to the monolithic step "
              f"[{reason}]: {detail}", file=sys.stderr)
        _M_FALLBACKS.inc()
        _telemetry.overlap_fallback_event(reason, detail)
        self._fallback_reason = reason
        self._fallback_step = self._fallback_builder()
        return self._fallback_step

    # -- plan / program construction --------------------------------------
    def _effective_threshold(self) -> int:
        """The dispatch-boundary granularity: the step's explicit
        threshold clamped by the coordinator's live one — the
        coordinator's fusion planner packs replayed cycles with ITS
        threshold, so a bucket must never exceed it (it would split
        into two launches and, under quantized formats, re-partition
        the scaling blocks).  Multi-process builds use the state's
        threshold instead: the live coordinator value is rank-0-only
        knowledge, while ``st.fusion_threshold_bytes`` starts from the
        (env-fingerprinted) HOROVOD_FUSION_THRESHOLD and is updated by
        the same fleet-wide hook that retunes the coordinators — the
        bucket partition must be identical on every rank (it is the
        collective program)."""
        st = _state.global_state()
        if st.multiprocess:
            coord = int(st.fusion_threshold_bytes)
        else:
            try:
                coord = int(st.coordinator.fusion_threshold)
            except Exception:  # noqa: BLE001 — no coordinator (size checks)
                coord = _fusion_threshold_bytes()
        if self._fusion_threshold is None:
            return coord
        return min(int(self._fusion_threshold), coord)

    def _wire_aval(self, leaf) -> SimpleNamespace:
        """(shape, WIRE dtype) of one gradient leaf — buckets group by
        the compressed dtype, like the static path's narrow-end-to-end
        packing.  Also records the per-leaf decompress context."""
        dtype = jnp.dtype(leaf.dtype)
        if self._compression is None:
            self._ctxs.append(None)
            return SimpleNamespace(shape=tuple(leaf.shape), dtype=dtype)
        wire, ctx = self._compression.compress(jnp.zeros((1,), dtype))
        if isinstance(ctx, jax.Array):
            raise _NonStaticContext()
        self._ctxs.append(ctx)
        return SimpleNamespace(shape=tuple(leaf.shape),
                               dtype=jnp.dtype(wire.dtype))

    def _compress_tree(self, grads):
        comp = self._compression
        if comp is None:
            return grads
        return jax.tree_util.tree_map(lambda g: comp.compress(g)[0], grads)

    def _build(self, args) -> None:
        self._built = True
        st = _state.global_state()
        if self._red_op == ReduceOp.ADASUM:
            # Adasum never overlaps: its scale-insensitive combination
            # is defined on the WHOLE gradient vector — there is no
            # per-bucket decomposition to stream.
            self._fall_back(
                "adasum",
                "op=Adasum combines the whole gradient vector; no "
                "per-bucket decomposition exists")
            return
        if tuple(self._mesh.devices.flat) != tuple(st.devices):
            self._fall_back(
                "sub-mesh",
                "step mesh is not the global replica mesh; a subset "
                "mesh keeps its in-program reduction")
            return
        self._mp = bool(st.multiprocess)
        if self._mp:
            if st.size != st.process_count:
                # The mp data plane carries exactly ONE contribution
                # per process (ops/collective._mp_global); a process
                # holding several local replicas would need a local
                # pre-reduction the bitwise contract cannot absorb.
                self._fall_back(
                    "mp-local-replicas",
                    f"{st.size} replicas over {st.process_count} "
                    f"processes; the mp data plane reduces one "
                    f"contribution per process")
                return
            mp_mesh = C._mp_kernels()[0]
            if tuple(mp_mesh.devices.flat) != tuple(
                    self._mesh.devices.flat):
                # The reduced buckets come back committed to the
                # process mesh; the apply program runs over the global
                # mesh — they must agree on device order or XLA
                # rejects the mixed device assignment.
                self._fall_back(
                    "mp-mesh-order",
                    "process-mesh device order differs from the "
                    "global replica mesh")
                return
        if self._has_state:
            params, model_state, _opt_state, batch = args
        else:
            (params, _opt_state, batch), model_state = args, None

        self._ctxs = []
        try:
            if (isinstance(self._loss_fn, ChainedLoss)
                    and len(self._loss_fn.stages) >= 2
                    and not self._has_aux and not self._has_state):
                self._build_segmented(params, batch)
            else:
                self._build_unsegmented(params, model_state, batch)
        except _Unbucketable as e:
            self._fall_back(e.reason, str(e))
            return
        except _NonStaticContext:
            self._fall_back(
                "nonstatic-compression",
                "compression context is value-dependent; the decompress "
                "cannot move to a separate apply program")
            return
        self._apply = self._build_apply()

    def _build_unsegmented(self, params, model_state, batch) -> None:
        has_aux, has_state = self._has_aux, self._has_state
        grad_fn = jax.value_and_grad(self._loss_fn,
                                     has_aux=has_aux or has_state)
        self._detect_sparse(grad_fn, params, model_state, batch)
        leaves, self._treedef = jax.tree_util.tree_flatten(params)
        avals = [self._wire_aval(leaf) for leaf in leaves]
        self._leaf_avals = avals
        self._seg_sizes = [len(avals)]
        self._plan = _build_plan([avals], self._effective_threshold())
        self._segmented = False

        def per_replica(params, model_state, batch):
            a = (params, model_state, batch) if has_state \
                else (params, batch)
            out, grads = grad_fn(*a)
            loss = out[0] if (has_aux or has_state) else out
            extra = out[1] if (has_aux or has_state) else None
            grads = self._compress_tree(grads)
            # Report the global mean loss (and pmean aux/state), exactly
            # like the monolithic per_replica.
            loss = jax.lax.pmean(loss, REPLICA_AXIS)
            extra = jax.tree_util.tree_map(
                lambda x: jax.lax.pmean(x, REPLICA_AXIS), extra)
            grads = jax.tree_util.tree_map(lambda g: g[None], grads)
            return loss, grads, extra

        self._grads_program = jax.jit(jax.shard_map(
            per_replica, mesh=self._mesh,
            in_specs=(P(), P(), P(REPLICA_AXIS)),
            out_specs=(P(), P(REPLICA_AXIS), P()), check_vma=False))

    def _detect_sparse(self, grad_fn, params, model_state, batch) -> None:
        """Best-effort trace-time structure probe: IndexedSlices leaves
        (or a grads tree that is not the params tree) cannot bucket —
        their wire payload is negotiated per step.  A loss that cannot
        be abstractly evaluated outside the replica context is assumed
        dense (standard AD cotangents are)."""
        from ..ops.sparse import IndexedSlices

        try:
            a = (params, model_state, batch) if self._has_state \
                else (params, batch)
            out = jax.eval_shape(grad_fn, *a)
        except Exception:  # noqa: BLE001 — collectives in the loss etc.
            return
        grads = out[1]
        flat, tdef = jax.tree_util.tree_flatten(
            grads, is_leaf=lambda g: isinstance(g, IndexedSlices))
        if any(isinstance(g, IndexedSlices) for g in flat):
            raise _Unbucketable(
                "sparse",
                "sparse (IndexedSlices) gradient leaves ship a "
                "negotiated-size payload the bucket planner cannot size")
        if tdef != jax.tree_util.tree_structure(params):
            raise _Unbucketable(
                "grad-tree",
                "gradient tree structure differs from the params tree")

    def _build_segmented(self, params, batch) -> None:
        chain: ChainedLoss = self._loss_fn
        params = chain._check_params(params)
        stages = chain.stages
        S = len(stages)
        self._segmented = True
        leaves, self._treedef = jax.tree_util.tree_flatten(list(params))
        seg_avals: List[List[Any]] = []
        for p in params:
            seg_avals.append([self._wire_aval(leaf)
                              for leaf in jax.tree_util.tree_leaves(p)])
        self._leaf_avals = [a for avals in seg_avals for a in avals]
        self._seg_sizes = [len(a) for a in seg_avals]
        self._plan = _build_plan(seg_avals, self._effective_threshold())

        def fwd(params, batch):
            carries = []
            carry = None
            for f, p in zip(stages[:-1], params[:-1]):
                carry = f(p, carry, batch)
                carries.append(carry)
            loss = stages[-1](params[-1], carry, batch)
            return jax.lax.pmean(loss, REPLICA_AXIS), tuple(carries)

        self._fwd_program = jax.jit(jax.shard_map(
            fwd, mesh=self._mesh, in_specs=(P(), P(REPLICA_AXIS)),
            out_specs=(P(), P(REPLICA_AXIS)), check_vma=False))

        def pr(tree):
            return jax.tree_util.tree_map(lambda x: x[None], tree)

        def make_last(k):
            def bwd(p, carry, batch):
                def f(p, c):
                    return stages[k](p, c, batch)
                out, vjp = jax.vjp(f, p, carry)
                g, ct = vjp(jnp.ones_like(out))
                return pr(self._compress_tree(g)), ct
            return bwd

        def make_mid(k):
            def bwd(p, carry, batch, ct_in):
                def f(p, c):
                    return stages[k](p, c, batch)
                _, vjp = jax.vjp(f, p, carry)
                g, ct = vjp(ct_in)
                return pr(self._compress_tree(g)), ct
            return bwd

        def make_first():
            def bwd(p, batch, ct_in):
                def f(p):
                    return stages[0](p, None, batch)
                _, vjp = jax.vjp(f, p)
                (g,) = vjp(ct_in)
                return pr(self._compress_tree(g))
            return bwd

        sm = jax.shard_map
        R = P(REPLICA_AXIS)
        self._bwd_programs: List[Callable] = [None] * S
        # Stage-boundary carries and cotangents are step-internal
        # single-consumer buffers: donate them so the backward chain
        # runs in-place on real accelerators.
        self._bwd_programs[S - 1] = jax.jit(
            sm(make_last(S - 1), mesh=self._mesh,
               in_specs=(P(), R, R), out_specs=(R, R),
               check_vma=False),
            donate_argnums=(1,))
        for k in range(1, S - 1):
            self._bwd_programs[k] = jax.jit(
                sm(make_mid(k), mesh=self._mesh,
                   in_specs=(P(), R, R, R), out_specs=(R, R),
                   check_vma=False),
                donate_argnums=(1, 3))
        self._bwd_programs[0] = jax.jit(
            sm(make_first(), mesh=self._mesh, in_specs=(P(), R, R),
               out_specs=R, check_vma=False),
            donate_argnums=(2,))

    def _build_apply(self) -> Callable:
        optimizer = self._optimizer
        comp = self._compression
        ctxs = list(self._ctxs or [])
        divide = self._red_op == ReduceOp.AVERAGE

        def apply_body(grads_pr, opt_state, params):
            g = jax.tree_util.tree_map(
                lambda x: jnp.squeeze(x, 0), grads_pr)
            leaves, tdef = jax.tree_util.tree_flatten(g)
            if comp is not None:
                leaves = [comp.decompress(x, ctx)
                          for x, ctx in zip(leaves, ctxs)]
            if divide:
                # The static path's `finish`: divide AFTER decompress in
                # the restored dtype by the f32 replica count — the
                # reductions themselves always ride as SUM.
                denom = jax.lax.psum(jnp.ones((), jnp.float32),
                                     REPLICA_AXIS)
                leaves = [x / denom.astype(x.dtype) for x in leaves]
            g = jax.tree_util.tree_unflatten(tdef, leaves)
            updates, opt_state = optimizer.update(g, opt_state, params)
            return optax.apply_updates(params, updates), opt_state

        donate = (0, 1, 2) if self._donate else (0,)
        # Single-process: reduced buckets are per-replica [size, ...]
        # arrays — each replica squeezes its own row.  Multi-process:
        # the mp megakernel returns REPLICATED [1, ...] tensors (the
        # negotiated local-row shape), so the grads ride in replicated
        # and every replica squeezes the same row; the psum(ones)
        # denominator still counts the world replicas (== processes —
        # the mp-local-replicas guard pinned size == process_count),
        # which is exactly the mp AVERAGE denominator.
        grads_spec = P() if self._mp else P(REPLICA_AXIS)
        return jax.jit(jax.shard_map(
            apply_body, mesh=self._mesh,
            in_specs=(grads_spec, P(), P()), out_specs=(P(), P()),
            check_vma=False), donate_argnums=donate)

    # -- execution ---------------------------------------------------------
    def _submit_segment(self, seg: _Segment, seg_leaves: List,
                        handles: List[Optional[int]], tl) -> None:
        dispatch_bucket_segment(self._prefix, seg, seg_leaves, handles,
                                tl, mp=self._mp)

    def __call__(self, *args):
        if self._fallback_step is not None:
            return self._fallback_step(*args)
        if not self._built:
            self._build(args)
            if self._fallback_step is not None:
                return self._fallback_step(*args)
        thr = self._effective_threshold()
        if thr != self._plan.threshold:
            # Fusion-threshold change (autotune / set_fusion_threshold):
            # the coordinator flushed its plan memo and the megakernel
            # cache; re-partition the dispatch boundaries to match.  The
            # re-used wire names carry new signatures, which the
            # response cache resolves as a program change (flush +
            # renegotiate once).
            self._replan(thr)
        return self._run(args)

    def _replan(self, threshold: int) -> None:
        seg_avals: List[List[Any]] = []
        pos = 0
        for n in self._seg_sizes:
            seg_avals.append(self._leaf_avals[pos:pos + n])
            pos += n
        self._plan = _build_plan(seg_avals, threshold)

    def _run(self, args):
        st = _state.global_state()
        tl = st.timeline
        stream = self._stream
        if self._has_state:
            params, model_state, opt_state, batch = args
        else:
            (params, opt_state, batch), model_state = args, None
        handles: List[Optional[int]] = [None] * self._plan.n_leaves
        window = _InflightWindow(_max_inflight()) if self._cpu_mesh \
            else None
        extra = None

        if self._segmented:
            chain_params = list(params)
            with _R_BACKWARD(program="fwd"):
                loss, carries = self._fwd_program(chain_params, batch)
            segs = self._plan.segments
            S = len(segs)
            staged = []  # serial schedule: submit only after the fence
            ct = None
            for k in range(S - 1, -1, -1):
                with _R_BACKWARD(program="bwd", segment=k):
                    if k == S - 1:
                        g, ct = self._bwd_programs[k](
                            chain_params[k], carries[k - 1], batch)
                    elif k == 0:
                        g = self._bwd_programs[k](chain_params[k], batch,
                                                  ct)
                        ct = None
                    else:
                        g, ct = self._bwd_programs[k](
                            chain_params[k], carries[k - 1], batch, ct)
                if window is not None:
                    window.admit((g, ct))
                seg_leaves = jax.tree_util.tree_leaves(g)
                if stream:
                    self._submit_segment(segs[k], seg_leaves, handles, tl)
                else:
                    staged.append((segs[k], seg_leaves))
            if not stream:
                # "Reduction serialized after backward": the exact
                # symptom docs/performance.md names — fence the whole
                # backward, then dispatch the same buckets.
                for _, seg_leaves in staged:
                    jax.block_until_ready(seg_leaves)
                for seg, seg_leaves in staged:
                    self._submit_segment(seg, seg_leaves, handles, tl)
        else:
            with _R_BACKWARD(program="grads"):
                loss, grads_pr, extra = self._grads_program(
                    params, model_state, batch)
            if window is not None:
                window.admit(grads_pr)
            seg_leaves = jax.tree_util.tree_leaves(grads_pr)
            if not stream:
                jax.block_until_ready(seg_leaves)
            self._submit_segment(self._plan.segments[0], seg_leaves,
                                 handles, tl)

        with _R_TAKE(handles=len(handles)):
            reduced = [C.take_async(h) for h in handles]
            if not stream:
                jax.block_until_ready(reduced)
        with _R_APPLY():
            red_tree = jax.tree_util.tree_unflatten(self._treedef,
                                                    reduced)
            new_params, opt_state = self._apply(red_tree, opt_state,
                                                params)
        if self._has_state:
            return new_params, extra, opt_state, loss
        if self._has_aux:
            return new_params, opt_state, loss, extra
        return new_params, opt_state, loss


class _Unbucketable(Exception):
    """Raised during plan building when the gradient tree cannot take
    the bucketed path; the step falls back to the monolithic program.
    ``reason`` is the short fallback name the telemetry/flight record
    carries (``sparse``, ``grad-tree``)."""

    def __init__(self, reason: str, detail: str):
        super().__init__(detail)
        self.reason = reason


class _NonStaticContext(Exception):
    pass


def make_overlapped_step(loss_fn, optimizer, mesh, red_op: ReduceOp,
                         fusion_threshold: Optional[int], has_aux: bool,
                         donate: bool, has_state: bool, compression,
                         stream: bool,
                         fallback_builder: Callable[[], Callable]):
    """Build the bucketed-backward step (``parallel/training._make_step``
    calls this when the overlap mode resolves on).  ``fallback_builder``
    constructs the monolithic static step for the unbucketable cases
    (Adasum, sparse leaves, subset meshes)."""
    if optax is None:
        return fallback_builder()
    return _OverlapStep(loss_fn, optimizer, mesh, red_op,
                        fusion_threshold, has_aux, donate, has_state,
                        compression, stream, fallback_builder)
