"""Pipeline parallelism: GPipe scan + host-scheduled 1F1B MPMD schedule.

Two generations of the same axis:

* :func:`gpipe` — the original beyond-parity extension (SURVEY.md §2.3
  "Pipeline parallelism: NO"): layer blocks shard over
  :data:`..core.topology.PIPE_AXIS`; a batch is cut into microbatches
  that flow stage-to-stage over ICI via ``lax.ppermute`` inside a
  ``lax.scan`` — the whole schedule is ONE compiled XLA program, so the
  backward pass (reverse scan, reversed permutes) is derived by JAX AD
  and is itself pipelined.  Bubble fraction is the usual
  ``(n_stages - 1) / (m + n_stages - 1)``, and — the cost this module's
  second half deletes — every gradient collective fires only after the
  whole scan, so the bubble ticks sit idle while the reduction waits.

* :func:`make_pipeline_train_step` — the MPMD rebuild (arXiv:2412.14374
  direction; ROADMAP open item 4): instead of one monolithic scan, each
  stage's forward and backward microbatch is its OWN compiled
  executable, dispatched by a host-side scheduler in 1F1B order
  (optionally with interleaved virtual stages).  The per-stage backward
  programs are the segmented-backward substrate the
  backward/communication-overlap step introduced
  (``parallel/overlap.py``: stage-boundary activations, one backward
  program per stage, ``jax.vjp`` with in-segment rematerialization) —
  and each stage's bucketed gradient dispatch rides the SAME
  partial-cycle choreography (:func:`..parallel.overlap.
  dispatch_bucket_segment`): the moment a stage's last microbatch
  backward is dispatched, its fusion groups negotiate/replay through
  the response cache and stream their megakernels into the remaining
  schedule ticks — communication hides in the pipeline bubbles instead
  of serializing after the flush.

Why 1F1B: at equal microbatch count the flush bubble is the same as
GPipe's, but (a) in-flight activation memory is bounded by the stage
depth instead of the microbatch count (``PipelinePlan.peak_activations``
— the property the dryrun tests gate), and (b) each stage finishes its
backwards EARLY (stage ``S-1`` first), so streamed gradient reduction
overlaps the other stages' cooldown (what that buys on the chip: no
cell yet, ROADMAP R3).

Env contract (validated at ``hvd.init``; rides the control-plane HELLO
env fingerprint — the schedule selects which compiled programs a rank
dispatches in which order, so it must be uniform fleet-wide):

  HVD_TPU_PIPELINE_SCHEDULE=1f1b|gpipe
      default 1f1b.  ``gpipe`` runs the SAME per-stage executables in
      all-forwards-then-all-backwards order with the gradient dispatch
      serialized after a full flush fence — the measurement comparator
      and the bitwise-identity reference (same programs, same
      microbatch accumulation order, different interleaving).
  HVD_TPU_PIPELINE_INTERLEAVE=<v>
      default 1.  Interleaved virtual stages: ``v`` must divide the
      stage count; the ``n_stages/v`` executors each own ``v``
      round-robin model chunks, shortening the per-chunk ramp so the
      flush bubble shrinks (gated structurally by the dryrun plan).

**Bitwise contract** (tests/test_pipeline_parallel.py): the 1F1B step's loss and parameters are
bitwise identical to the GPipe-ordered dispatch of the same per-stage
programs — backwards execute in microbatch order at every stage under
both schedules, so the gradient accumulation chains are the same
arithmetic; only the interleaving and the reduction dispatch points
differ.  Against the monolithic reference (``jax.grad`` of the
microbatch-mean loss) the parity is allclose, not bitwise — XLA
compiles per-stage programs with different fusion decisions than one
whole-graph backward (the same ULP story as
``parallel/overlap.ChainedLoss``).

**Sub-mesh placement (mp × pipeline; hvd-fuse)**: pass
``stage_meshes=[mesh_0, ..., mesh_{S-1}]`` (e.g. from
:func:`stage_submeshes`) and each stage's executables compile over its
OWN sub-mesh instead of sharing the global replica mesh — real MPMD
placement: stage *k*'s forward/backward/apply only ever touch stage
*k*'s devices, and the host loop moves boundary carries/cotangents
between sub-meshes with ``device_put``.  A sub-mesh may carry extra
axes beyond :data:`~..core.state.REPLICA_AXIS` (e.g.
:data:`~..core.topology.MODEL_AXIS`), so a stage body can run
tensor-parallel fused closers (``parallel/tensor.py``) inside its own
sub-mesh — the mp × pipeline composition.  Under placement the
per-stage gradient reduction leaves the dynamic bucket path: each
stage gets ONE fused reduce+apply program (in-program ``psum`` over
the stage's replica axis + optimizer update, an
:class:`~..ops.fused.FusedProgram`) dispatched the moment the stage's
last backward is in flight (1F1B) or after the flush fence (the GPipe
comparator) — 1f1b ≡ gpipe stays bitwise under placement because the
programs and accumulation chains are identical, only dispatch points
move.  ``opt_state`` must then be a per-stage sequence (mirroring
``params``), and ``donate`` applies to the backward programs only.
"""

from __future__ import annotations

import collections
import math
import os
import time
from dataclasses import dataclass, field
from types import SimpleNamespace
from typing import Callable, List, NamedTuple, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import NamedSharding
from jax.sharding import PartitionSpec as P

from .. import telemetry as _telemetry
from ..analysis import donation as _donation
from ..core import state as _state
from ..core.state import REPLICA_AXIS
from ..core.topology import MODEL_AXIS, PIPE_AXIS
from ..memory import ledger as _mem
from ..memory import oom as _oom
from ..memory import planner as _mem_planner
from ..ops import fused as _fused

try:
    import optax
except Exception:  # pragma: no cover - optax is baked into the image
    optax = None

SCHEDULE_ENV = "HVD_TPU_PIPELINE_SCHEDULE"
INTERLEAVE_ENV = "HVD_TPU_PIPELINE_INTERLEAVE"
_VALID_SCHEDULES = ("1f1b", "gpipe")

# hvd-telemetry (docs/metrics.md "Pipeline schedule").
_M_MICROBATCHES = _telemetry.counter(
    "pipeline.microbatches",
    "microbatches executed through the MPMD pipeline schedule")
_M_BUBBLE = _telemetry.histogram(
    "pipeline.bubble_seconds", "seconds",
    "host seconds waiting on gradient reductions after the last "
    "schedule tick was dispatched — bubble/communication time NOT "
    "hidden inside the schedule")
_M_INFLIGHT = _telemetry.gauge(
    "pipeline.inflight_activations",
    "peak stage-boundary activations held live by the last schedule")
# hvd-mem: the figure that actually bounds a launch — BYTES, not tensor
# count (a carry count of 9 says nothing about whether 9 carries fit).
_M_INFLIGHT_BYTES = _telemetry.gauge(
    "pipeline.inflight_activation_bytes",
    "peak stage-boundary activation bytes held live by the last "
    "schedule (the 1F1B-vs-GPipe memory bound, in bytes)")


def _nearest_divisors(n: int, m: int) -> Tuple[int, int]:
    """The divisors of ``n`` nearest to ``m`` from below and above —
    the suggestion surface for schedule-shape errors."""
    lo = next((k for k in range(min(m, n), 0, -1) if n % k == 0), 1)
    hi = next((k for k in range(max(m, 1), n + 1) if n % k == 0), n)
    return lo, hi


def _indivisible_message(what: str, axis: int, m: int) -> str:
    lo, hi = _nearest_divisors(axis, m)
    suggest = f"{lo}" if lo == hi else f"{lo} or {hi}"
    return (f"{what} axis of size {axis} is not divisible by "
            f"num_microbatches={m}; nearest valid counts: {suggest}")


def schedule_env() -> str:
    return (os.environ.get(SCHEDULE_ENV, "1f1b").strip().lower()
            or "1f1b")


def interleave_env() -> int:
    v = os.environ.get(INTERLEAVE_ENV, "1").strip() or "1"
    try:
        return int(v)
    except ValueError:
        # Same named-knob contract as validate_env — the public dryrun
        # path (hvd.schedule_plan with no init) reads the env directly.
        raise ValueError(
            f"{INTERLEAVE_ENV}={v!r}: expected a positive integer "
            f"(virtual stages per pipeline executor)") from None


def validate_env() -> None:
    """Fail ``hvd.init()`` — not the first pipeline step — on a
    malformed schedule knob (same contract as the overlap/compression
    knobs; cross-rank uniformity is checked by the HELLO env
    fingerprint, ops/transport.py)."""
    v = os.environ.get(SCHEDULE_ENV)
    if v and schedule_env() not in _VALID_SCHEDULES:
        raise ValueError(
            f"{SCHEDULE_ENV}={v!r}: expected one of "
            f"{'|'.join(_VALID_SCHEDULES)}")
    iv = os.environ.get(INTERLEAVE_ENV)
    if iv:
        try:
            ok = int(iv) >= 1
        except ValueError:
            ok = False
        if not ok:
            raise ValueError(
                f"{INTERLEAVE_ENV}={iv!r}: expected a positive integer "
                f"(virtual stages per pipeline executor)")


# ---------------------------------------------------------------------------
# Schedule plan: the dryrun surface (shape gated without hardware)
# ---------------------------------------------------------------------------

class Action(NamedTuple):
    """One schedule slot: dispatch ``phase`` (``"F"``/``"B"``) of
    microbatch ``mb`` at pipeline stage ``stage``."""

    phase: str
    stage: int
    mb: int


@dataclass
class PipelinePlan:
    """A fully resolved dispatch schedule.

    ``ticks`` is the deterministic host dispatch order: at tick ``t``
    every listed action is handed to the device stream (one executable
    dispatch each); data dependencies always point to earlier ticks.
    ``bubble_ticks``/``bubble_fraction`` count executor-idle slots
    (an executor with remaining work but no ready action), and
    ``peak_activations`` the maximum number of stage-boundary carries
    live at once — the memory bound 1F1B holds at the stage depth
    while GPipe grows it with the microbatch count.
    """

    n_stages: int
    num_microbatches: int
    schedule: str
    interleave: int
    ticks: List[List[Action]] = field(default_factory=list)
    bubble_ticks: int = 0
    peak_activations: int = 0

    @property
    def n_executors(self) -> int:
        return self.n_stages // self.interleave

    @property
    def total_ticks(self) -> int:
        return len(self.ticks)

    @property
    def bubble_fraction(self) -> float:
        slots = self.n_executors * max(self.total_ticks, 1)
        return self.bubble_ticks / slots


def _resolve_schedule(schedule: Optional[str], interleave: Optional[int],
                      n_stages: int) -> Tuple[str, int]:
    sched = (schedule or schedule_env()).strip().lower()
    if sched not in _VALID_SCHEDULES:
        raise ValueError(
            f"pipeline schedule {sched!r}: expected one of "
            f"{'|'.join(_VALID_SCHEDULES)} ({SCHEDULE_ENV})")
    v = interleave if interleave is not None else interleave_env()
    v = int(v)
    if v < 1:
        raise ValueError(f"interleave={v}: must be >= 1")
    if n_stages % v != 0:
        lo, hi = _nearest_divisors(n_stages, v)
        suggest = f"{lo}" if lo == hi else f"{lo} or {hi}"
        raise ValueError(
            f"interleave={v} does not divide n_stages={n_stages}; "
            f"nearest valid interleave depths: {suggest}")
    return sched, v


def _stage_action_list(schedule: str, S: int, m: int, s: int) -> list:
    """Stage ``s``'s action order.  GPipe: all forwards, then all
    backwards.  1F1B: ``min(m, S-1-s)`` warmup forwards, a steady
    one-forward-one-backward phase, then the backward cooldown.
    Backwards run in microbatch order under BOTH schedules — the
    bitwise gradient-accumulation contract."""
    if schedule == "gpipe":
        return ([Action("F", s, i) for i in range(m)]
                + [Action("B", s, i) for i in range(m)])
    w = min(m, S - 1 - s)
    acts = [Action("F", s, i) for i in range(w)]
    for k in range(m - w):
        acts.append(Action("F", s, w + k))
        acts.append(Action("B", s, k))
    acts += [Action("B", s, i) for i in range(m - w, m)]
    return acts


def schedule_plan(n_stages: int, num_microbatches: int,
                  schedule: Optional[str] = None,
                  interleave: Optional[int] = None) -> PipelinePlan:
    """Resolve the dispatch schedule for ``n_stages`` × ``m``
    microbatches — the ``HVD_TPU_VIRTUAL_SLICES``-style dryrun surface:
    tests and operators gate the schedule SHAPE (tick order, bubble
    slots, peak activation memory) with no hardware and no jax
    dispatch.

    The plan is built by a deterministic event simulation: each of the
    ``n_stages/interleave`` executors owns its round-robin virtual
    stages and, every tick, fires the first owned stage whose next
    queued action (the per-stage 1F1B/GPipe order) has its
    dependencies satisfied by earlier ticks.  Forward of ``(s, i)``
    needs forward ``(s-1, i)``; backward needs the stage's own forward
    plus backward ``(s+1, i)``.
    """
    S, m = int(n_stages), int(num_microbatches)
    if S < 1 or m < 1:
        raise ValueError(f"n_stages={S} and num_microbatches={m} must "
                         f"be >= 1")
    sched, v = _resolve_schedule(schedule, interleave, S)
    D = S // v
    owners = {d: [d + j * D for j in range(v)] for d in range(D)}
    queues = {s: collections.deque(_stage_action_list(sched, S, m, s))
              for s in range(S)}
    fwd_done, bwd_done = set(), set()
    plan = PipelinePlan(n_stages=S, num_microbatches=m, schedule=sched,
                        interleave=v)

    def ready(a: Action) -> bool:
        if a.phase == "F":
            return a.stage == 0 or (a.stage - 1, a.mb) in fwd_done
        return ((a.stage, a.mb) in fwd_done
                and (a.stage == S - 1
                     or (a.stage + 1, a.mb) in bwd_done))

    live = 0
    while any(queues.values()):
        fired: List[Action] = []
        for d in range(D):
            for s in owners[d]:
                q = queues[s]
                if q and ready(q[0]):
                    fired.append(q.popleft())
                    break
            else:
                if any(queues[s] for s in owners[d]):
                    plan.bubble_ticks += 1
        if not fired:
            raise RuntimeError(
                f"pipeline schedule wedged: no ready action with "
                f"{sum(map(len, queues.values()))} pending "
                f"(schedule={sched}, S={S}, m={m}, v={v})")
        for a in fired:
            if a.phase == "F":
                fwd_done.add((a.stage, a.mb))
                if a.stage < S - 1:
                    live += 1  # carry born (consumed by B of stage+1)
            else:
                bwd_done.add((a.stage, a.mb))
                if a.stage > 0:
                    live -= 1  # carry (stage-1, mb) consumed
            plan.peak_activations = max(plan.peak_activations, live)
        plan.ticks.append(fired)
    return plan


# ---------------------------------------------------------------------------
# Sub-mesh placement (mp × pipeline)
# ---------------------------------------------------------------------------

def stage_submeshes(n_stages: int, *, mesh=None, model: int = 1
                    ) -> Tuple[jax.sharding.Mesh, ...]:
    """Split a replica mesh's devices into ``n_stages`` contiguous
    sub-meshes — the standard placement for
    ``make_pipeline_train_step(..., stage_meshes=...)``.

    Each sub-mesh gets ``devices/n_stages`` devices shaped
    ``(replica, model)``: axis :data:`~..core.state.REPLICA_AXIS` plus,
    when ``model > 1``, :data:`~..core.topology.MODEL_AXIS` — so a
    stage body can run tensor-parallel fused closers on its own
    devices (the mp × pipeline composition).  Contiguous splits keep
    each stage inside one ICI neighborhood on real slice topologies.
    """
    mesh = mesh or _state.mesh()
    devs = list(mesh.devices.flat)
    S, v = int(n_stages), int(model)
    if S < 1 or v < 1:
        raise ValueError(f"n_stages={S} and model={v} must be >= 1")
    if len(devs) % S != 0:
        raise ValueError(
            f"{len(devs)} devices do not split into {S} equal stage "
            f"sub-meshes")
    per = len(devs) // S
    if per % v != 0:
        raise ValueError(
            f"stage sub-mesh of {per} devices is not divisible by "
            f"model={v}")
    out = []
    for s in range(S):
        block = np.asarray(devs[s * per:(s + 1) * per])
        if v == 1:
            out.append(jax.sharding.Mesh(block, (REPLICA_AXIS,)))
        else:
            out.append(jax.sharding.Mesh(
                block.reshape(per // v, v), (REPLICA_AXIS, MODEL_AXIS)))
    return tuple(out)


def _validate_stage_meshes(stage_meshes, n_stages: int) -> tuple:
    meshes = tuple(stage_meshes)
    if len(meshes) != n_stages:
        raise ValueError(
            f"stage_meshes has {len(meshes)} meshes for {n_stages} "
            f"stages — one sub-mesh per stage")
    sizes = set()
    for k, mk in enumerate(meshes):
        if REPLICA_AXIS not in mk.axis_names:
            raise ValueError(
                f"stage_meshes[{k}] has axes {mk.axis_names!r}; every "
                f"stage sub-mesh needs the {REPLICA_AXIS!r} replica "
                f"axis (extra axes like {MODEL_AXIS!r} are fine)")
        sizes.add(int(mk.shape[REPLICA_AXIS]))
    if len(sizes) > 1:
        raise ValueError(
            f"stage sub-meshes disagree on replica count "
            f"({sorted(sizes)}): boundary carries are sharded over the "
            f"replica axis, so every stage needs the same count")
    return meshes


def _to_mesh(tree, mesh, spec):
    """Move a pytree onto ``mesh`` with ``spec`` on every leaf — the
    host-side boundary transfer between stage sub-meshes."""
    s = NamedSharding(mesh, spec)
    return jax.tree_util.tree_map(lambda x: jax.device_put(x, s), tree)


# ---------------------------------------------------------------------------
# The MPMD pipeline train step
# ---------------------------------------------------------------------------

class _AotProgram:
    """AOT-compile-on-first-dispatch wrapper around one jitted stage
    program (hvd-mem): the first call lowers + compiles with the
    concrete arguments — the SAME executable ``jit`` would have built,
    one compile total — then harvests ``compiled.memory_analysis()``
    into the planner's per-mesh table (where the backend implements the
    query), and every dispatch runs inside the OOM guard naming this
    executable, so a pipeline-stage RESOURCE_EXHAUSTED dumps forensics
    instead of a bare traceback.  A shape change (or any non-OOM
    compiled-call failure) falls back to the jit wrapper, which
    recompiles transparently — semantics identical to plain jit."""

    __slots__ = ("name", "_fn", "_compiled", "_donate")

    def __init__(self, name: str, fn, donate: Tuple[int, ...] = ()) -> None:
        self.name = name
        self._fn = fn
        self._compiled = None
        # hvd-race donation sanitizer: the stage's donated positions —
        # every dispatch routes through the registry so a stale
        # re-dispatch of a consumed activation/state buffer raises a
        # DonationError naming this stage program (the bug class the
        # jit-fallback-after-consumed fix below closed by hand).
        self._donate = tuple(donate)

    def __call__(self, *args):
        with _oom.guard(self.name):
            if self._compiled is None:
                try:
                    compiled = self._fn.lower(*args).compile()
                    _mem_planner.record_compiled(self.name, compiled)
                    self._compiled = compiled
                except Exception:  # noqa: BLE001 — AOT lowering is an
                    self._compiled = False  # optimization, jit is the
                    # semantic baseline
            if self._compiled:
                try:
                    return _donation.guard_dispatch(
                        self.name, self._compiled, args, self._donate)
                except Exception as e:  # noqa: BLE001 — see below
                    if _oom.is_resource_exhausted(e):
                        raise
                    if isinstance(e, _donation.DonationError):
                        # The sanitizer caught a stale donated input
                        # BEFORE dispatch; the jit fallback would read
                        # the same dead buffers and mask the named
                        # diagnostic with XLA's deletion error.
                        raise
                    # A RUNTIME failure after XLA consumed the donated
                    # inputs must surface, not retry: the jit fallback
                    # would read deleted buffers and mask the original
                    # error (the ops/collective.py consumed-check
                    # convention).  Shape mismatches fail BEFORE
                    # dispatch — inputs intact — and hand over to jit
                    # PERMANENTLY: jit's own cache then serves every
                    # recurring shape, where re-arming the AOT path
                    # would pay a fresh XLA compile per A/B shape
                    # alternation (e.g. an epoch-end partial
                    # microbatch) that plain jit never pays.
                    if any(isinstance(a, jax.Array) and a.is_deleted()
                           for a in jax.tree_util.tree_leaves(args)):
                        raise
                    self._compiled = False
            return self._fn(*args)


class _PipelineStep:
    """Host-scheduled MPMD pipeline train step: per-stage compiled
    forward/backward microbatch executables dispatched in
    ``PipelinePlan`` order, per-stage gradient accumulation folded into
    the backward programs, and each stage's bucketed reduction streamed
    as partial cycles the moment its last backward is dispatched
    (``schedule="1f1b"``) or serialized after a flush fence
    (``schedule="gpipe"`` — the comparator leg).  Programs build
    lazily on the first call (microbatch shapes need a concrete
    batch)."""

    def __init__(self, chain, optimizer, mesh, num_microbatches: int,
                 schedule: str, interleave: int, average: bool,
                 fusion_threshold: Optional[int], donate: bool,
                 stage_meshes=None):
        from .overlap import ChainedLoss, _next_prefix

        if optax is None:  # pragma: no cover - optax baked into image
            raise RuntimeError("make_pipeline_train_step needs optax")
        if not isinstance(chain, ChainedLoss):
            chain = ChainedLoss(list(chain))
        if len(chain.stages) < 2:
            raise ValueError(
                "make_pipeline_train_step needs at least 2 stages; a "
                "single-stage loss trains faster through "
                "make_train_step")
        self._chain = chain
        self._optimizer = optimizer
        self._S = len(chain.stages)
        self._stage_meshes = None if stage_meshes is None else \
            _validate_stage_meshes(stage_meshes, self._S)
        if self._stage_meshes is not None:
            # Placed mode never touches the global replica mesh; keep a
            # reference mesh for sizing (batch divisibility = the
            # per-stage replica count).
            self._mesh = self._stage_meshes[0]
        else:
            self._mesh = mesh or _state.mesh()
        self._m = int(num_microbatches)
        self._average = average
        self._fusion_threshold = fusion_threshold
        self._donate = donate
        from .overlap import _is_cpu_mesh

        # Data-parallel width: the replica axis alone (a placed
        # sub-mesh may carry a model axis on top).
        self._replicas = int(self._mesh.shape[REPLICA_AXIS]) \
            if REPLICA_AXIS in self._mesh.axis_names \
            else int(self._mesh.devices.size)
        self._plan = schedule_plan(self._S, self._m, schedule, interleave)
        self._prefix = _next_prefix()
        self._built = False
        self._bucket_plan = None
        self._cpu_mesh = _is_cpu_mesh(self._mesh)

    # -- introspection (tests / bench) ------------------------------------
    @property
    def plan(self) -> PipelinePlan:
        return self._plan

    @property
    def schedule(self) -> str:
        return self._plan.schedule

    @property
    def bucket_count(self) -> Optional[int]:
        return None if self._bucket_plan is None \
            else self._bucket_plan.n_buckets

    @property
    def stage_meshes(self) -> Optional[tuple]:
        """The per-stage placement, or ``None`` when every stage shares
        the global replica mesh."""
        return self._stage_meshes

    @property
    def placed(self) -> bool:
        return self._stage_meshes is not None

    # -- build -------------------------------------------------------------
    def _check_batch(self, batch) -> None:
        n = self._replicas
        for leaf in jax.tree_util.tree_leaves(batch):
            axis = int(leaf.shape[0])
            if axis % self._m != 0:
                raise ValueError(_indivisible_message("batch", axis,
                                                      self._m))
            if axis % n != 0:
                raise ValueError(
                    f"batch axis of size {axis} is not divisible by "
                    f"the replica count {n} (the data-parallel shard)")
            if (axis // n) % self._m != 0:
                raise ValueError(
                    f"batch axis {axis} shards to {axis // n} rows per "
                    f"replica; " + _indivisible_message(
                        "per-replica batch", axis // n, self._m))

    def _build(self, params, batch) -> None:
        from .data import _fusion_threshold_bytes
        from .overlap import _build_plan

        self._built = True
        st = _state.global_state()
        if st.multiprocess:
            raise ValueError(
                "make_pipeline_train_step is single-process "
                "(single-controller SPMD) in this build; multi-process "
                "pipeline scheduling composes with the mp overlap path "
                "in a later round (docs/performance.md).")
        params = self._chain._check_params(params)
        self._check_batch(batch)
        leaves, self._treedef = jax.tree_util.tree_flatten(list(params))
        if self._stage_meshes is None:
            seg_avals = [[SimpleNamespace(shape=tuple(x.shape),
                                          dtype=jnp.dtype(x.dtype))
                          for x in jax.tree_util.tree_leaves(p)]
                         for p in params]
            thr = self._fusion_threshold
            if thr is None:
                try:
                    thr = int(st.coordinator.fusion_threshold)
                except Exception:  # noqa: BLE001 — size-check contexts
                    thr = _fusion_threshold_bytes()
            self._bucket_plan = _build_plan(seg_avals, int(thr))
        self._preflight(params, batch)
        self._build_programs()
        self._apply = self._build_apply(params)

    def _preflight(self, params, batch) -> None:
        """hvd-mem pre-flight (docs/memory.md): size the schedule's
        peak carries via ``jax.eval_shape`` over the stage chain — no
        compute, no compile — and WARN before the first dispatch when
        activations + stage params + gradient accumulators exceed the
        advertised per-rank HBM capacity.  Best-effort: a stage whose
        body resists shape abstraction skips the check, never the
        build."""
        if _oom.advertised_capacity() is None:
            return
        try:
            m = self._m

            def sds_nbytes(tree) -> int:
                total = 0
                for leaf in jax.tree_util.tree_leaves(tree):
                    total += int(jnp.dtype(leaf.dtype).itemsize) * int(
                        math.prod(leaf.shape) or 1)
                return total

            def mb(x):
                return jax.ShapeDtypeStruct(
                    (int(x.shape[0]) // m,) + tuple(x.shape[1:]),
                    x.dtype)

            mb_batch = jax.tree_util.tree_map(mb, batch)
            stages = self._chain.stages
            carry = jax.eval_shape(
                lambda p, b: stages[0](p, None, b), params[0], mb_batch)
            max_carry = sds_nbytes(carry)
            for k in range(1, self._S - 1):
                carry = jax.eval_shape(
                    lambda p, c, b, k=k: stages[k](p, c, b),
                    params[k], carry, mb_batch)
                max_carry = max(max_carry, sds_nbytes(carry))
            world = int(self._mesh.devices.size)
            pbytes = sum(_mem.tree_nbytes(p) for p in params)
            # Per-DEVICE figure vs the per-device capacity: carries
            # and gradient accumulators shard over the replica axis
            # (global/world per device); params are replicated (full
            # copy per device).
            predicted = (self._plan.peak_activations * max_carry
                         // max(1, world) + 2 * pbytes)
            _oom.preflight_warn(
                predicted, "make_pipeline_train_step",
                f"{self._plan.peak_activations} peak carries x "
                f"{max_carry} B / {world} devices + stage params + "
                f"accumulators ({self._plan.schedule}, m={m})")
        except Exception:  # noqa: BLE001 — pre-flight must never
            pass           # break a build eval_shape cannot model

    def _build_programs(self) -> None:
        stages = self._chain.stages
        S, m = self._S, self._m
        sm = jax.shard_map
        R = P(REPLICA_AXIS)

        def mesh_of(k: int):
            # Placed: stage k's executables live on stage k's sub-mesh.
            if self._stage_meshes is not None:
                return self._stage_meshes[k]
            return self._mesh

        def mb_slice(batch, i):
            def sl(x):
                xs = x.reshape((m, x.shape[0] // m) + x.shape[1:])
                return jax.lax.dynamic_index_in_dim(xs, i, keepdims=False)
            return jax.tree_util.tree_map(sl, batch)

        def pr(tree):
            return jax.tree_util.tree_map(lambda x: x[None], tree)

        def acc_add(acc, g):
            return jax.tree_util.tree_map(jnp.add, acc, g)

        # Forward programs: one per stage, microbatch index traced so
        # every microbatch reuses ONE executable per stage.
        def make_fwd(k):
            def fwd(p, carry, batch, i):
                return stages[k](p, carry, mb_slice(batch, i))
            return fwd

        def fwd0(p, batch, i):
            return stages[0](p, None, mb_slice(batch, i))

        def fwd_last(p, carry, batch, i):
            loss = stages[S - 1](p, carry, mb_slice(batch, i))
            return jax.lax.pmean(loss, REPLICA_AXIS)

        self._fwd: List[Callable] = [None] * S
        self._fwd[0] = _AotProgram("pipeline/F0", jax.jit(
            sm(fwd0, mesh=mesh_of(0), in_specs=(P(), R, P()),
               out_specs=R, check_vma=False)))
        for k in range(1, S - 1):
            self._fwd[k] = _AotProgram(f"pipeline/F{k}", jax.jit(
                sm(make_fwd(k), mesh=mesh_of(k),
                   in_specs=(P(), R, R, P()), out_specs=R,
                   check_vma=False)))
        self._fwd[S - 1] = _AotProgram(f"pipeline/F{S - 1}", jax.jit(
            sm(fwd_last, mesh=mesh_of(S - 1), in_specs=(P(), R, R, P()),
               out_specs=P(), check_vma=False)))

        # Backward programs: jax.vjp with in-segment rematerialization
        # (the overlap substrate), gradient ACCUMULATION folded in (the
        # `acc` variants donate and replace the running sum — one
        # dispatch per action, no separate eager adds).  Backwards run
        # in microbatch order, so `acc` chains are the same arithmetic
        # under every schedule.
        def make_bwd_last(with_acc):
            def bwd(p, carry, batch, i, *acc):
                def f(p, c):
                    return stages[S - 1](p, c, mb_slice(batch, i))
                out, vjp = jax.vjp(f, p, carry)
                g, ct = vjp(jnp.ones_like(out))
                g = pr(g)
                if with_acc:
                    g = acc_add(acc[0], g)
                return g, ct
            return bwd

        def make_bwd_mid(k, with_acc):
            def bwd(p, carry, batch, i, ct_in, *acc):
                def f(p, c):
                    return stages[k](p, c, mb_slice(batch, i))
                _, vjp = jax.vjp(f, p, carry)
                g, ct = vjp(ct_in)
                g = pr(g)
                if with_acc:
                    g = acc_add(acc[0], g)
                return g, ct
            return bwd

        def make_bwd_first(with_acc):
            def bwd(p, batch, i, ct_in, *acc):
                def f(p):
                    return stages[0](p, None, mb_slice(batch, i))
                _, vjp = jax.vjp(f, p)
                (g,) = vjp(ct_in)
                g = pr(g)
                if with_acc:
                    g = acc_add(acc[0], g)
                return g
            return bwd

        def jit_b(name, k, fn, in_specs, out_specs, donate):
            return _AotProgram(name, jax.jit(
                sm(fn, mesh=mesh_of(k), in_specs=in_specs,
                   out_specs=out_specs, check_vma=False),
                donate_argnums=donate), donate=donate)

        self._bwd: List[Callable] = [None] * S
        self._bwd_acc: List[Callable] = [None] * S
        self._bwd[S - 1] = jit_b(f"pipeline/B{S - 1}", S - 1,
                                 make_bwd_last(False),
                                 (P(), R, R, P()), (R, R), (1,))
        self._bwd_acc[S - 1] = jit_b(f"pipeline/B{S - 1}acc", S - 1,
                                     make_bwd_last(True),
                                     (P(), R, R, P(), R), (R, R), (1, 4))
        for k in range(1, S - 1):
            self._bwd[k] = jit_b(f"pipeline/B{k}", k,
                                 make_bwd_mid(k, False),
                                 (P(), R, R, P(), R), (R, R), (1, 4))
            self._bwd_acc[k] = jit_b(f"pipeline/B{k}acc", k,
                                     make_bwd_mid(k, True),
                                     (P(), R, R, P(), R, R), (R, R),
                                     (1, 4, 5))
        self._bwd[0] = jit_b("pipeline/B0", 0, make_bwd_first(False),
                             (P(), R, P(), R), R, (3,))
        self._bwd_acc[0] = jit_b("pipeline/B0acc", 0,
                                 make_bwd_first(True),
                                 (P(), R, P(), R, R), R, (3, 4))

        self._loss_mean = jax.jit(lambda xs: jnp.mean(jnp.stack(xs)))
        # Per-microbatch index constants, built once: the tick loop is
        # the dispatch critical path, and S*m fresh host→device
        # transfers per step would sit right on it.
        self._mb_idx = [jnp.asarray(i, jnp.int32) for i in range(m)]

    def _build_apply(self, params) -> Optional[Callable]:
        optimizer = self._optimizer
        average = self._average
        m = self._m

        def scale(g, opt_state, stage_params):
            leaves, tdef = jax.tree_util.tree_flatten(g)
            # Accumulated as RAW per-microbatch per-replica sums; the
            # mean-loss gradient divides by microbatches × replicas
            # (exactly the monolithic mean-loss denominator).
            denom = jnp.float32(m)
            if average:
                denom = denom * jax.lax.psum(jnp.ones((), jnp.float32),
                                             REPLICA_AXIS)
            leaves = [x / denom.astype(x.dtype) for x in leaves]
            g = jax.tree_util.tree_unflatten(tdef, leaves)
            updates, opt_state = optimizer.update(g, opt_state,
                                                  stage_params)
            return optax.apply_updates(stage_params, updates), opt_state

        if self._stage_meshes is None:
            def apply_body(grads_pr, opt_state, prm):
                g = jax.tree_util.tree_map(
                    lambda x: jnp.squeeze(x, 0), grads_pr)
                return scale(g, opt_state, prm)

            donate = (0, 1, 2) if self._donate else (0,)
            return jax.jit(jax.shard_map(
                apply_body, mesh=self._mesh,
                in_specs=(P(REPLICA_AXIS), P(), P()),
                out_specs=(P(), P()), check_vma=False),
                donate_argnums=donate)

        # Placed: one fused reduce+apply program PER STAGE on the
        # stage's own sub-mesh — the cross-replica psum happens inside
        # the same executable as the optimizer update (hvd-fuse), so
        # the 1F1B scheduler can dispatch it the moment the stage's
        # last backward is in flight and the reduction streams into
        # the other stages' remaining ticks.
        def apply_body(grads_pr, opt_state, prm):
            g = jax.tree_util.tree_map(
                lambda x: jnp.squeeze(x, 0), grads_pr)
            g = jax.lax.psum(g, REPLICA_AXIS)
            return scale(g, opt_state, prm)

        self._apply_s = []
        for k, mk in enumerate(self._stage_meshes):
            jitted = jax.jit(jax.shard_map(
                apply_body, mesh=mk,
                in_specs=(P(REPLICA_AXIS), P(), P()),
                out_specs=(P(), P()), check_vma=False))
            launch_bytes = sum(
                _mem_planner.fused_group_bytes(tuple(leaf.shape), 1,
                                               dtype=leaf.dtype)
                for leaf in jax.tree_util.tree_leaves(params[k]))
            self._apply_s.append(_fused.FusedProgram(
                f"pipeline/apply{k}", jitted, mesh=mk, chunks=1,
                launch_bytes=launch_bytes))
        return None

    # -- execution ---------------------------------------------------------
    def __call__(self, params, opt_state, batch):
        if not self._built:
            self._build(params, batch)
        if self._stage_meshes is not None:
            if (not isinstance(opt_state, (list, tuple))
                    or len(opt_state) != self._S):
                raise ValueError(
                    "stage_meshes placement needs a PER-STAGE opt_state "
                    "sequence (e.g. [optimizer.init(p) for p in "
                    f"params]); got {type(opt_state).__name__} for "
                    f"{self._S} stages")
            params = [_to_mesh(p, mk, P())
                      for p, mk in zip(params, self._stage_meshes)]
            opt_state = [_to_mesh(o, mk, P())
                         for o, mk in zip(opt_state, self._stage_meshes)]
        return self._run(list(params), opt_state, batch)

    def _run(self, params, opt_state, batch):
        from .overlap import (_InflightWindow, _max_inflight,
                              dispatch_bucket_segment)

        st = _state.global_state()
        tl = st.timeline
        S, m = self._S, self._m
        plan = self._plan
        stream = plan.schedule == "1f1b"
        meshes = self._stage_meshes
        R = P(REPLICA_AXIS)
        if meshes is not None:
            # Each stage reads microbatches from its own sub-mesh copy
            # of the batch (one transfer per stage per step, off the
            # per-tick critical path).
            batches = [_to_mesh(batch, mk, R) for mk in meshes]
            applied: List = [None] * S
        window = _InflightWindow(_max_inflight()) if self._cpu_mesh \
            else None
        carries = {}          # (stage, mb) -> boundary activation
        carry_nb = {}         # (stage, mb) -> ledger bytes (hvd-mem)
        cts = {}              # (stage, mb) -> cotangent from stage's B
        accs: List = [None] * S
        losses: List = [None] * m
        handles: List[Optional[int]] = [None] * (
            0 if self._bucket_plan is None else self._bucket_plan.n_leaves)
        live = peak = 0
        live_b = peak_b = 0
        mem_on = _mem.enabled()

        def born(key, out):
            # A carry was born: count it AND charge its bytes to the
            # ledger (pipeline.activations) — the figure that actually
            # bounds the schedule (peak carries x carry size).
            nonlocal live_b, peak_b
            carries[key] = out
            if mem_on:
                nb = _mem.tree_nbytes(out)
                carry_nb[key] = nb
                live_b += nb
                peak_b = max(peak_b, live_b)
                _mem.ledger.alloc("pipeline.activations", nb)

        def consumed(key):
            nonlocal live_b
            out = carries.pop(key)
            nb = carry_nb.pop(key, 0)
            if nb:
                live_b -= nb
                _mem.ledger.free("pipeline.activations", nb)
            return out

        def stage_batch(s):
            return batch if meshes is None else batches[s]

        def carry_in(s, mb):
            # Stage s's input carry; placed mode moves it onto stage
            # s's sub-mesh ONCE (the stored copy serves s's backward
            # too).
            c = carries[(s - 1, mb)]
            if meshes is not None:
                c = _to_mesh(c, meshes[s], R)
                carries[(s - 1, mb)] = c
            return c

        def ct_in(s, mb):
            ct = cts.pop((s + 1, mb))
            if meshes is not None:
                ct = _to_mesh(ct, meshes[s], R)
            return ct

        for tick in plan.ticks:
            for a in tick:
                i = self._mb_idx[a.mb]
                s = a.stage
                if a.phase == "F":
                    if s == 0:
                        out = self._fwd[0](params[0], stage_batch(0), i)
                        born((0, a.mb), out)
                        live += 1
                    elif s == S - 1:
                        out = losses[a.mb] = self._fwd[s](
                            params[s], carry_in(s, a.mb),
                            stage_batch(s), i)
                    else:
                        out = self._fwd[s](
                            params[s], carry_in(s, a.mb),
                            stage_batch(s), i)
                        born((s, a.mb), out)
                        live += 1
                    peak = max(peak, live)
                else:
                    prog = self._bwd_acc[s] if accs[s] is not None \
                        else self._bwd[s]
                    extra = (accs[s],) if accs[s] is not None else ()
                    if s == S - 1:
                        out = prog(params[s], consumed((s - 1, a.mb)),
                                   stage_batch(s), i, *extra)
                        accs[s], cts[(s, a.mb)] = out
                        live -= 1
                    elif s == 0:
                        out = accs[0] = prog(params[0], stage_batch(0),
                                             i, ct_in(0, a.mb), *extra)
                    else:
                        out = prog(params[s], consumed((s - 1, a.mb)),
                                   stage_batch(s), i, ct_in(s, a.mb),
                                   *extra)
                        accs[s], cts[(s, a.mb)] = out
                        live -= 1
                    if stream and a.mb == m - 1:
                        if meshes is not None:
                            # Placed: the stage's fused reduce+apply
                            # (in-program psum + optimizer update on
                            # the stage sub-mesh) dispatches NOW —
                            # the reduction streams into the other
                            # stages' remaining ticks.
                            applied[s] = self._apply_s[s](
                                accs[s], opt_state[s], params[s])
                        else:
                            # This stage's LAST backward: its buckets
                            # negotiate/replay NOW, as partial cycles —
                            # the reduction streams into the remaining
                            # schedule ticks (the bubble).
                            dispatch_bucket_segment(
                                self._prefix,
                                self._bucket_plan.segments[s],
                                jax.tree_util.tree_leaves(accs[s]),
                                handles, tl)
                if window is not None:
                    window.admit(out)

        # Exposed-bubble window: everything the host pays between the
        # LAST schedule tick's dispatch and the reduced gradients being
        # ready.  The GPipe-ordered leg pays its flush fence, the
        # serialized bucket dispatch AND the whole reduction inside
        # this window; the 1F1B leg's reductions were dispatched inside
        # the schedule, so only the residual drain shows up.
        t0 = time.perf_counter()
        if not stream:
            # GPipe-ordered comparator: reduction serialized after the
            # full flush — fence every accumulated gradient, then
            # dispatch the same reductions (buckets, or the per-stage
            # fused reduce+apply programs under placement).
            jax.block_until_ready([jax.tree_util.tree_leaves(acc)
                                   for acc in accs])
            for s in range(S):
                if meshes is not None:
                    applied[s] = self._apply_s[s](
                        accs[s], opt_state[s], params[s])
                else:
                    dispatch_bucket_segment(
                        self._prefix, self._bucket_plan.segments[s],
                        jax.tree_util.tree_leaves(accs[s]), handles, tl)

        if meshes is not None:
            new_params = [a[0] for a in applied]
            new_opt = [a[1] for a in applied]
            jax.block_until_ready(
                jax.tree_util.tree_leaves(new_params))
        else:
            from ..ops import collective as C

            reduced = [C.take_async(h) for h in handles]
            jax.block_until_ready(reduced)
        if _telemetry.enabled():
            _M_BUBBLE.observe(time.perf_counter() - t0)
            _M_MICROBATCHES.inc(m)
            _M_INFLIGHT.set(peak)
            _M_INFLIGHT_BYTES.set(peak_b)
        if mem_on:
            _mem.ledger.note_step()
        loss = self._loss_mean(losses)
        if meshes is not None:
            return new_params, new_opt, loss
        red_tree = jax.tree_util.tree_unflatten(self._treedef, reduced)
        new_params, opt_state = self._apply(red_tree, opt_state, params)
        return new_params, opt_state, loss


def make_pipeline_train_step(
    stages,
    optimizer,
    *,
    num_microbatches: int,
    schedule: Optional[str] = None,
    interleave: Optional[int] = None,
    mesh=None,
    average: bool = True,
    fusion_threshold: Optional[int] = None,
    donate: bool = False,
    stage_meshes: Optional[Sequence] = None,
):
    """Build the host-scheduled MPMD pipeline train step.

    Args:
      stages: a :class:`~horovod_tpu.parallel.overlap.ChainedLoss` (or
        a sequence of ``stage(stage_params, carry, microbatch)``
        callables — stage 0 receives ``carry=None``, the last stage
        returns the scalar per-replica microbatch loss).
      optimizer: an optax ``GradientTransformation``.
      num_microbatches: pipeline depth-filling factor; every batch
        leaf's leading axis must divide by it (and the microbatch by
        the replica count) — violations raise naming the axis size and
        the nearest valid counts.
      schedule: ``1f1b`` (default; ``HVD_TPU_PIPELINE_SCHEDULE``) or
        ``gpipe`` — the all-forwards-then-all-backwards dispatch of
        the SAME executables with the reduction serialized after a
        flush fence (the comparator; bitwise-identical results).
      interleave: virtual stages per executor
        (``HVD_TPU_PIPELINE_INTERLEAVE``, default 1); must divide the
        stage count.
      mesh: replica mesh (data-parallel axis); defaults to the global
        one.  The batch is sharded over it; gradients reduce through
        the dynamic partial-cycle path per stage.
      average: divide the accumulated gradients by
        ``num_microbatches × replicas`` (the mean-loss gradient);
        ``False`` divides by ``num_microbatches`` only.
      fusion_threshold: per-stage bucket granularity in bytes
        (defaults to the coordinator's live threshold).
      donate: donate params/opt_state into the apply program.
      stage_meshes: optional per-stage sub-mesh placement (one mesh
        per stage, e.g. from :func:`stage_submeshes`) — the mp ×
        pipeline composition.  Each stage's executables compile over
        its own sub-mesh (which may carry a model axis on top of the
        replica axis), boundary carries/cotangents move between
        sub-meshes on the host, and each stage's gradients reduce
        through its own fused reduce+apply program instead of the
        dynamic bucket path.  Requires ``opt_state`` to be a
        per-stage sequence (``[optimizer.init(p) for p in params]``);
        ``donate`` then covers the backward programs only.

    Returns:
      ``step(params, opt_state, batch) -> (params, opt_state, loss)``
      with ``params`` a per-stage sequence; ``loss`` is the mean over
      microbatches of the pmean'd per-microbatch loss.  ``step.plan``
      exposes the resolved :class:`PipelinePlan` (the dryrun surface).
    """
    return _PipelineStep(stages, optimizer, mesh, num_microbatches,
                         schedule, interleave, average, fusion_threshold,
                         donate, stage_meshes)


# ---------------------------------------------------------------------------
# The original GPipe scan (one compiled program over the pipe axis)
# ---------------------------------------------------------------------------

def gpipe(stage_fn: Callable, stage_params, x, *, num_microbatches: int,
          axis_name: str = PIPE_AXIS):
    """Run ``x`` through ``n_stages`` pipelined applications of
    ``stage_fn``.

    Args:
      stage_fn: ``stage_fn(stage_params, x_mb) -> y_mb`` (shape-
        preserving).  Called by every device on its own stage's params.
      stage_params: this device's stage parameters (from shard_map over
        the pipe axis).
      x: the full per-pipeline batch ``[batch, ...]`` (replicated across
        the pipe axis); ``batch`` must divide by ``num_microbatches``.
      num_microbatches: pipeline depth-filling factor.

    Returns:
      ``y`` with the same shape as ``x``, valid on every stage (the last
      stage's results are summed across the axis, other stages contribute
      zeros — one psum at the end).
    """
    n = jax.lax.axis_size(axis_name)
    idx = jax.lax.axis_index(axis_name)
    m = num_microbatches
    if x.shape[0] % m != 0:
        raise ValueError(_indivisible_message("batch", x.shape[0], m))
    mb = x.shape[0] // m
    xs = x.reshape((m, mb) + x.shape[1:])
    # send i -> i+1 (last stage's send is dropped into stage 0, ignored)
    perm = [(i, (i + 1) % n) for i in range(n)]

    def tick(carry, t):
        recv, outs = carry
        # Stage 0 draws the next microbatch from the batch; later stages
        # consume what arrived from the left neighbor.
        mb_idx = jnp.clip(t, 0, m - 1)
        first_in = jax.lax.dynamic_index_in_dim(xs, mb_idx, keepdims=False)
        x_in = jnp.where(idx == 0, first_in, recv)
        y = stage_fn(stage_params, x_in)
        # The last stage finished microbatch t - (n - 1) this tick.
        out_idx = t - (n - 1)
        valid = jnp.logical_and(idx == n - 1, out_idx >= 0)
        outs = jax.lax.dynamic_update_index_in_dim(
            outs,
            jnp.where(valid, y,
                      jax.lax.dynamic_index_in_dim(
                          outs, jnp.clip(out_idx, 0, m - 1),
                          keepdims=False)),
            jnp.clip(out_idx, 0, m - 1), axis=0)
        recv = jax.lax.ppermute(y, axis_name, perm)
        return (recv, outs), None

    ticks = jnp.arange(m + n - 1)
    recv0 = jnp.zeros((mb,) + x.shape[1:], x.dtype)
    outs0 = jnp.zeros_like(xs)
    (_, outs), _ = jax.lax.scan(tick, (recv0, outs0), ticks)
    # Only the last stage holds real outputs; share them with one psum.
    outs = jnp.where(idx == n - 1, outs, jnp.zeros_like(outs))
    outs = jax.lax.psum(outs, axis_name)
    return outs.reshape(x.shape)


def stage_index(axis_name: str = PIPE_AXIS):
    """This device's pipeline stage id (inside shard_map)."""
    return jax.lax.axis_index(axis_name)


def select_stage_params(params_per_stage, *, axis_name: str = PIPE_AXIS):
    """Slice one stage's parameters out of a stacked
    ``[n_stages, ...]``-leading pytree (inside shard_map, replicated
    input)."""
    idx = jax.lax.axis_index(axis_name)
    return jax.tree_util.tree_map(
        lambda leaf: jax.lax.dynamic_index_in_dim(leaf, idx,
                                                  keepdims=False),
        params_per_stage)
