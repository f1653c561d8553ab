"""Fused data-plane megakernels for the eager collective executor.

PR 2 deleted the steady state's control-plane cost (response cache +
replayed fusion plans); what remained of the per-step tax was data-plane
dispatch: ``ops/collective._execute_response`` surrounded each jitted
collective with a Python loop of *eager* XLA dispatches — a
``jnp.concatenate`` pack, per-tensor slice/reshape unpacks, a separate
divide launch for AVERAGE — with no buffer donation and no executable
reuse tied to the cached plans.  That is exactly the fusion-buffer copy
overhead the original Horovod paper identifies as the small-tensor
scaling wall (arXiv:1802.05799 §4), re-materialized as host dispatch
latency instead of memcpy bandwidth.

This module replaces that choreography with **one jitted, donated
megakernel per fusion group**: a shape/dtype/layout/reduce-op/mesh-keyed
executable that packs the group's tensors into a flat fusion buffer,
runs the collective once, folds the AVERAGE divide in, and unpacks to
the result tensors *inside a single XLA program* — the compiler fuses
the copies into the collective and the drain thread performs exactly
one dispatch per group (asserted by tests/test_megakernel.py via
utils/xla_dispatch.py).  ``donate_argnums`` covers every input buffer
the executor itself owns (host-converted contributions, the packed
multi-process fusion buffer), so the steady state stops allocating; the
user's own arrays are never donated.

Compiled executables are cached per group structure and recorded under
the fusion-plan digest of the PR 2 response cache
(``ops/cache.py:plan_fusion`` / ``cycle_digest``), so a replayed cycle
goes straight from ``FRAME_RESPONSE_BATCH`` to a pre-compiled launch.
The cache is bounded and flushed through the same plan-memo
invalidation hook as the memoized fusion plans
(``Coordinator.set_fusion_threshold`` → :func:`flush`).

On multi-slice DCN deployments (``core/topology.replica_hierarchy``)
the ALLREDUCE reduction is lowered hierarchically — ``psum_scatter``
over ICI → ``psum`` over DCN → ``all_gather`` over ICI — which moves
``1/ici_size`` of the bytes over the slow DCN leg, with each leg's wire
format composing independently (``HVD_TPU_DCN_COMPRESS`` /
``HVD_TPU_ICI_COMPRESS``: full precision, bf16/fp16 casts, or int8/int4
quantized exchanges; cf. EQuARX, arXiv:2506.17615).

Quantized reduction (this PR's tentpole): when the compression policy
(ops/compression.py, ``hvd.set_compression`` / ``HVD_TPU_COMPRESSION``)
selects int8/int4 for a fusion group, the pack→reduce→unpack executable
compiles the block-scaled quantize → wire exchange → dequantize
pipeline INTO the same single XLA program — zero extra dispatches —
with stochastic rounding (seeded per step via the ``st`` input, so the
executable is reused across steps) and **error-feedback residuals**:
per-tensor state owned by this executor, added to the next step's
contribution inside the kernel, flushed with the executable cache on
plan invalidation, and checkpoint-restorable
(:func:`compression_state` / :func:`load_compression_state`).

Env contract (docs/performance.md):
  HVD_TPU_MEGAKERNEL=0           fall back to the per-tensor eager
                                 executor (default on; the tests'
                                 bitwise reference)
  HVD_TPU_HIERARCHICAL=auto|on|off   see core/topology.py
  HVD_TPU_VIRTUAL_SLICES=<k>         see core/topology.py
  HVD_TPU_DCN_COMPRESS=none|bf16|fp16|int8|int4
                                 DCN-leg wire format (default: inherit
                                 the group's quantized format, else
                                 full precision)
  HVD_TPU_ICI_COMPRESS=none|int8|int4  ICI-leg wire format (default
                                 none = full precision)
  HVD_TPU_COMPRESSION / HVD_TPU_QUANT_*  see ops/compression.py
"""

from __future__ import annotations

import functools
import math
import os
import sys
import time
import weakref
from dataclasses import dataclass, field
from typing import Any, Callable, Dict, List, Optional, Sequence, Tuple

import json

import jax
import jax.numpy as jnp
from jax.sharding import NamedSharding, PartitionSpec as P

from ..analysis import donation as _donation
from ..analysis import lockorder as _lockorder
from ..analysis import program as _program
from ..core import state as _state
from ..core import topology as _topology
from ..core.state import REPLICA_AXIS
from ..utils import xla_dispatch as _xla_dispatch
from .. import telemetry as _telemetry
from .. import trace as _trace
from ..memory import ledger as _mem
from ..memory import oom as _oom
from ..memory import planner as _mem_planner
from . import compression as _compression
from .wire import ReduceOp

# hvd-telemetry (docs/metrics.md): per-launch bytes the fused
# collective moves in WIRE format — the quantized-allreduce observable
# (the matching logical bytes ride MegakernelStats and surface as the
# compression.ratio gauge).
_M_WIRE_BYTES = _telemetry.histogram(
    "collective.wire_bytes", "bytes",
    "wire-format bytes per fused collective launch")
_R_LAUNCH = _trace.RegionFamily("megakernel/", "collective")

# Compiled-executable cache bound: a stable program needs one entry per
# (fusion group structure x mesh); jittery tick partitioning can mint a
# few orders, never hundreds — overflow means churn, so clear wholesale
# like the fusion-plan memo (ops/cache.py take_ready).
CACHE_CAPACITY = 128

DCN_COMPRESS_ENV = "HVD_TPU_DCN_COMPRESS"
ICI_COMPRESS_ENV = "HVD_TPU_ICI_COMPRESS"

# Persistent compile cache: jax's XLA compilation cache persists to the
# directory core/state.compile_cache_dir() resolves, and every cold
# megakernel build appends its group structure to
# <dir>/megakernel_manifest.json, so an elastic relaunch — or any repeat
# run — can AOT-rebuild the steady-state executables at init time
# (:func:`warm_start`) and hit the disk cache instead of recompiling on
# the first training step.
MANIFEST_NAME = "megakernel_manifest.json"
MANIFEST_CAP = 256

_enabled_override: Optional[bool] = None


def enabled() -> bool:
    """Megakernel executor gate (default on); ``set_enabled`` overrides
    the env for in-process A/B runs (bench, tests)."""
    if _enabled_override is not None:
        return _enabled_override
    return os.environ.get("HVD_TPU_MEGAKERNEL", "1") != "0"


def set_enabled(value: Optional[bool]) -> None:
    """Force the executor on/off (``None`` restores the env gate)."""
    global _enabled_override
    _enabled_override = value


# Reduce-op kernel families the megakernel can lower.  ADASUM is absent
# by design: its per-tensor dot products are scale adaptations that the
# coordinator never fuses (ops/cache.plan_fusion) and that need the
# ladder/VHDD kernels of ops/collective.py.
_OPS = ("psum", "pmin", "pmax", "pprod")


@dataclass(frozen=True)
class Hierarchy:
    """Static hierarchical-reduction parameters baked into a kernel:
    the topology's ICI×DCN decomposition plus each leg's wire format —
    ``wire_dtype`` is the DCN cast narrowing (bf16/fp16), ``dcn_quant``
    / ``ici_quant`` the quantized exchange formats (ops/compression.py
    WireFormat); None everywhere = full precision."""

    topo: _topology.ReplicaHierarchy
    wire_dtype: Optional[str]
    dcn_quant: Optional[_compression.WireFormat] = None
    ici_quant: Optional[_compression.WireFormat] = None


@dataclass(frozen=True)
class GroupSpec:
    """Cache key of one fused-group executable: everything that changes
    the traced program.  ``mesh_key`` is the tuple of jax Device
    OBJECTS (the same convention as ops/collective._kernels: a
    restarted backend's fresh devices miss naturally).  ``quant`` is
    the group's wire format from the compression policy (None = full
    precision; "cast" folds dtype narrowing around the reduction;
    "quant" compiles the int8/int4 pipeline in)."""

    mesh_key: Tuple[Any, ...]
    variant: str          # "sp_pr" | "sp_rep" | "mp"
    op: str               # _OPS member
    average: bool
    denom: int
    dtype: str
    shapes: Tuple[Tuple[int, ...], ...]
    donate: Tuple[bool, ...]
    hier: Optional[Hierarchy] = None
    quant: Optional[_compression.WireFormat] = None


@dataclass
class MegakernelStats:
    builds: int = 0
    # hvd-telemetry: wall seconds constructing the jitted callables
    # (trace graph building) and — the dominant cost — the first
    # dispatch of each cold executable, which is where XLA compiles.
    # Surfaced as megakernel.build_seconds / megakernel.compile_seconds
    # gauges by the runtime collector (telemetry/__init__.py).
    build_seconds: float = 0.0
    compile_seconds: float = 0.0
    cache_hits: int = 0
    flushes: int = 0
    launches: int = 0
    # XLA executable launches observed DURING megakernel launches (only
    # populated under HVD_TPU_COUNT_DISPATCHES=1): the dispatch-count
    # regression contract is launch_dispatches == launches — exactly one
    # executable per fusion group, no eager-op creep.
    launch_dispatches: int = 0
    hier_launches: int = 0
    donated_inputs: int = 0
    # Executables AOT-rebuilt from the persistent-cache manifest at
    # init (warm_start) and the wall seconds it took — on a relaunch
    # with a warm XLA disk cache this is the recompile time saved from
    # the first training step.
    warm_starts: int = 0
    warm_seconds: float = 0.0
    # Bytes-on-wire accounting (quantized allreduce): logical_bytes is
    # what the collective's payload traversals would move uncompressed,
    # wire_bytes what they move in the launched kernels' wire formats
    # (codes + block scales; per-leg on hierarchical launches).  The
    # ratio is surfaced as the compression.ratio gauge
    # (tests/test_megakernel.py bounds it per codec).
    logical_bytes: int = 0
    wire_bytes: int = 0
    quant_launches: int = 0


stats = MegakernelStats()

_lock = _lockorder.make_lock("megakernel._lock")
_compiled: Dict[GroupSpec, Callable] = {}  # guarded_by: _lock
_digests: Dict[GroupSpec, str] = {}  # guarded_by: _lock
_by_digest: Dict[str, GroupSpec] = {}  # guarded_by: _lock
# Error-feedback residual state (quantized allreduce), owned by the
# executor: ONE flat buffer per fusion group, keyed
# ("g", process_set_id, name_1, ..., name_k) — the concatenation of the
# group's per-tensor residuals in pack order (per-tensor kernel
# arguments would double the executable's arity and jax's per-array
# dispatch cost; the steady state's grouping is stable thanks to the
# PR 2 cached fusion plans, and a re-partition resets the affected
# tensors' error history to zero, which costs one step of correction,
# never correctness).  Flushed with the executable cache (plan
# invalidation re-partitions groups) and checkpoint-restorable via
# compression_state()/load_compression_state.
_residuals: Dict[Tuple, Any] = {}  # guarded_by: _lock
# Per-fusion-group launch counters: the stochastic-rounding tick.  The
# kernel takes (seed, tick) as a runtime input, so one compiled
# executable serves every step while the noise stays step-unique and —
# under a fixed HVD_TPU_QUANT_SEED — bitwise reproducible.
_ticks: Dict[Tuple, int] = {}  # guarded_by: _lock
# Donation-safety probes (tests): weakrefs of the inputs donated by the
# most recent launch — after the launch nothing in the runtime may hold
# them, so post-gc the refs must be dead.  Only recorded while dispatch
# counting is on; production launches skip the bookkeeping.
last_donated: List[weakref.ref] = []


def dcn_compress_name() -> str:
    """The DCN-leg compressor name; "" when the knob is UNSET — unset
    means "inherit the group's quantized format", while an explicit
    ``none`` pins the leg to full precision (the opt-out)."""
    return os.environ.get(DCN_COMPRESS_ENV, "")


def ici_compress_name() -> str:
    return os.environ.get(ICI_COMPRESS_ENV, "none")


def flush(reason: str) -> None:
    """Drop every compiled executable AND the quantization state (the
    plan-memo invalidation hook: fusion-threshold changes re-partition
    groups, so the old structures — and the error-feedback residuals
    accumulated against them — go cold; reclaim instead of aging
    out)."""
    with _lock:
        n = len(_compiled)
        nr = len(_residuals)
        _compiled.clear()
        _digests.clear()
        _by_digest.clear()
        _residuals.clear()
        _ticks.clear()
        stats.flushes += 1
    _sync_residual_ledger()
    if n or nr:
        print(f"[hvd-megakernel] cache flushed ({reason}): "
              f"{n} executables, {nr} residual tensors dropped",
              file=sys.stderr)


def cache_size() -> int:
    with _lock:
        return len(_compiled)


# ---------------------------------------------------------------------------
# Quantization state: error-feedback residuals + stochastic-rounding ticks
# ---------------------------------------------------------------------------

def next_tick(group_key: Tuple) -> int:
    """This launch's stochastic-rounding tick for one fusion group
    (0, 1, 2, ... per group identity) — both executor paths (fused and
    eager-reference) draw from the same counter, so the noise stream is
    a property of the PROGRAM, not of which executor ran it."""
    with _lock:
        t = _ticks.get(group_key, 0)
        _ticks[group_key] = t + 1
        return t


def take_residual(key: Tuple, dtype,
                  shapes: Sequence[Tuple[int, ...]]) -> Optional[Any]:
    """REMOVE and return the stored error-feedback residual for
    ``key``, or None when absent/stale (first use, post-flush, changed
    group shape).  Take-semantics on purpose: the caller donates the
    buffer into the launch, and the store must never keep a reference
    to soon-to-be-deleted device memory — a concurrent
    :func:`compression_state` (e.g. the background-checkpoint snapshot)
    would otherwise read a deleted array.  ``shapes`` lists the
    acceptable shapes (the mp path accepts both its live [P, T] global
    array and a checkpoint-restored local [T])."""
    with _lock:
        r = _residuals.pop(key, None)
    _sync_residual_ledger()
    if r is None \
            or not any(tuple(r.shape) == tuple(s) for s in shapes) \
            or str(r.dtype) != str(jnp.dtype(dtype)) \
            or (isinstance(r, jax.Array) and r.is_deleted()):
        return None
    return r


def store_residuals(keys: Sequence[Tuple], arrays: Sequence) -> None:
    with _lock:
        for key, arr in zip(keys, arrays):
            _residuals[key] = arr
    _sync_residual_ledger()


def drop_residuals(keys: Sequence[Tuple]) -> None:
    """Forget residual entries whose buffers were donated into a launch
    that then FAILED — they reference deleted device memory and must
    restart from zero rather than poison the next launch."""
    with _lock:
        for key in keys:
            _residuals.pop(key, None)
    _sync_residual_ledger()


def _sync_residual_ledger() -> None:
    """hvd-mem: mirror the EF residual store's byte total into the
    device-memory ledger (``megakernel.residuals``) — the store is the
    one long-lived executor-owned buffer set, so the ledger carries its
    absolute size rather than alloc/free deltas.  NOT gated on
    telemetry enablement: a flush/drop landing while an A/B leg has
    telemetry off must still clear the figure, or the ledger reports
    phantom residual bytes forever after re-enable (the frees in
    input.py/checkpoint.py are unconditional for the same reason);
    the cost is one dict walk per residual transition, nowhere near a
    hot path."""
    with _lock:
        arrays = list(_residuals.values())
    total = 0
    for v in arrays:
        nb = getattr(v, "nbytes", None)
        if nb:
            try:
                total += int(nb)
            except (TypeError, ValueError):
                pass
    _mem.ledger.set("megakernel.residuals", total)


def residual_count() -> int:
    with _lock:
        return len(_residuals)


def compression_state() -> Dict[str, Dict[str, Any]]:
    """Checkpoint-portable snapshot of the quantization state: the
    error-feedback residuals (host numpy) and per-group ticks.  Save it
    alongside the model tree and hand it back to
    :func:`load_compression_state` after restore, so a resumed run
    continues the telescoping error correction instead of restarting it
    (exported as ``hvd.compression_state``)."""
    import numpy as np

    with _lock:
        items = list(_residuals.items())
        ticks = {json.dumps(list(k)): int(v) for k, v in _ticks.items()}
    res = {}
    for k, v in items:
        if isinstance(v, jax.Array):
            if v.is_deleted():
                continue  # donated into an in-flight launch: skip
            if not v.is_fully_addressable:
                # mp residual: a [P, T] global — export this process's
                # local [T] shard (what the restore path re-uploads).
                v = np.asarray(v.addressable_data(0))[0]
        res[json.dumps(list(k))] = np.asarray(v)
    return {"residuals": res, "ticks": ticks}


def load_compression_state(state: Dict[str, Dict[str, Any]]) -> None:
    """Restore a :func:`compression_state` snapshot (exported as
    ``hvd.load_compression_state``)."""
    import numpy as np

    res = {tuple(json.loads(k)): np.asarray(v)
           for k, v in (state.get("residuals") or {}).items()}
    ticks = {tuple(json.loads(k)): int(v)
             for k, v in (state.get("ticks") or {}).items()}
    with _lock:
        _residuals.clear()
        _residuals.update(res)
        _ticks.clear()
        _ticks.update(ticks)
    _sync_residual_ledger()


def digest_of(spec: GroupSpec) -> Optional[str]:
    """Fusion-plan digest a compiled spec was recorded under (tests)."""
    with _lock:
        return _digests.get(spec)


def spec_for_digest(digest: str) -> Optional[GroupSpec]:
    """Reverse lookup: the compiled group keyed by a plan digest — how
    bench/tests prove a replayed cycle lands on a pre-compiled
    executable."""
    with _lock:
        return _by_digest.get(digest)


def plan_digest(entries: Sequence[_program.SignatureEntry],
                quant: Optional[_compression.WireFormat] = None) -> str:
    """The PR 2 fusion-plan digest of a group's signature entries
    (analysis/program.py's canonical scheme, shared with
    ops/cache.cycle_digest so cache diagnostics and executable records
    name a cycle identically).  The quantization spec is folded in —
    the same tensor program under a different codebook is a different
    compiled plan, and their records must never collide."""
    base = _program.entries_digest(list(entries))
    if quant is None:
        return base
    import hashlib

    return hashlib.sha256(
        f"{base}|{quant}".encode("utf-8")).hexdigest()[:len(base)]


@functools.lru_cache(maxsize=64)
def _hierarchy_cached(mesh_key: Tuple, dtype: str, mode: str,
                      virtual: str, dcn: str, ici: str,
                      group_name: str) -> Optional[Hierarchy]:
    # The env values are part of the key, so this memo needs no
    # invalidation: a changed knob is a different key (the O(n) device
    # scan + group-tuple construction runs once per configuration, not
    # once per fusion-group launch on the steady-state hot path).
    h = _topology.replica_hierarchy(mesh_key)
    if h is None:
        return None

    def quant_fmt(name):
        # Leg formats gate on dtype only — the whole fusion buffer
        # rides the leg, so the per-tensor min-elems floor is moot.
        fmt = _compression.wire_format_for(name, jnp.dtype(dtype),
                                           1 << 30)
        return fmt if fmt is not None and fmt.kind == "quant" else None

    wire = _compression.wire_dtype_for(dcn or "none", jnp.dtype(dtype))
    dcn_q = quant_fmt(dcn) if dcn else None
    if dcn == "" and dcn_q is None and wire is None and group_name:
        # Per-leg composition default: a group whose policy selected a
        # quantized format keeps it on the slow DCN leg when
        # HVD_TPU_DCN_COMPRESS is UNSET; an explicit value — including
        # ``none`` — overrides (the full-precision-DCN opt-out).  The
        # ICI legs stay full precision unless HVD_TPU_ICI_COMPRESS
        # opts them in.
        dcn_q = quant_fmt(group_name)
    return Hierarchy(
        topo=h,
        wire_dtype=(jnp.dtype(wire).name if wire is not None else None),
        dcn_quant=dcn_q, ici_quant=quant_fmt(ici))


def hierarchy_for(mesh_devices: Tuple, op: str, dtype,
                  group_fmt=None) -> Optional[Hierarchy]:
    """The hierarchical-reduction plan for one group, or None for flat.

    Only the psum family decomposes (SUM/AVERAGE — the gradient path);
    each leg's wire format applies the compression.py applicability
    rule to the group's dtype at plan time so the kernel folds the
    casts/codecs.  ``group_fmt`` (the group's policy WireFormat) feeds
    the DCN-leg inheritance default."""
    if op != "psum":
        return None
    return _hierarchy_cached(
        tuple(mesh_devices), jnp.dtype(dtype).name,
        os.environ.get(_topology.HIERARCHICAL_ENV, "auto"),
        os.environ.get(_topology.VIRTUAL_SLICES_ENV, ""),
        dcn_compress_name(), ici_compress_name(),
        group_fmt.name if (group_fmt is not None
                           and group_fmt.kind == "quant") else "")


# ---------------------------------------------------------------------------
# Kernel bodies
# ---------------------------------------------------------------------------

def _numel(shape: Tuple[int, ...]) -> int:
    return int(math.prod(shape)) if shape else 1


def _reduce_flat(spec: GroupSpec):
    """flat [T] local vector -> [T] reduced (replicated across the
    group's axis) — the collective core of every megakernel."""
    hier = spec.hier

    def reduce_fn(v):
        if spec.op == "pmin":
            return jax.lax.pmin(v, REPLICA_AXIS)
        if spec.op == "pmax":
            return jax.lax.pmax(v, REPLICA_AXIS)
        if spec.op == "pprod":
            # No lax.pprod exists: gather + local product, like the
            # per-tensor kernels (XLA fuses the pointwise product into
            # the gather's consumer).
            return jnp.prod(
                jax.lax.all_gather(v, REPLICA_AXIS, axis=0), axis=0)
        if hier is None:
            return jax.lax.psum(v, REPLICA_AXIS)
        # Hierarchical ICI x DCN: scatter-reduce inside the slice, sum
        # the 1/ici_size fragments across slices (optionally narrowed on
        # that slow leg only), then re-gather inside the slice.
        L = v.shape[0]
        pad = (-L) % hier.topo.ici_size
        if pad:
            v = jnp.concatenate([v, jnp.zeros((pad,), v.dtype)])
        ici = [list(g) for g in hier.topo.ici_groups]
        dcn = [list(g) for g in hier.topo.dcn_groups]
        s = jax.lax.psum_scatter(v, REPLICA_AXIS, scatter_dimension=0,
                                 tiled=True, axis_index_groups=ici)
        if hier.wire_dtype is not None:
            s = jax.lax.psum(s.astype(jnp.dtype(hier.wire_dtype)),
                             REPLICA_AXIS,
                             axis_index_groups=dcn).astype(v.dtype)
        else:
            s = jax.lax.psum(s, REPLICA_AXIS, axis_index_groups=dcn)
        g = jax.lax.all_gather(s, REPLICA_AXIS, axis=0, tiled=True,
                               axis_index_groups=ici)
        return g[:L] if pad else g

    if spec.quant is not None and spec.quant.kind == "cast":
        # Policy-selected cast compression (bf16/fp16): the whole
        # reduction runs in the wire dtype, restored on unpack —
        # decompress-then-divide order, like the eager compressors.
        wire = jnp.dtype(spec.quant.wire_dtype)
        inner = reduce_fn

        def reduce_cast(v):
            return inner(v.astype(wire)).astype(v.dtype)

        return reduce_cast
    return reduce_fn


def _unpack(spec: GroupSpec, red, lead: Tuple[int, ...]):
    """Split the reduced flat buffer back into the group's payload
    shapes, folding the AVERAGE divide (floor division for integer
    dtypes — the `_divide` contract of ops/collective.py)."""
    outs = []
    offs = 0
    integral = not jnp.issubdtype(jnp.dtype(spec.dtype), jnp.inexact)
    for shp in spec.shapes:
        cnt = _numel(shp)
        piece = red[..., offs:offs + cnt].reshape(lead + shp)
        offs += cnt
        if spec.average:
            piece = piece // spec.denom if integral else piece / spec.denom
        outs.append(piece)
    return tuple(outs)


def _needs_quant_build(spec: GroupSpec) -> bool:
    if spec.quant is not None and spec.quant.kind == "quant":
        return True
    h = spec.hier
    return h is not None and (h.dcn_quant is not None
                              or h.ici_quant is not None)


def _quant_unit(spec: GroupSpec) -> int:
    """Flat-buffer alignment so every exchange chunk is a whole number
    of scaling blocks: n·block for the flat two-phase exchange,
    ici_size·block for the hierarchical legs."""
    blocks = [f.block for f in (
        spec.quant, spec.hier.dcn_quant if spec.hier else None,
        spec.hier.ici_quant if spec.hier else None)
        if f is not None and f.kind == "quant"]
    block = max(blocks) if blocks else 2
    n = spec.hier.topo.ici_size if spec.hier is not None \
        else len(spec.mesh_key)
    return n * block


def _hier_quant_reduce(vin, spec: GroupSpec, key, pos):
    """Hierarchical ICI×DCN reduction with per-leg wire formats: the
    scatter and gather legs ride ICI (full precision, or int8/int4 via
    HVD_TPU_ICI_COMPRESS), the cross-slice sum rides DCN in its own
    format (cast or quantized).  Returns the reduced [Tp] float32."""
    hier = spec.hier
    topo = hier.topo
    ici = [list(g) for g in topo.ici_groups]
    dcn = [list(g) for g in topo.dcn_groups]
    myslice = jnp.take(
        jnp.asarray(topo.slice_of_positions(), dtype=jnp.int32), pos)
    if hier.ici_quant is not None:
        frag = _compression.quantized_scatter_sum(
            vin, hier.ici_quant, key, axis=REPLICA_AXIS,
            n=topo.ici_size, noise_pos=pos, groups=ici)
    else:
        frag = jax.lax.psum_scatter(
            vin, REPLICA_AXIS, scatter_dimension=0, tiled=True,
            axis_index_groups=ici).astype(jnp.float32)
    if hier.dcn_quant is not None:
        frag = _compression.quantized_gather_sum(
            frag, hier.dcn_quant, key, axis=REPLICA_AXIS, pos=myslice,
            groups=dcn)
    elif hier.wire_dtype is not None:
        frag = jax.lax.psum(
            frag.astype(jnp.dtype(hier.wire_dtype)), REPLICA_AXIS,
            axis_index_groups=dcn).astype(jnp.float32)
    else:
        frag = jax.lax.psum(frag, REPLICA_AXIS, axis_index_groups=dcn)
    if hier.ici_quant is not None:
        return _compression.quantized_all_gather(
            frag, hier.ici_quant, key, axis=REPLICA_AXIS, pos=pos,
            groups=ici)
    return jax.lax.all_gather(frag, REPLICA_AXIS, axis=0, tiled=True,
                              axis_index_groups=ici)


def _build_quant(spec: GroupSpec, mesh) -> Callable:
    """Trace + wrap one QUANTIZED group executable: pack → (residual
    add) → quantize → wire exchange → dequantize → unpack, all in the
    same single XLA program as the uncompressed megakernel — the
    quantize/dequantize stages cost zero extra dispatches.

    Signature per variant (``st`` = uint32[2] (seed, tick) — a runtime
    input, so one executable serves every step):

    =========  =================================================
    sp_pr      (t_1..t_k[, res], st) → (o_1..o_k[, res'])
    sp_rep     same, replicated layouts
    mp         (buf[, res], st) → (o_1..o_k[, res'])
    =========  =================================================

    ``res`` is the error-feedback residual as ONE flat buffer per
    group ([n, T] per-replica / [T] replicated) — per-TENSOR residual
    arrays would double the executable's argument count and pay jax's
    per-array dispatch cost twice over; the flat buffer is their exact
    concatenation, group-keyed in the executor's store.  Residual IO
    exists only on the error-feedback path (flat quantized reduction);
    the hierarchical per-leg codecs rely on stochastic rounding alone
    (docs/tensor-fusion.md)."""
    fmt = spec.quant if (spec.quant is not None
                         and spec.quant.kind == "quant") else None
    cast = spec.quant if (spec.quant is not None
                          and spec.quant.kind == "cast") else None
    hier = spec.hier
    n = len(spec.mesh_key)
    k = len(spec.shapes)
    T = sum(_numel(s) for s in spec.shapes)
    dtype = jnp.dtype(spec.dtype)
    use_ef = fmt is not None and fmt.error_feedback and hier is None
    shared = spec.variant == "sp_rep"
    pad = (-T) % _quant_unit(spec)

    def reduce_local(v, r, st):
        key = _compression.step_key(st[0], st[1])
        vin = v + r if r is not None else v
        if cast is not None:
            vin = vin.astype(jnp.dtype(cast.wire_dtype))
        if pad:
            vin = jnp.concatenate([vin, jnp.zeros((pad,), vin.dtype)])
        pos = jax.lax.axis_index(REPLICA_AXIS)
        if hier is None:
            red, r_new = _compression.quantized_reduce_collective(
                vin, fmt, key, axis=REPLICA_AXIS, n=n, my_chunk=pos,
                noise_pos=0 if shared else pos, error_feedback=use_ef,
                phase2_feedback=use_ef and not shared)
        else:
            red = _hier_quant_reduce(vin, spec, key, pos)
            r_new = None
        red = red[:T].astype(dtype)
        return red, (r_new[:T] if r_new is not None else None)

    nin = k + (1 if use_ef else 0)
    if spec.variant in ("sp_pr", "sp_rep"):
        lead = (1,) if spec.variant == "sp_pr" else ()

        def body(*args):
            ts, st = args[:k], args[-1]
            res = args[k] if use_ef else None
            if spec.variant == "sp_pr":
                v = jnp.squeeze(jnp.concatenate(
                    [t.reshape((t.shape[0], -1)) for t in ts], axis=1), 0)
                r = jnp.squeeze(res, 0) if use_ef else None
            else:
                v = jnp.concatenate([t.reshape(-1) for t in ts])
                r = res
            red, r_new = reduce_local(v, r, st)
            outs = _unpack(spec, red[None] if lead else red, lead)
            if use_ef:
                outs = outs + ((r_new[None] if lead else r_new),)
            return outs

        part = P(REPLICA_AXIS) if spec.variant == "sp_pr" else P()
        in_specs = tuple(part for _ in range(nin)) + (P(),)
        out_specs = tuple(part for _ in range(nin))
    elif spec.variant == "mp":
        def body(*args):
            buf = args[0]
            res = args[1] if use_ef else None
            st = args[-1]
            v = jnp.squeeze(buf, 0)
            r = jnp.squeeze(res, 0) if use_ef else None
            red, r_new = reduce_local(v, r, st)
            outs = _unpack(spec, red, ())
            if use_ef:
                outs = outs + (r_new[None],)
            return outs

        in_specs = (P(REPLICA_AXIS),) \
            + ((P(REPLICA_AXIS),) if use_ef else ()) + (P(),)
        out_specs = tuple(P() for _ in spec.shapes) \
            + ((P(REPLICA_AXIS),) if use_ef else ())
    else:
        raise ValueError(f"unknown megakernel variant {spec.variant!r}")

    if spec.variant == "mp":
        donate = (0, 1) if use_ef else (0,)
    else:
        donate = tuple(i for i, d in enumerate(spec.donate) if d) \
            + ((k,) if use_ef else ())  # the residual is executor-owned
    return jax.jit(
        jax.shard_map(body, mesh=mesh, in_specs=in_specs,
                      out_specs=out_specs, check_vma=False),
        donate_argnums=donate)


def _build(spec: GroupSpec, mesh) -> Callable:
    """Trace + wrap one group executable: pack → reduce → unpack in a
    single XLA program over ``mesh``, donated on the owned inputs."""
    if _needs_quant_build(spec):
        return _build_quant(spec, mesh)
    reduce_fn = _reduce_flat(spec)

    if spec.variant == "sp_pr":
        # Single-process, per-replica [n, *payload] inputs sharded over
        # the replica axis; outputs keep the layout (every row = the
        # reduction, Horovod's allreduce contract).
        def body(*ts):
            flat = jnp.concatenate(
                [t.reshape((t.shape[0], -1)) for t in ts], axis=1)
            red = reduce_fn(jnp.squeeze(flat, 0))[None]
            return _unpack(spec, red, (1,))

        in_specs = tuple(P(REPLICA_AXIS) for _ in spec.shapes)
        out_specs = tuple(P(REPLICA_AXIS) for _ in spec.shapes)
    elif spec.variant == "sp_rep":
        # Replicated inputs: every replica contributes the same value;
        # psum multiplies by the axis size exactly like the honest
        # per-tensor psum_rep kernel.
        def body(*ts):
            flat = jnp.concatenate([t.reshape(-1) for t in ts])
            red = reduce_fn(flat)
            return _unpack(spec, red, ())

        in_specs = tuple(P() for _ in spec.shapes)
        out_specs = tuple(P() for _ in spec.shapes)
    elif spec.variant == "mp":
        # Multi-process: one packed [P, T] fusion buffer (each process
        # contributed its flat shard), replicated payload outputs.
        def body(buf):
            red = reduce_fn(jnp.squeeze(buf, 0))
            return _unpack(spec, red, ())

        in_specs = (P(REPLICA_AXIS),)
        out_specs = tuple(P() for _ in spec.shapes)
    else:
        raise ValueError(f"unknown megakernel variant {spec.variant!r}")

    donate = tuple(i for i, d in enumerate(spec.donate) if d)
    return jax.jit(
        jax.shard_map(body, mesh=mesh, in_specs=in_specs,
                      out_specs=out_specs, check_vma=False),
        donate_argnums=donate)


def _pack_key(shapes, dtype, donate, mesh_key):
    return GroupSpec(mesh_key=mesh_key, variant="pack", op="psum",
                     average=False, denom=1, dtype=dtype, shapes=shapes,
                     donate=donate)


def _cache_insert(spec: GroupSpec, fn: Callable,
                  digest: Optional[str] = None,
                  seconds: float = 0.0) -> None:
    """Bounded insert shared by :func:`packer` and :func:`executable`:
    on overflow the whole table clears (wholesale, like the fusion-plan
    memo) rather than aging entries out."""
    with _lock:
        if len(_compiled) >= CACHE_CAPACITY:
            _compiled.clear()
            _digests.clear()
            _by_digest.clear()
            stats.flushes += 1
        _compiled[spec] = fn
        if digest is not None:
            _digests[spec] = digest
            _by_digest[digest] = spec
        stats.builds += 1
        stats.build_seconds += seconds


def packer(shapes: Tuple[Tuple[int, ...], ...], dtype: str,
           donate: Tuple[bool, ...], mesh_key) -> Callable:
    """Jitted local pack (multi-process leg): flatten + concatenate the
    group's local contributions into one fusion buffer in a single
    dispatch, donating the executor-owned inputs."""
    spec = _pack_key(shapes, dtype, donate, mesh_key)
    with _lock:
        fn = _compiled.get(spec)
        if fn is not None:
            stats.cache_hits += 1
            return fn
    fn = jax.jit(
        lambda *ts: jnp.concatenate([t.reshape(-1) for t in ts]),
        donate_argnums=tuple(i for i, d in enumerate(donate) if d))
    _cache_insert(spec, fn)
    return fn


def executable(spec: GroupSpec, mesh,
               digest_fn: Optional[Callable[[], str]] = None
               ) -> Tuple[Callable, bool]:
    """The compiled megakernel for ``spec`` — cached, bounded, recorded
    under its fusion-plan digest on the cold build (``digest_fn`` is
    only invoked then, keeping the hot path free of hashing).  Returns
    ``(fn, built)``: ``built`` tells THIS caller whether it did the
    cold build, so launch() can attribute the first (compiling)
    dispatch without racing other threads' builds."""
    with _lock:
        fn = _compiled.get(spec)
        if fn is not None:
            stats.cache_hits += 1
            return fn, False
    t0 = time.perf_counter()
    fn = _build(spec, mesh)
    digest = digest_fn() if digest_fn is not None else None
    _cache_insert(spec, fn, digest,
                  seconds=time.perf_counter() - t0)
    _record_manifest(spec, digest)  # cold builds only
    return fn, True


# ---------------------------------------------------------------------------
# Persistent compile cache: manifest + AOT warm start (hvd-pipeline)
# ---------------------------------------------------------------------------

def compile_cache_dir() -> Optional[str]:
    return _state.compile_cache_dir()


def _mesh_fingerprint(mesh_key) -> dict:
    d0 = mesh_key[0]
    return {"platform": getattr(d0, "platform", "?"),
            "device_kind": getattr(d0, "device_kind", "?"),
            "count": len(mesh_key)}


def _manifest_entry(spec: GroupSpec, digest: Optional[str]) -> dict:
    from dataclasses import asdict

    return {
        "variant": spec.variant,
        "op": spec.op,
        "average": spec.average,
        "denom": spec.denom,
        "dtype": spec.dtype,
        "shapes": [list(s) for s in spec.shapes],
        "donate": list(spec.donate),
        "hier": spec.hier is not None,
        "quant": asdict(spec.quant) if spec.quant is not None else None,
        "digest": digest,
        "mesh": _mesh_fingerprint(spec.mesh_key),
    }


def load_manifest(directory: str) -> List[dict]:
    path = os.path.join(directory, MANIFEST_NAME)
    try:
        with open(path) as f:
            data = json.load(f)
        entries = data.get("entries", [])
        return entries if isinstance(entries, list) else []
    except (OSError, ValueError):
        return []


def record_manifest_entry(entry: dict,
                          directory: Optional[str] = None) -> None:
    """Best-effort append of one executable record to the persistent-
    cache manifest (dedup by structure — the ``digest`` field is
    excluded from the key — bounded, atomic rename; never takes the
    executable lock: file IO must not nest inside it).

    Shared by the megakernel's cold-build recording and hvd-serve,
    whose prefill/decode executables ride the SAME manifest under
    ``variant: "serving"`` so one compile-cache directory warms a
    relaunched fleet's training AND serving programs
    (:func:`warm_start` here skips serving entries;
    ``serving.engine.InferenceEngine.warm_start`` consumes them)."""
    d = directory or compile_cache_dir()
    if d is None:
        return
    try:
        entries = load_manifest(d)
        key = {k: v for k, v in entry.items() if k != "digest"}
        if any({k: v for k, v in e.items() if k != "digest"} == key
               for e in entries):
            return
        entries.append(entry)
        entries = entries[-MANIFEST_CAP:]
        os.makedirs(d, exist_ok=True)
        path = os.path.join(d, MANIFEST_NAME)
        tmp = f"{path}.tmp.{os.getpid()}"
        with open(tmp, "w") as f:
            json.dump({"format": "hvd-megakernel-manifest-v1",
                       "entries": entries}, f, indent=1)
        os.replace(tmp, path)
    except Exception:  # noqa: BLE001 — the manifest is an optimization
        pass


def serving_entries(directory: Optional[str] = None) -> List[dict]:
    """The manifest's hvd-serve executable records (variant
    ``"serving"``), for ``serving.engine.InferenceEngine.warm_start``."""
    d = directory or compile_cache_dir()
    if d is None:
        return []
    return [e for e in load_manifest(d)
            if e.get("variant") == "serving"]


def mesh_fingerprint(mesh_key) -> dict:
    """Public alias of the manifest's mesh identity (platform, device
    kind, count) — serving entries carry the same fingerprint."""
    return _mesh_fingerprint(tuple(mesh_key))


def _record_manifest(spec: GroupSpec, digest: Optional[str]) -> None:
    """Record one cold megakernel build.  Only the single-process group
    variants are recorded: the mp variant's mesh and packed-buffer
    layout are incarnation-specific."""
    if compile_cache_dir() is None or spec.variant not in ("sp_pr",
                                                           "sp_rep"):
        return
    record_manifest_entry(_manifest_entry(spec, digest))


def _warm_avals(spec: GroupSpec, mesh) -> List[jax.ShapeDtypeStruct]:
    """Abstract inputs for AOT-lowering one recorded group executable
    (global shapes + shardings exactly as launch() passes them —
    including the residual mirrors and the (seed, tick) state input on
    the quantized signatures)."""
    n = len(spec.mesh_key)
    dtype = jnp.dtype(spec.dtype)
    if spec.variant == "sp_pr":
        sh = NamedSharding(mesh, P(REPLICA_AXIS))
        avals = [jax.ShapeDtypeStruct((n,) + shp, dtype, sharding=sh)
                 for shp in spec.shapes]
    else:
        sh = NamedSharding(mesh, P())
        avals = [jax.ShapeDtypeStruct(shp, dtype, sharding=sh)
                 for shp in spec.shapes]
    if _needs_quant_build(spec):
        fmt = spec.quant
        if (fmt is not None and fmt.kind == "quant"
                and fmt.error_feedback and spec.hier is None):
            T = sum(_numel(s) for s in spec.shapes)
            if spec.variant == "sp_pr":
                avals.append(jax.ShapeDtypeStruct(
                    (n, T), dtype,
                    sharding=NamedSharding(mesh, P(REPLICA_AXIS))))
            else:
                avals.append(jax.ShapeDtypeStruct(
                    (T,), dtype, sharding=NamedSharding(mesh, P())))
        avals.append(jax.ShapeDtypeStruct(
            (2,), jnp.uint32, sharding=NamedSharding(mesh, P())))
    return avals


def warm_start(mesh, directory: Optional[str] = None) -> int:
    """AOT-rebuild the manifest's group executables for ``mesh``.

    Called by ``hvd.init()`` with the resolved compile-cache directory:
    every recorded group whose mesh fingerprint matches is re-traced and
    compiled ahead of the first training step — against a warm XLA disk
    cache the compile is a cache read, so an elastic relaunch resumes at
    full step rate instead of paying the cold-compile stall mid-loop.
    Hierarchy is recomputed from the CURRENT env/topology (the knobs may
    legitimately differ across incarnations).  Best-effort per entry;
    returns the number of executables warmed."""
    d = directory or compile_cache_dir()
    if d is None:
        return 0
    fp = _mesh_fingerprint(tuple(mesh.devices.flat))
    mesh_key = tuple(mesh.devices.flat)
    warmed = 0
    t0 = time.perf_counter()
    for entry in load_manifest(d):
        if entry.get("mesh") != fp:
            continue
        if entry.get("variant") not in ("sp_pr", "sp_rep"):
            continue
        try:
            quant = (_compression.WireFormat(**entry["quant"])
                     if entry.get("quant") else None)
            spec = GroupSpec(
                mesh_key=mesh_key, variant=entry["variant"],
                op=entry["op"], average=bool(entry["average"]),
                denom=int(entry["denom"]), dtype=entry["dtype"],
                shapes=tuple(tuple(s) for s in entry["shapes"]),
                donate=tuple(bool(x) for x in entry["donate"]),
                hier=hierarchy_for(mesh_key, entry["op"], entry["dtype"],
                                   group_fmt=quant),
                quant=quant)
            with _lock:
                if spec in _compiled:
                    continue
            fn = _build(spec, mesh)
            compiled = fn.lower(*_warm_avals(spec, mesh)).compile()
            # hvd-mem: harvest compiled.memory_analysis() per warmed
            # executable (where the backend implements it) — the
            # static planner's per-mesh "compiled" section.
            _mem_planner.record_compiled(
                f"megakernel/{entry['op']}/{entry['variant']}"
                f"/{entry.get('digest') or warmed}", compiled)
            _cache_insert(spec, fn, entry.get("digest"))
            warmed += 1
        except Exception:  # noqa: BLE001 — a stale entry must not
            continue       # break init; the group just compiles lazily
    if warmed:
        with _lock:
            stats.warm_starts += warmed
            stats.warm_seconds += time.perf_counter() - t0
        print(f"[hvd-megakernel] warm start: {warmed} executables "
              f"rebuilt from {os.path.join(d, MANIFEST_NAME)}",
              file=sys.stderr)
    return warmed


def wire_accounting_legs(spec: GroupSpec) -> Tuple[int, int, int]:
    """``(logical_bytes, wire_bytes, dcn_bytes)`` one launch of ``spec``
    moves — ``dcn_bytes`` is the cross-slice share of ``wire_bytes``
    (0 for flat launches); the hvd-trace launch span carries both so
    the analyzer can split a hierarchical launch's time into its ICI
    and DCN legs.

    The model counts payload traversals per leg — flat reductions make
    two (the scatter- and gather-phase of a bandwidth-optimal
    allreduce), hierarchical ones two ICI traversals plus the 1/ici
    DCN fragment — each in that leg's wire format (codes + one 2-byte
    scale per block for quantized legs).  The per-member (n−1)/n factor
    is common to both figures and cancels in the ratio
    (docs/metrics.md)."""
    T = sum(_numel(s) for s in spec.shapes)
    item = jnp.dtype(spec.dtype).itemsize

    def fmt_bytes(count: int, fmt) -> int:
        if fmt is None:
            return count * item
        if fmt.kind == "cast":
            return count * (fmt.bits // 8)
        return (count * fmt.bits + 7) // 8 + (-(-count // fmt.block)) * 2

    if spec.hier is None:
        return 2 * T * item, 2 * fmt_bytes(T, spec.quant), 0
    h = spec.hier
    F = -(-T // h.topo.ici_size)
    cast = spec.quant if (spec.quant is not None
                          and spec.quant.kind == "cast") else None
    ici_f = h.ici_quant or cast
    if h.dcn_quant is not None:
        dcn_f = h.dcn_quant
    elif h.wire_dtype is not None:
        dcn_f = _compression.WireFormat(
            kind="cast", name=h.wire_dtype, wire_dtype=h.wire_dtype,
            bits=8 * jnp.dtype(h.wire_dtype).itemsize,
            stochastic=False, error_feedback=False)
    else:
        dcn_f = cast
    logical = (2 * T + F) * item
    dcn_b = fmt_bytes(F, dcn_f)
    return logical, 2 * fmt_bytes(T, ici_f) + dcn_b, dcn_b


def wire_accounting(spec: GroupSpec) -> Tuple[int, int]:
    """``(logical_bytes, wire_bytes)`` — see
    :func:`wire_accounting_legs`."""
    logical, wire_b, _dcn = wire_accounting_legs(spec)
    return logical, wire_b


def _launch_name(spec: GroupSpec) -> str:
    """Executable name for OOM forensics (cold/error paths only — the
    steady-state launch never builds it)."""
    return f"megakernel/{spec.op}/{spec.variant}x{len(spec.shapes)}"


def launch(spec: GroupSpec, mesh, values: Sequence,
           digest_fn: Optional[Callable[[], str]] = None,
           donate_mask: Optional[Sequence[bool]] = None):
    """One megakernel dispatch for a fusion group.  Under dispatch
    counting (tests/bench) the launch is wrapped in a thread-local
    window and the observed executable count is accumulated on
    ``stats`` — the "exactly one dispatch per group" regression
    contract — and the donated inputs are recorded as weakrefs for the
    use-after-donate probe.  ``donate_mask`` extends ``spec.donate``
    when the quantized kernels append executor-owned inputs (residuals)
    beyond the per-tensor contributions."""
    fn, cold = executable(spec, mesh, digest_fn)
    mask = tuple(donate_mask) if donate_mask is not None else spec.donate
    logical_b, wire_b, dcn_b = wire_accounting_legs(spec)
    # hvd-mem: the launch's HBM footprint (contributions + outputs, the
    # SAME byte model the planner predicts with) is accounted against
    # the ledger for the dispatch's lifetime, and a RESOURCE_EXHAUSTED
    # dumps the flight ring naming this executable and the top ledger
    # categories.  The byte arithmetic only runs when something
    # consumes it (ledger, trace span, simulated capacity), so the
    # telemetry-off A/B leg measures a true zero-accounting path and
    # the ≤5 % overhead gate covers the accounting it claims to.
    mem_on = _mem.enabled()
    trace_on = _trace.enabled()
    cap = _oom.simulated_capacity()
    fusion_b = (_mem_planner.fusion_group_bytes(
        spec.shapes, spec.dtype, len(spec.mesh_key), spec.variant)
        if (mem_on or trace_on or cap is not None) else 0)
    if cap is not None:
        # The capacity knob is per-DEVICE HBM: project the per-device
        # footprint (one payload of inputs + one of outputs per
        # device, identical across variants), not the 2·world global
        # figure the ledger/planner consistency contract shares — a
        # world>1 job with a correctly pinned per-rank capacity must
        # not raise fake OOMs (docs/memory.md).
        _oom.check_simulated(
            lambda: _launch_name(spec),
            _mem_planner.fusion_group_device_bytes(spec.shapes,
                                                   spec.dtype))
    # hvd-race donation sanitizer: every launch routes through the
    # registry — re-dispatching a buffer a previous launch donated
    # raises a DonationError naming THAT launch, and this launch's
    # donated inputs are registered afterwards (HVD_TPU_DONATION_CHECK).
    donated_idx = tuple(i for i, d in enumerate(mask) if d)

    def dispatch():
        # XLA compiles on the cold executable's FIRST dispatch; time
        # exactly that call (one perf_counter pair, cold path only) so
        # megakernel.compile_seconds reports real compilation cost.
        if not cold:
            return _donation.guard_dispatch(
                _launch_name(spec), fn, values, donated_idx)
        t0 = time.perf_counter()
        out = _donation.guard_dispatch(
            _launch_name(spec), fn, values, donated_idx)
        with _lock:
            stats.compile_seconds += time.perf_counter() - t0
        return out

    counting = _xla_dispatch.counting_enabled()
    # hvd-trace launch region: the compiled collective itself.  The
    # wire-byte legs let the analyzer split a hierarchical launch's
    # time into its ICI ("collective") and DCN shares; mem_bytes
    # mirrors the ledger charge so the fleet trace shows each
    # launch's HBM footprint next to its wall time (hvd-mem).
    with _R_LAUNCH[spec.op](groups=len(spec.shapes),
                            hier=spec.hier is not None,
                            wire_bytes=wire_b, dcn_bytes=dcn_b,
                            mem_bytes=fusion_b):
        if mem_on:
            _mem.ledger.alloc("megakernel.fusion", fusion_b)
        try:
            if counting:
                probes = [weakref.ref(v)
                          for v, d in zip(values, mask) if d]
                with _xla_dispatch.record() as scope:
                    outs = dispatch()
                with _lock:
                    stats.launches += 1
                    stats.launch_dispatches += scope.count
                    stats.donated_inputs += sum(mask)
                    stats.logical_bytes += logical_b
                    stats.wire_bytes += wire_b
                    if spec.hier is not None:
                        stats.hier_launches += 1
                    if _needs_quant_build(spec):
                        stats.quant_launches += 1
                    last_donated[:] = probes
            else:
                outs = dispatch()
                with _lock:
                    stats.launches += 1
                    stats.donated_inputs += sum(mask)
                    stats.logical_bytes += logical_b
                    stats.wire_bytes += wire_b
                    if spec.hier is not None:
                        stats.hier_launches += 1
                    if _needs_quant_build(spec):
                        stats.quant_launches += 1
        except Exception as e:  # noqa: BLE001 — re-raised: forensics only
            if _oom.is_resource_exhausted(e):
                _oom.oom_event(_launch_name(spec), e, fusion_b or None)
            raise
        finally:
            if mem_on:
                _mem.ledger.free("megakernel.fusion", fusion_b)
    if _telemetry.enabled():
        _M_WIRE_BYTES.observe(wire_b)
    return outs
