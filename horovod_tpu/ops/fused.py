"""Fused computation-collective kernels: compute inside the reduction.

The megakernel ladder fused the *collective's* phases (pack→reduce→
unpack, PR 3; quantize→exchange→dequantize, PR 6) and the overlap/1F1B
paths hid *whole* reductions under *other* programs' compute — but the
producer computation and its own collective still ran as sequential
phases: the GEMM finishes, THEN its psum/reduce_scatter/all_to_all
dispatches.  This module is the remaining step (arXiv:2305.06942,
ROADMAP open item 4): chunk the producer GEMM along a reduction-free
axis and emit ONE XLA program in which chunk *i*'s partial product
enters its collective leg while chunk *i+1* computes.  The original
Horovod (arXiv:1802.05799) could never express this — its runtime sat
outside the framework's graph; here the transform is compiler-visible,
so XLA's async collective scheduling overlaps the legs without any new
runtime machinery.

**Bitwise contract** (tests/test_fused.py; the MoE round trip in
tests/test_expert_parallel.py): every fused primitive is bitwise-identical to its unfused
reference program.  Three facts make that possible without the PR-6
pow2/ordered-sum discipline:

* chunking runs along a **reduction-free** axis (GEMM rows, the MoE
  capacity axis) — each output element's contraction is computed by
  exactly one chunk, with the same K-axis accumulation order the
  unfused GEMM uses (verified empirically per backend: the bitwise
  tests re-check it every run);
* ``psum`` / ``psum_scatter`` / ``all_gather`` are elementwise in the
  chunked axis — splitting rows never reorders any element's
  cross-replica reduction;
* the MoE ``all_to_all`` pair is chunked as a ROUND TRIP: a lone
  tiled all_to_all permutes chunk rows relative to the unfused layout,
  but the inverse all_to_all on the same chunk undoes it, so the
  dispatch→FFN→combine pipeline concatenates back to the exact
  unfused bytes.

Chunks of fewer than :data:`MIN_CHUNK_ROWS` rows are never emitted:
XLA:CPU's single-row GEMM (a gemv) may accumulate in a different order
than the M≥2 GEMM kernel (the PR-7 serving discovery), so a plan that
would degenerate falls back to fewer — ultimately one — chunk.  One
chunk IS the unfused reference program; ``HVD_TPU_FUSE=off`` pins it.

Env contract (validated at ``hvd.init``; both knobs ride the
control-plane HELLO env fingerprint — they select the compiled SPMD
program, so they must be uniform fleet-wide):

  HVD_TPU_FUSE=auto|on|off
      auto (default) = on: the transform is bitwise and costs nothing
      when the chunk plan degenerates, so there is no mesh on which
      auto should decline it.  ``off`` pins the unfused reference
      programs (the fallback-parity leg CI runs).
  HVD_TPU_FUSE_CHUNKS=<n>
      default 4.  Upper bound on chunks per fused group; plans clamp
      so every chunk keeps ≥ MIN_CHUNK_ROWS rows.

Host-side, :class:`FusedProgram` wraps each fused group's executable
with the repo's standard compiled-program services: AOT compile on
first dispatch with ``compiled.memory_analysis()`` harvested into the
memory planner, a manifest record (``variant: "fused"``) so a
relaunched fleet warm-starts the same groups from the compile-cache
directory, per-launch hvd-mem ledger charges via
the planner's shared byte formula (:func:`..memory.planner.
fused_group_bytes`), OOM-guarded dispatch, and the
``fused.groups_compiled`` / ``fused.launches`` telemetry documented
in docs/metrics.md.

Threading: everything here runs on the caller's (main/user) thread —
module state is one counter-protected lock, and no method is entered
from the runtime's thread fleet, so there are no ``# thread:`` roles
to declare.
"""

from __future__ import annotations

import os
import threading
from typing import Callable, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp

from .. import telemetry as _telemetry
from ..memory import ledger as _mem
from ..memory import oom as _oom
from ..memory import planner as _mem_planner

FUSE_ENV = "HVD_TPU_FUSE"
CHUNKS_ENV = "HVD_TPU_FUSE_CHUNKS"
_VALID_MODES = ("auto", "on", "off")
DEFAULT_CHUNKS = 4
# The PR-7 gemv trap: a 1-row chunk's dot may accumulate differently
# from the M≥2 GEMM kernel, breaking the bitwise contract.
MIN_CHUNK_ROWS = 2

# hvd-telemetry (docs/metrics.md "Fused computation-collective").
_M_GROUPS = _telemetry.counter(
    "fused.groups_compiled",
    "fused computation-collective executables compiled (one per "
    "FusedProgram, on its first dispatch)")
_M_LAUNCHES = _telemetry.counter(
    "fused.launches",
    "fused-group executable dispatches")


def fuse_mode() -> str:
    """The fusion knob, normalized (1/0 alias on/off)."""
    v = (os.environ.get(FUSE_ENV, "auto").strip().lower() or "auto")
    return {"1": "on", "0": "off"}.get(v, v)


def fuse_chunks() -> int:
    """Requested chunks per fused group (``HVD_TPU_FUSE_CHUNKS``)."""
    v = os.environ.get(CHUNKS_ENV, "").strip()
    if not v:
        return DEFAULT_CHUNKS
    try:
        n = int(v)
    except ValueError:
        raise ValueError(
            f"{CHUNKS_ENV}={v!r}: expected a positive integer "
            f"(chunks per fused computation-collective group)") \
            from None
    if n < 1:
        raise ValueError(
            f"{CHUNKS_ENV}={v!r}: expected a positive integer "
            f"(chunks per fused computation-collective group)")
    return n


def validate_env() -> None:
    """Fail ``hvd.init()`` — not the first fused dispatch — on a
    malformed fusion knob (same contract as the overlap/pipeline
    knobs; cross-rank uniformity is checked by the HELLO env
    fingerprint, ops/transport.py)."""
    v = os.environ.get(FUSE_ENV)
    if v and fuse_mode() not in _VALID_MODES:
        raise ValueError(
            f"{FUSE_ENV}={v!r}: expected one of "
            f"{'|'.join(_VALID_MODES)} (1/0 alias on/off)")
    fuse_chunks()


def enabled(override: Optional[bool] = None) -> bool:
    """Whether fused (chunk-interleaved) program bodies are emitted.
    ``auto`` means on: the transform is bitwise-identical by contract
    and free when the chunk plan degenerates to one chunk."""
    if override is not None:
        return bool(override)
    return fuse_mode() != "off"


def plan_chunks(n_rows: int, chunks: Optional[int] = None
                ) -> Tuple[Tuple[int, int], ...]:
    """Static ``(start, size)`` chunk plan for a reduction-free axis of
    ``n_rows`` rows.

    The requested chunk count (default :func:`fuse_chunks`) is clamped
    so every chunk keeps at least :data:`MIN_CHUNK_ROWS` rows; the
    remainder spreads one row at a time over the leading chunks, so the
    plan is a pure function of ``(n_rows, chunks)`` — part of the
    compiled program's identity, like every other SPMD knob."""
    want = fuse_chunks() if chunks is None else int(chunks)
    if want < 1:
        raise ValueError(f"chunks must be >= 1, got {want}")
    c = max(1, min(want, n_rows // MIN_CHUNK_ROWS))
    base, extra = divmod(n_rows, c)
    plan = []
    start = 0
    for i in range(c):
        size = base + (1 if i < extra else 0)
        plan.append((start, size))
        start += size
    return tuple(plan)


def _slice(x, start: int, size: int, axis: int):
    return jax.lax.dynamic_slice_in_dim(x, start, size, axis=axis)


def chunked_map(fn: Callable, x, *, axis: int = 0,
                chunks: Optional[int] = None,
                fuse: Optional[bool] = None):
    """Apply ``fn`` to static chunks of ``x`` along a reduction-free
    ``axis`` and concatenate — THE fused-group building block.

    ``fn`` is a chunk-shaped compute+collective pipeline (e.g. the MoE
    dispatch→FFN→combine round trip); emitting it per chunk inside one
    traced program lets XLA overlap chunk *i*'s collective with chunk
    *i+1*'s compute.  Disabled (or degenerate) plans call ``fn`` once
    on the whole array — exactly the unfused reference program."""
    if not enabled(fuse):
        return fn(x)
    plan = plan_chunks(int(x.shape[axis]), chunks)
    if len(plan) == 1:
        return fn(x)
    outs = [fn(_slice(x, start, size, axis)) for start, size in plan]
    return jnp.concatenate(outs, axis=axis)


def matmul_psum(x, w, *, axis_name: str, chunks: Optional[int] = None,
                fuse: Optional[bool] = None,
                preferred_element_type=jnp.float32):
    """``psum(x @ w)`` with the GEMM chunked along ``x``'s rows so each
    chunk's partial-product reduction overlaps the next chunk's GEMM
    (the Megatron row-parallel closer, fused).  Bitwise-identical to
    the unfused ``psum(dot(x, w))``: rows are reduction-free and psum
    is elementwise."""
    def leg(xc):
        part = jnp.dot(xc, w, preferred_element_type=preferred_element_type)
        return jax.lax.psum(part, axis_name)
    return chunked_map(leg, x, axis=0, chunks=chunks, fuse=fuse)


def matmul_reduce_scatter(x, w, *, axis_name: str,
                          scatter_axis: int = -1,
                          chunks: Optional[int] = None,
                          fuse: Optional[bool] = None,
                          preferred_element_type=jnp.float32):
    """``psum_scatter(x @ w)`` chunked along ``x``'s rows — the
    sequence-parallel variant of the row-parallel closer: each device
    keeps only its ``scatter_axis`` shard of the summed output."""
    def leg(xc):
        part = jnp.dot(xc, w, preferred_element_type=preferred_element_type)
        ax = scatter_axis if scatter_axis >= 0 else part.ndim + scatter_axis
        return jax.lax.psum_scatter(part, axis_name,
                                    scatter_dimension=ax, tiled=True)
    return chunked_map(leg, x, axis=0, chunks=chunks, fuse=fuse)


def all_gather_matmul(x, w, *, axis_name: str, gather_axis: int = -1,
                      chunks: Optional[int] = None,
                      fuse: Optional[bool] = None,
                      preferred_element_type=jnp.float32):
    """``all_gather(x) @ w`` chunked along ``x``'s rows — the
    sequence-parallel opener: chunk *i+1*'s gather flies while chunk
    *i* multiplies.  ``gather_axis`` is the sharded feature axis of
    ``x`` (the contraction axis of the dot)."""
    def leg(xc):
        ax = gather_axis if gather_axis >= 0 else xc.ndim + gather_axis
        xg = jax.lax.all_gather(xc, axis_name, axis=ax, tiled=True)
        return jnp.dot(xg, w, preferred_element_type=preferred_element_type)
    return chunked_map(leg, x, axis=0, chunks=chunks, fuse=fuse)


# ---------------------------------------------------------------------------
# Host-side fused-group executables
# ---------------------------------------------------------------------------

_state_lock = threading.Lock()
_n_groups = 0  # guarded_by: _state_lock


def _next_group_id() -> int:
    global _n_groups
    with _state_lock:
        _n_groups += 1
        return _n_groups


def fused_manifest_entry(name: str, mesh, shapes: Sequence[Tuple[int, ...]],
                         dtype, chunks: int) -> dict:
    """The persistent-cache manifest record for one fused group
    (``variant: "fused"`` — same file, same dedup/bound/atomic-rename
    contract as the megakernel and serving entries, so one
    compile-cache directory warms a relaunched fleet's fused groups
    too).  The chunk count is part of the record: it is part of
    the compiled program."""
    from . import megakernel as _mk

    return {
        "variant": "fused",
        "op": name,
        "dtype": str(jnp.dtype(dtype)),
        "shapes": [list(s) for s in shapes],
        "chunks": int(chunks),
        "digest": None,
        "mesh": _mk.mesh_fingerprint(tuple(mesh.devices.flat)),
    }


def fused_entries(directory: Optional[str] = None) -> list:
    """The manifest's fused-group records (warm-start consumer side)."""
    from . import megakernel as _mk

    d = directory or _mk.compile_cache_dir()
    if d is None:
        return []
    return [e for e in _mk.load_manifest(d)
            if e.get("variant") == "fused"]


class FusedProgram:
    """One fused computation-collective group's executable, wrapped in
    the repo's standard compiled-program services (the pipeline
    ``_AotProgram`` pattern): AOT compile on first dispatch —
    ``compiled.memory_analysis()`` harvested into the planner's
    per-mesh table, a ``variant: "fused"`` manifest record for warm
    start — then OOM-guarded dispatches that bump ``fused.launches``
    and charge the hvd-mem ledger with the planner's shared byte
    formula for the group's live set (output + one chunk's partial
    product).  Any compiled-call failure that is not
    RESOURCE_EXHAUSTED falls back to the jit wrapper permanently —
    semantics identical to plain jit."""

    __slots__ = ("name", "chunks", "_fn", "_compiled", "_mesh",
                 "_launch_bytes")

    def __init__(self, name: str, fn, *, mesh, chunks: int,
                 launch_bytes: int = 0) -> None:
        self.name = f"fused/{name}.g{_next_group_id()}"
        self.chunks = int(chunks)
        self._fn = fn
        self._compiled = None
        self._mesh = mesh
        self._launch_bytes = int(launch_bytes)

    def _record(self, args) -> None:
        shapes = [tuple(a.shape) for a in jax.tree_util.tree_leaves(args)]
        dtypes = [a.dtype for a in jax.tree_util.tree_leaves(args)]
        from . import megakernel as _mk

        _mk.record_manifest_entry(fused_manifest_entry(
            self.name, self._mesh, shapes,
            dtypes[0] if dtypes else jnp.float32, self.chunks))

    def __call__(self, *args):
        with _oom.guard(self.name):
            if self._compiled is None:
                try:
                    compiled = self._fn.lower(*args).compile()
                    _mem_planner.record_compiled(self.name, compiled)
                    self._compiled = compiled
                except Exception:  # noqa: BLE001 — AOT lowering is an
                    self._compiled = False  # optimization; jit is the
                    # semantic baseline
                _M_GROUPS.inc()
                self._record(args)
            if _telemetry.enabled():
                _M_LAUNCHES.inc()
            mem_on = _mem.enabled() and self._launch_bytes
            if mem_on:
                _mem.ledger.alloc("fused.launch", self._launch_bytes)
            try:
                if self._compiled:
                    try:
                        return self._compiled(*args)
                    except Exception as e:  # noqa: BLE001 — fall back
                        if _oom.is_resource_exhausted(e):
                            raise
                        self._compiled = False
                return self._fn(*args)
            finally:
                if mem_on:
                    _mem.ledger.free("fused.launch", self._launch_bytes)
