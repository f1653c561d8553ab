"""Multi-axis device-mesh topology for hybrid parallelism.

The reference's rank space is flat — one MPI_COMM_WORLD axis, because data
parallelism is its only strategy (SURVEY.md §2.3; reference
horovod/common/operations.cc:1176-1196).  A TPU pod is not flat: chips form
a torus of ICI links, and XLA shards programs over an N-dimensional
``jax.sharding.Mesh`` whose named axes map onto that torus.  This module
owns the axis vocabulary and mesh construction for every parallelism
strategy the framework offers beyond the reference's DP:

====== ============================ ======================================
axis   strategy                     what is sharded over it
====== ============================ ======================================
data   data parallel (DP)           batch; gradients psum over it
model  tensor parallel (TP)         weight matrices (heads / hidden dim)
seq    sequence/context par. (SP)   the sequence axis (ring attention)
pipe   pipeline parallel (PP)       transformer layer blocks
expert expert parallel (EP)         MoE experts (all_to_all routing)
====== ============================ ======================================

Axis ordering puts ``data`` outermost (it tolerates the slowest links —
gradient psum once per step, so it can ride DCN across slices) and
``model`` innermost (activations move every layer, so it must sit on the
fastest ICI neighbors).  This is the standard mapping from the public
scaling playbooks; XLA then lowers each collective onto the matching
links.

Expert parallelism conventionally *reuses* the data axis (experts sharded
over DP groups, tokens routed with all_to_all inside them), so ``expert``
only becomes its own mesh axis when explicitly requested.
"""

from __future__ import annotations

import os
from dataclasses import dataclass
from typing import Optional, Sequence, Tuple

import jax
import numpy as np

# Canonical axis names.  ``REPLICA_AXIS`` ("hvd") from core.state is the
# degenerate 1-D case used by the Horovod-parity API.
DATA_AXIS = "data"
MODEL_AXIS = "model"
SEQ_AXIS = "seq"
PIPE_AXIS = "pipe"
EXPERT_AXIS = "expert"

# Outermost → innermost mesh order (slowest → fastest links).
_AXIS_ORDER = (DATA_AXIS, PIPE_AXIS, EXPERT_AXIS, SEQ_AXIS, MODEL_AXIS)


@dataclass(frozen=True)
class ParallelConfig:
    """Degrees of each parallelism strategy.

    Any degree may be 1 (strategy disabled).  The product of all degrees
    must equal the number of devices the mesh is built over.  ``expert``
    defaults to 0 = "ride the data axis" (the conventional EP placement);
    set it >0 for a dedicated expert mesh axis.
    """

    data: int = 1
    model: int = 1
    seq: int = 1
    pipe: int = 1
    expert: int = 0

    @property
    def device_count(self) -> int:
        n = self.data * self.model * self.seq * self.pipe
        return n * (self.expert if self.expert > 0 else 1)

    def axis_sizes(self) -> dict:
        sizes = {DATA_AXIS: self.data, PIPE_AXIS: self.pipe,
                 SEQ_AXIS: self.seq, MODEL_AXIS: self.model}
        if self.expert > 0:
            sizes[EXPERT_AXIS] = self.expert
        return sizes


def _resolve(config, devices, degrees):
    if config is None:
        config = ParallelConfig(**degrees)
    elif degrees:
        raise TypeError("pass either a ParallelConfig or keyword degrees, "
                        "not both")
    devs = list(devices if devices is not None else jax.devices())
    if config.device_count != len(devs):
        raise ValueError(
            f"parallel config {config} needs {config.device_count} devices "
            f"but {len(devs)} were provided")
    return config, devs


def make_mesh(config: Optional[ParallelConfig] = None,
              devices: Optional[Sequence] = None,
              **degrees) -> jax.sharding.Mesh:
    """Build the multi-axis device mesh for a parallel configuration.

    Either pass a :class:`ParallelConfig` or axis degrees as keywords::

        mesh = make_mesh(data=2, model=2, seq=2)   # 8 devices

    Axes with degree 1 are still present in the mesh (size-1 axes are free)
    so the same model code works at any configuration.  Devices default to
    ``jax.devices()``; their count must equal the product of the degrees.
    """
    config, devs = _resolve(config, devices, degrees)
    sizes = config.axis_sizes()
    names = tuple(a for a in _AXIS_ORDER if a in sizes)
    shape = tuple(sizes[a] for a in names)
    arr = np.asarray(devs).reshape(shape)
    return jax.sharding.Mesh(arr, names)


def _hybrid_layout(devs, slice_of, names, sizes, dcn_factor) -> np.ndarray:
    """Explicit hybrid device layout: outer (DCN) blocks of each split
    axis cross slices, inner (ICI) blocks stay inside one slice — the
    same placement contract ``mesh_utils.create_hybrid_device_mesh``
    implements from hardware attributes, but computed from a declared
    slice assignment so it works with ANY devices (CPU test meshes,
    overridden topologies)."""
    groups: dict = {}
    for d in devs:
        groups.setdefault(slice_of(d), []).append(d)
    slice_ids = sorted(groups)
    if len({len(g) for g in groups.values()}) != 1:
        raise ValueError(
            f"slices must be equal-sized; got "
            f"{ {s: len(g) for s, g in groups.items()} }")
    shape = tuple(sizes[a] for a in names)
    ici_shape = [sizes[a] // dcn_factor.get(a, 1) for a in names]
    dcn_shape = [dcn_factor.get(a, 1) for a in names]
    arr = np.empty(shape, dtype=object)
    for idx in np.ndindex(shape):
        dcn_coord = [i // m for i, m in zip(idx, ici_shape)]
        ici_coord = [i % m for i, m in zip(idx, ici_shape)]
        sid = int(np.ravel_multi_index(dcn_coord, dcn_shape))
        wid = int(np.ravel_multi_index(ici_coord, ici_shape))
        arr[idx] = groups[slice_ids[sid]][wid]
    return arr


def make_hybrid_mesh(config: Optional[ParallelConfig] = None,
                     devices: Optional[Sequence] = None,
                     dcn_axes: Tuple[str, ...] = (DATA_AXIS,),
                     slice_map=None,
                     **degrees) -> jax.sharding.Mesh:
    """Build a mesh for a multi-slice (DCN-connected) TPU deployment.

    On a multi-slice pod, chips within a slice talk over ICI; slices talk
    over DCN.  The scaling recipe is to put the gradient-sync axes
    (``data``, and ``pipe`` when microbatches amortize it) across DCN —
    they communicate once per step — and keep every per-layer axis
    (``model``/``seq``/``expert``) inside a slice on ICI.  This wraps
    ``jax.experimental.mesh_utils.create_hybrid_device_mesh`` so the
    device order actually honors that placement; on single-slice (or CPU
    test) topologies it degrades to :func:`make_mesh` unchanged.

    ``dcn_axes`` lists the axes to lay across slices (outermost first).
    A DCN axis whose degree exceeds its share of the slice count is split
    between DCN and ICI — e.g. 2 slices x 4 chips with ``data=4, model=2``
    puts a 2-way data factor across DCN and a 2-way data factor on ICI
    inside each slice (the standard multi-slice DP recipe).

    ``slice_map`` overrides slice detection: a callable ``device →
    slice id`` or a ``device.id → slice id`` mapping.  Use it when the
    runtime misreports the topology — or to exercise the hybrid layout
    end-to-end on hardware without slices (the test suite trains over
    8 CPU devices declared as 2 virtual slices).
    """
    import math

    config, devs = _resolve(config, devices, degrees)

    if slice_map is not None:
        slice_of = slice_map if callable(slice_map) \
            else (lambda d: slice_map[d.id])
    else:
        slice_of = lambda d: getattr(d, "slice_index", 0)  # noqa: E731
    num_slices = len({slice_of(d) for d in devs})
    if num_slices <= 1:
        return make_mesh(config, devices=devs)

    sizes = config.axis_sizes()
    names = tuple(a for a in _AXIS_ORDER if a in sizes)
    for a in dcn_axes:
        if a not in names:
            raise ValueError(f"dcn axis {a!r} not in mesh axes {names}")
    # Split each DCN axis's degree into (cross-slice, in-slice) factors,
    # outermost first, until the slices are exactly tiled.
    remaining = num_slices
    dcn_factor = {}
    for a in dcn_axes:
        f = math.gcd(sizes[a], remaining)
        dcn_factor[a] = f
        remaining //= f
    if remaining != 1:
        raise ValueError(
            f"DCN axes {dcn_axes} with degrees "
            f"{[sizes[a] for a in dcn_axes]} cannot tile {num_slices} "
            f"slices; the cross-slice axes must tile the slices exactly.")
    if slice_map is not None:
        arr = _hybrid_layout(devs, slice_of, names, sizes, dcn_factor)
        return jax.sharding.Mesh(arr, names)
    from jax.experimental import mesh_utils

    mesh_shape = [sizes[a] // dcn_factor.get(a, 1) for a in names]
    dcn_shape = [dcn_factor.get(a, 1) for a in names]
    arr = mesh_utils.create_hybrid_device_mesh(
        mesh_shape, dcn_shape, devices=devs,
        allow_split_physical_axes=True)
    return jax.sharding.Mesh(arr, names)


# ---------------------------------------------------------------------------
# Replica-axis ICI x DCN hierarchy (the eager data plane's view of a
# multi-slice deployment)
# ---------------------------------------------------------------------------
# The eager collective path runs over the flat 1-D replica mesh; on a
# multi-slice pod that flatness hides a 2-level link topology — chips
# inside a slice talk over ICI, slices talk over DCN, and DCN is an
# order of magnitude slower.  A flat psum over the replica axis makes
# XLA move every byte across DCN n_slices times; the bandwidth-optimal
# decomposition is psum_scatter over ICI -> psum over DCN (1/ici_size
# of the bytes) -> all_gather over ICI, optionally quantizing the DCN
# leg only (cf. EQuARX, arXiv:2506.17615).  This block derives that
# hierarchy as axis_index_groups over the SAME flat replica axis, so
# the megakernel executor (ops/megakernel.py) can lower hierarchical
# collectives without re-meshing anything.
#
# Env contract (docs/performance.md):
#   HVD_TPU_HIERARCHICAL=auto|on|off   auto (default): hierarchical when
#                                      real multi-slice topology is
#                                      detected; on: also honor declared
#                                      virtual slices; off: always flat.
#   HVD_TPU_VIRTUAL_SLICES=<k>         declare k equal contiguous virtual
#                                      slices (CPU dryrun meshes / tests
#                                      / topology overrides).
HIERARCHICAL_ENV = "HVD_TPU_HIERARCHICAL"
VIRTUAL_SLICES_ENV = "HVD_TPU_VIRTUAL_SLICES"


def hierarchical_mode() -> str:
    mode = os.environ.get(HIERARCHICAL_ENV, "auto").lower()
    if mode not in ("auto", "on", "off"):
        raise ValueError(
            f"{HIERARCHICAL_ENV}={mode!r}: expected auto, on or off")
    return mode


def validate_env() -> None:
    """Fail ``hvd.init()`` — not the first collective — on malformed
    topology knobs.  These select the compiled SPMD program, so they
    must also be UNIFORM across ranks; the control-plane handshake
    cross-checks the combined fingerprint
    (ops/compression.env_fingerprint)."""
    hierarchical_mode()
    value = os.environ.get(VIRTUAL_SLICES_ENV)
    if value:
        try:
            int(value)
        except ValueError:
            raise ValueError(
                f"{VIRTUAL_SLICES_ENV}={value!r}: expected an "
                f"integer") from None


@dataclass(frozen=True)
class ReplicaHierarchy:
    """ICI x DCN decomposition of a flat replica axis of n devices.

    ``ici_groups``: one group per slice (positions along the replica
    axis); ``dcn_groups``: one group per in-slice position, pairing the
    k-th chip of every slice — together they express the two-level
    reduction as grouped collectives over the unchanged 1-D mesh.
    """

    n_slices: int
    ici_size: int
    ici_groups: Tuple[Tuple[int, ...], ...]
    dcn_groups: Tuple[Tuple[int, ...], ...]

    def slice_of_positions(self) -> Tuple[int, ...]:
        """Slice ordinal of every replica-axis position — the static
        lookup table quantized hierarchical kernels index with
        ``lax.axis_index`` to derive their per-leg noise/chunk
        coordinates (ops/megakernel.py)."""
        table = [0] * (self.n_slices * self.ici_size)
        for si, group in enumerate(self.ici_groups):
            for pos in group:
                table[pos] = si
        return tuple(table)


def replica_hierarchy(devices: Sequence) -> Optional[ReplicaHierarchy]:
    """The ICI x DCN hierarchy of ``devices`` (mesh order), or ``None``
    when the topology is flat / undecomposable / disabled.

    Real slice membership comes from ``device.slice_index`` (multi-slice
    runtimes); ``HVD_TPU_VIRTUAL_SLICES`` + ``HVD_TPU_HIERARCHICAL=on``
    declares contiguous virtual slices for dryrun meshes.  Unequal slice
    sizes degrade to flat — the grouped collectives need a rectangular
    decomposition.
    """
    mode = hierarchical_mode()
    if mode == "off":
        return None
    n = len(devices)
    if n < 2:
        return None
    slice_ids = [getattr(d, "slice_index", None) for d in devices]
    by_slice: dict = {}
    if any(s is not None for s in slice_ids) and len(
            {s for s in slice_ids if s is not None}) > 1:
        for pos, sid in enumerate(slice_ids):
            by_slice.setdefault(sid, []).append(pos)
    elif mode == "on":
        k = int(os.environ.get(VIRTUAL_SLICES_ENV, "0") or 0)
        if k > 1 and n % k == 0:
            ici = n // k
            by_slice = {s: list(range(s * ici, (s + 1) * ici))
                        for s in range(k)}
    if len(by_slice) < 2:
        return None
    sizes = {len(g) for g in by_slice.values()}
    if len(sizes) != 1:
        return None  # ragged slices: no rectangular decomposition
    ici_groups = tuple(tuple(by_slice[s]) for s in sorted(by_slice))
    ici = len(ici_groups[0])
    dcn_groups = tuple(tuple(g[i] for g in ici_groups)
                       for i in range(ici))
    return ReplicaHierarchy(
        n_slices=len(ici_groups), ici_size=ici,
        ici_groups=ici_groups, dcn_groups=dcn_groups)


def axis_size(axis: str) -> int:
    """Extent of ``axis`` inside traced code (static under shard_map)."""
    return jax.lax.axis_size(axis)


def axis_index(axis: str):
    """This shard's coordinate along ``axis`` inside traced code."""
    return jax.lax.axis_index(axis)


def mesh_axis_sizes(mesh: jax.sharding.Mesh) -> dict:
    return dict(zip(mesh.axis_names, mesh.devices.shape))


def validate_mesh(mesh: jax.sharding.Mesh,
                  required_axes: Tuple[str, ...]) -> None:
    """Raise with a clear message when a strategy is used on a mesh that
    lacks its axis (the analogue of the reference coordinator's explicit
    mismatch errors, operations.cc:255-461 — fail loudly, not with a
    compiler backtrace)."""
    missing = [a for a in required_axes if a not in mesh.axis_names]
    if missing:
        raise ValueError(
            f"mesh with axes {mesh.axis_names} is missing required "
            f"axes {missing}; build it with horovod_tpu.core.topology."
            f"make_mesh(...)")
