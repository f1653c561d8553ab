"""hvd-fuse unit tests: fused computation-collective kernels
(ops/fused.py).

The bitwise contract is the load-bearing one — every fused primitive
must reproduce its unfused reference program's bytes exactly (chunking
runs along reduction-free axes only).  The
integration call sites have their own suites (test_tensor_parallel.py,
test_expert_parallel.py, test_pipeline_parallel.py)."""

import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import PartitionSpec as P

from horovod_tpu.core.topology import MODEL_AXIS, make_mesh
from horovod_tpu.memory import ledger as ledger_mod
from horovod_tpu.memory import planner
from horovod_tpu.ops import fused as F


def _mesh(n=4):
    return make_mesh(model=n, devices=jax.devices()[:n])


# ---------------------------------------------------------------------------
# Chunk planning
# ---------------------------------------------------------------------------

def test_plan_chunks_even_split():
    assert F.plan_chunks(16, 4) == ((0, 4), (4, 4), (8, 4), (12, 4))


def test_plan_chunks_remainder_spreads_over_leading_chunks():
    assert F.plan_chunks(10, 4) == ((0, 3), (3, 3), (6, 2), (8, 2))


def test_plan_chunks_clamps_to_min_chunk_rows():
    # 6 rows / 4 requested → only 3 chunks keep >= MIN_CHUNK_ROWS.
    assert F.plan_chunks(6, 4) == ((0, 2), (2, 2), (4, 2))
    # Fewer rows than 2*MIN_CHUNK_ROWS: degenerate single-chunk plan —
    # the unfused reference program (the PR-7 gemv trap guard).
    assert F.plan_chunks(3, 4) == ((0, 3),)
    assert F.plan_chunks(1, 8) == ((0, 1),)


def test_plan_chunks_covers_every_row_exactly_once():
    for rows in (2, 5, 7, 16, 33):
        for want in (1, 2, 3, 4, 8):
            plan = F.plan_chunks(rows, want)
            covered = [s for start, size in plan
                       for s in range(start, start + size)]
            assert covered == list(range(rows)), (rows, want, plan)
            assert all(size >= F.MIN_CHUNK_ROWS for _, size in plan) \
                or len(plan) == 1


def test_plan_chunks_rejects_nonpositive():
    with pytest.raises(ValueError):
        F.plan_chunks(8, 0)


# ---------------------------------------------------------------------------
# Env knobs
# ---------------------------------------------------------------------------

def test_fuse_mode_normalizes_aliases(monkeypatch):
    monkeypatch.setenv(F.FUSE_ENV, "1")
    assert F.fuse_mode() == "on"
    monkeypatch.setenv(F.FUSE_ENV, "0")
    assert F.fuse_mode() == "off"
    assert not F.enabled()
    monkeypatch.delenv(F.FUSE_ENV)
    assert F.fuse_mode() == "auto"
    assert F.enabled()  # auto means on: the transform is bitwise


def test_enabled_override_beats_env(monkeypatch):
    monkeypatch.setenv(F.FUSE_ENV, "off")
    assert F.enabled(True)
    monkeypatch.setenv(F.FUSE_ENV, "on")
    assert not F.enabled(False)


def test_validate_env_rejects_bad_mode(monkeypatch):
    monkeypatch.setenv(F.FUSE_ENV, "sideways")
    with pytest.raises(ValueError, match="HVD_TPU_FUSE"):
        F.validate_env()


@pytest.mark.parametrize("bad", ["zero", "0", "-2", "1.5"])
def test_validate_env_rejects_bad_chunks(monkeypatch, bad):
    monkeypatch.delenv(F.FUSE_ENV, raising=False)
    monkeypatch.setenv(F.CHUNKS_ENV, bad)
    with pytest.raises(ValueError, match="HVD_TPU_FUSE_CHUNKS"):
        F.validate_env()


def test_fuse_chunks_env(monkeypatch):
    monkeypatch.delenv(F.CHUNKS_ENV, raising=False)
    assert F.fuse_chunks() == F.DEFAULT_CHUNKS
    monkeypatch.setenv(F.CHUNKS_ENV, "7")
    assert F.fuse_chunks() == 7


def test_init_validates_fusion_knobs(monkeypatch):
    # The knob fails hvd.init(), not the first fused dispatch (the
    # validate_env chain in core/state.init).
    import horovod_tpu as hvd

    monkeypatch.setenv(F.FUSE_ENV, "sideways")
    with pytest.raises(ValueError, match="HVD_TPU_FUSE"):
        hvd.init(devices=jax.devices())
    monkeypatch.delenv(F.FUSE_ENV)


def test_fusion_knobs_ride_env_fingerprint():
    # Both knobs select the compiled SPMD program, so they must be in
    # the HELLO env fingerprint (fleet-uniformity check).
    from horovod_tpu.ops import compression as _compression

    assert F.FUSE_ENV in _compression._SPMD_ENV_KNOBS
    assert F.CHUNKS_ENV in _compression._SPMD_ENV_KNOBS


# ---------------------------------------------------------------------------
# chunked_map
# ---------------------------------------------------------------------------

def test_chunked_map_off_calls_fn_once_on_whole_array():
    calls = []

    def fn(x):
        calls.append(x.shape)
        return x * 2

    x = jnp.ones((16, 4))
    out = F.chunked_map(fn, x, chunks=4, fuse=False)
    assert calls == [(16, 4)]
    assert out.shape == (16, 4)


def test_chunked_map_degenerate_plan_is_reference_program():
    calls = []

    def fn(x):
        calls.append(x.shape)
        return x

    F.chunked_map(fn, jnp.ones((3, 4)), chunks=4, fuse=True)
    assert calls == [(3, 4)]  # < 2*MIN_CHUNK_ROWS rows: one chunk


def test_chunked_map_concatenates_chunks_in_order():
    x = jnp.arange(16.0).reshape(16, 1)
    out = F.chunked_map(lambda c: c + 100.0, x, chunks=4, fuse=True)
    assert np.asarray(out).tobytes() == np.asarray(x + 100.0).tobytes()


def test_chunked_map_respects_axis():
    x = jnp.arange(32.0).reshape(2, 16)
    out = F.chunked_map(lambda c: c * 3.0, x, axis=1, chunks=4,
                        fuse=True)
    assert np.asarray(out).tobytes() == np.asarray(x * 3.0).tobytes()


# ---------------------------------------------------------------------------
# Fused primitives: bitwise vs the unfused reference program
# ---------------------------------------------------------------------------

def _bitwise(mesh, fn_fused, fn_ref, *args):
    run = lambda fn: np.asarray(jax.jit(jax.shard_map(
        fn, mesh=mesh, in_specs=tuple(P() for _ in args), out_specs=P(),
        check_vma=False))(*args)).tobytes()
    return run(fn_fused) == run(fn_ref)


@pytest.mark.parametrize("chunks", [2, 4])
def test_matmul_psum_bitwise(chunks):
    mesh = _mesh()
    rng = np.random.default_rng(0)
    x = jnp.asarray(rng.standard_normal((16, 8)).astype(np.float32))
    w = jnp.asarray(rng.standard_normal((8, 8)).astype(np.float32))
    assert _bitwise(
        mesh,
        lambda x, w: F.matmul_psum(x, w, axis_name=MODEL_AXIS,
                                   chunks=chunks, fuse=True),
        lambda x, w: jax.lax.psum(
            jnp.dot(x, w, preferred_element_type=jnp.float32),
            MODEL_AXIS),
        x, w)


@pytest.mark.parametrize("chunks", [2, 4])
def test_matmul_reduce_scatter_bitwise(chunks):
    mesh = _mesh()
    rng = np.random.default_rng(1)
    x = jnp.asarray(rng.standard_normal((16, 8)).astype(np.float32))
    w = jnp.asarray(rng.standard_normal((8, 8)).astype(np.float32))
    assert _bitwise(
        mesh,
        lambda x, w: F.matmul_reduce_scatter(
            x, w, axis_name=MODEL_AXIS, chunks=chunks, fuse=True),
        lambda x, w: jax.lax.psum_scatter(
            jnp.dot(x, w, preferred_element_type=jnp.float32),
            MODEL_AXIS, scatter_dimension=1, tiled=True),
        x, w)


@pytest.mark.parametrize("chunks", [2, 4])
def test_all_gather_matmul_bitwise(chunks):
    mesh = _mesh()
    rng = np.random.default_rng(2)
    x = jnp.asarray(rng.standard_normal((16, 8)).astype(np.float32))
    w = jnp.asarray(rng.standard_normal((32, 8)).astype(np.float32))
    assert _bitwise(
        mesh,
        lambda x, w: F.all_gather_matmul(
            x, w, axis_name=MODEL_AXIS, chunks=chunks, fuse=True),
        lambda x, w: jnp.dot(
            jax.lax.all_gather(x, MODEL_AXIS, axis=1, tiled=True), w,
            preferred_element_type=jnp.float32),
        x, w)


def _psum_group(mesh):
    """The jitted chunked matmul+psum group over ``mesh`` (4 chunks)."""
    return jax.jit(jax.shard_map(
        lambda x, w: F.matmul_psum(x, w, axis_name=MODEL_AXIS,
                                   chunks=4, fuse=True),
        mesh=mesh, in_specs=(P(), P()), out_specs=P(),
        check_vma=False))


def test_fused_group_is_one_dispatch():
    """A chunked matmul+psum group stays ONE XLA executable launch:
    the chunks are emitted inside the one program, not as launches."""
    from horovod_tpu.utils import xla_dispatch

    mesh = _mesh()
    x = jnp.ones((16, 8), jnp.float32)
    w = jnp.ones((8, 8), jnp.float32)
    fn = _psum_group(mesh)
    jax.block_until_ready(fn(x, w))
    with xla_dispatch.exact_scope():
        with xla_dispatch.record(all_threads=True) as scope:
            jax.block_until_ready(fn(x, w))
    assert scope.count == 1


# ---------------------------------------------------------------------------
# Host-side services: FusedProgram, manifest, ledger, telemetry
# ---------------------------------------------------------------------------

def test_fused_program_compiles_once_and_matches_jit():
    mesh = _mesh()
    rng = np.random.default_rng(3)
    x = jnp.asarray(rng.standard_normal((16, 8)).astype(np.float32))
    w = jnp.asarray(rng.standard_normal((8, 8)).astype(np.float32))
    fn = _psum_group(mesh)
    g0 = F._M_GROUPS.value
    l0 = F._M_LAUNCHES.value
    prog = F.FusedProgram("test/psum", fn, mesh=mesh, chunks=4)
    a = prog(x, w)
    b = prog(x, w)
    assert F._M_GROUPS.value == g0 + 1  # one compile, two launches
    assert F._M_LAUNCHES.value == l0 + 2
    want = np.asarray(fn(x, w)).tobytes()
    assert np.asarray(a).tobytes() == want
    assert np.asarray(b).tobytes() == want


def test_fused_program_ledger_charge_is_scoped_to_the_launch():
    mesh = _mesh()
    x = jnp.ones((16, 8), jnp.float32)
    w = jnp.ones((8, 8), jnp.float32)
    fn = _psum_group(mesh)
    nbytes = planner.fused_group_bytes((16, 8), 4)
    led = ledger_mod.ledger
    led.set("fused.launch", 0)
    prog = F.FusedProgram("test/ledger", fn, mesh=mesh, chunks=4,
                          launch_bytes=nbytes)
    prog(x, w)
    # Charged for the launch window, fully released after.
    assert led.bytes_by_category().get("fused.launch", 0) == 0
    if ledger_mod.enabled():
        assert led.peak_by_category().get("fused.launch", 0) >= nbytes


def test_fused_manifest_entry_round_trip(tmp_path, monkeypatch):
    from horovod_tpu.ops import megakernel as mk

    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path))
    mesh = _mesh()
    entry = F.fused_manifest_entry("fused/test.g1", mesh,
                                   [(16, 8), (8, 8)], jnp.float32, 4)
    assert entry["variant"] == "fused"
    assert entry["chunks"] == 4
    mk.record_manifest_entry(entry)
    mk.record_manifest_entry(entry)  # dedup
    got = F.fused_entries(str(tmp_path))
    assert len(got) == 1
    assert got[0]["op"] == "fused/test.g1"
    assert got[0]["chunks"] == 4


def test_fused_group_bytes_formula():
    # Full output + the largest chunk's partial product, in items of
    # the dtype.
    assert planner.fused_group_bytes((16, 8), 4) == (128 + 32) * 4
    # Remainder: ceil(10/4)=3 rows in the largest chunk.
    assert planner.fused_group_bytes((10, 4), 4) == (40 + 12) * 4
    # One chunk: the whole output doubles (reference program).
    assert planner.fused_group_bytes((16, 8), 1) == (128 + 128) * 4
    assert planner.fused_group_bytes((16, 8), 4, dtype="bfloat16") \
        == (128 + 32) * 2
