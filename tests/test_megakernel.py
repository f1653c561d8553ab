"""Megakernel executor tests (ops/megakernel.py).

Covers the dataplane PR's contracts:
  * numerical identity — megakernel results BITWISE-identical to the
    per-tensor eager path across dtypes, reduce ops, layouts and
    process sets;
  * dispatch-count regression — exactly one XLA executable launch per
    fusion group in the steady state (real launches counted at jax's
    dispatch choke point, utils/xla_dispatch.py);
  * donation safety — executor-owned input buffers are donated and
    never read (or even referenced) after dispatch;
  * hierarchical ICI×DCN allreduce — equivalent to the flat psum on a
    multi-slice dryrun mesh, including the compressed-DCN-leg variant;
  * executable-cache behavior — plan-digest keyed reuse, bounded size,
    the fusion-threshold invalidation hook;
  * the AVERAGE-divide folds on the non-megakernel kernels
    (reducescatter, replicated broadcast).
"""

import gc

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from horovod_tpu.ops import megakernel as mk
from horovod_tpu.utils import xla_dispatch


@pytest.fixture(autouse=True)
def _restore_megakernel():
    yield
    mk.set_enabled(None)


def _bitwise_equal(a, b):
    a, b = np.asarray(a), np.asarray(b)
    assert a.dtype == b.dtype and a.shape == b.shape
    assert a.tobytes() == b.tobytes(), "results not bitwise identical"


def _both_paths(run):
    """Run ``run(tag)`` with the eager executor and the megakernel and
    return both result lists."""
    mk.set_enabled(False)
    eager = run("eager")
    mk.set_enabled(True)
    fused = run("mega")
    return eager, fused


# ---------------------------------------------------------------------------
# Numerical identity
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("op_name", ["Average", "Sum", "Min", "Max",
                                     "Product"])
@pytest.mark.parametrize("dtype", [np.float32, np.int32])
def test_identity_fused_per_replica(hvd, op_name, dtype):
    n = hvd.size()
    op = getattr(hvd, op_name)
    rng = np.random.default_rng(42)
    if dtype == np.float32:
        base = [rng.standard_normal((n, 3, 2)).astype(dtype)
                for _ in range(4)]
    else:
        base = [rng.integers(1, 5, size=(n, 3, 2)).astype(dtype)
                for _ in range(4)]
    inputs = [hvd.shard(t) for t in base]

    def run(tag):
        return [np.asarray(o) for o in hvd.grouped_allreduce(
            inputs, op=op, name=f"mkid.{op_name}.{np.dtype(dtype).name}."
                                f"{tag}")]

    eager, fused = _both_paths(run)
    for a, b in zip(eager, fused):
        _bitwise_equal(a, b)


def test_identity_replicated_host_inputs(hvd):
    # Host numpy contributions (executor-owned → donated) in a fused
    # AVERAGE group, mixed shapes including a scalar.
    vals = [np.arange(6.0, dtype=np.float32).reshape(2, 3),
            np.float32(5.0),
            np.arange(4.0, dtype=np.float32)]

    def run(tag):
        return [np.asarray(o) for o in hvd.grouped_allreduce(
            [v.copy() if isinstance(v, np.ndarray) else v for v in vals],
            average=True, name=f"mkrep.{tag}")]

    eager, fused = _both_paths(run)
    for a, b in zip(eager, fused):
        _bitwise_equal(a, b)
    # Replicated average over identical contributions is the identity.
    np.testing.assert_array_equal(fused[0], vals[0])


def test_identity_single_tensor(hvd, monkeypatch):
    # Exact-mean assertion: pin the identity compressor (the CI leg
    # re-runs this file under HVD_TPU_COMPRESSION=int8).
    monkeypatch.setenv("HVD_TPU_COMPRESSION", "none")
    n = hvd.size()
    pr = hvd.shard(np.arange(n * 4, dtype=np.float32).reshape(n, 4))

    def run(tag):
        return [np.asarray(hvd.allreduce(pr, average=True,
                                         name=f"mksingle.{tag}"))]

    eager, fused = _both_paths(run)
    _bitwise_equal(eager[0], fused[0])
    np.testing.assert_allclose(
        fused[0], np.broadcast_to(
            np.arange(n * 4, dtype=np.float32).reshape(n, 4)
            .mean(axis=0), (n, 4)))


def test_identity_process_set(hvd):
    ps = hvd.add_process_set([0, 2, 5])
    x = np.arange(8.0, dtype=np.float32)

    def run(tag):
        return [np.asarray(hvd.allreduce(
            x, average=False, name=f"mkps.{tag}", process_set=ps))]

    eager, fused = _both_paths(run)
    _bitwise_equal(eager[0], fused[0])
    np.testing.assert_allclose(fused[0], x * 3)
    hvd.remove_process_set(ps)


def test_adasum_still_uses_dedicated_kernels(hvd):
    # Adasum never routes through the megakernel (its dots are
    # per-tensor); the dedicated ladder/VHDD kernels must keep running
    # under the default-on executor.
    n = hvd.size()
    launches0 = mk.stats.launches
    pr = hvd.shard(np.stack([np.full(4, float(i + 1), np.float32)
                             for i in range(n)]))
    out = np.asarray(hvd.allreduce(pr, op=hvd.Adasum, name="mkadasum"))
    assert out.shape == (n, 4)
    assert mk.stats.launches == launches0


# ---------------------------------------------------------------------------
# Dispatch-count regression (one executable launch per fusion group)
# ---------------------------------------------------------------------------

def _steady_cycle_dispatches(hvd, mega, tag):
    """XLA dispatches and megakernel launches of ONE steady-state cycle
    (six stable-named tensors, negotiation replayed from the response
    cache) on the chosen executor."""
    n = hvd.size()
    inputs = [hvd.shard(np.full((n, 16), float(j), np.float32))
              for j in range(6)]

    def cycle():
        # quiesce: the drain tick must not split the cycle into two
        # fused responses between submissions.
        with hvd.quiesce():
            hs = [hvd.allreduce_async(x, average=True, name=f"{tag}.{j}")
                  for j, x in enumerate(inputs)]
        return [hvd.synchronize(h) for h in hs]

    mk.set_enabled(mega)
    cycle()  # cold: compile + populate the response cache
    cycle()  # warm: the steady state (replayed negotiation)
    launches0 = mk.stats.launches
    with xla_dispatch.exact_scope():
        with xla_dispatch.record(all_threads=True) as scope:
            cycle()
    return scope.count, mk.stats.launches - launches0


def test_steady_state_one_dispatch_per_group(hvd):
    import horovod_tpu.core.state as state_mod

    st = state_mod.global_state()
    replayed0 = st.response_cache.stats.replayed_tensors
    dispatches, groups = _steady_cycle_dispatches(hvd, True, "mkdisp")
    assert groups >= 1
    # THE contract: the fused path issues exactly one executable launch
    # per fusion group — any eager-op creep (a stray reshape, slice or
    # divide on the drain path) breaks this equality.
    assert dispatches == groups, (
        f"steady-state cycle issued {dispatches} XLA dispatches for "
        f"{groups} fusion group(s); the megakernel contract is exactly "
        f"one per group")
    # And the cycle really was the steady state: negotiation replayed
    # from the response cache, not re-run.
    assert st.response_cache.stats.replayed_tensors > replayed0


def test_megakernel_at_least_halves_the_eager_executors_dispatches(hvd):
    """The per-tensor executor (HVD_TPU_MEGAKERNEL=0) surrounds each
    fused response with pack / slice / divide launches; the megakernel
    folds them into the one executable: at least 2x fewer dispatches
    for the same steady-state cycle."""
    eager, _ = _steady_cycle_dispatches(hvd, False, "mkred.eager")
    mega, _ = _steady_cycle_dispatches(hvd, True, "mkred.mega")
    assert mega >= 1 and eager >= 2 * mega, (eager, mega)


def test_no_creep_invariant_suite_wide(hvd):
    # Accumulated across every megakernel launch of the whole test
    # session (conftest arms HVD_TPU_COUNT_DISPATCHES for the suite):
    # a launch can contribute at most one observed dispatch — more
    # means eager ops crept inside the launch window.
    mk.set_enabled(True)
    x = np.ones(4, np.float32)
    hvd.allreduce(x, average=True, name="mkinv")
    assert mk.stats.launches > 0
    assert mk.stats.launch_dispatches <= mk.stats.launches


# ---------------------------------------------------------------------------
# Donation safety
# ---------------------------------------------------------------------------

def test_donated_inputs_dropped_after_dispatch(hvd, monkeypatch):
    monkeypatch.setenv("HVD_TPU_COMPRESSION", "none")
    mk.set_enabled(True)
    donated0 = mk.stats.donated_inputs
    src = np.arange(32.0, dtype=np.float32)
    out = np.asarray(hvd.allreduce(src, average=True, name="mkdonate"))
    np.testing.assert_array_equal(out, src)  # user's numpy untouched
    assert mk.stats.donated_inputs > donated0, \
        "host-converted contribution was not donated"
    # The executor must hold NO reference to the donated buffer after
    # dispatch (use-after-donate on the drain thread would raise on a
    # deleted array; a surviving reference here is the leak that makes
    # it possible).
    probes = list(mk.last_donated)
    assert probes
    gc.collect()
    alive = [r() for r in probes if r() is not None]
    for arr in alive:
        # jax may keep the object alive internally briefly; what must
        # hold is that donation went through — the buffer is deleted,
        # so ANY later read would raise instead of returning stale data.
        assert arr.is_deleted()


def test_user_arrays_never_donated(hvd, monkeypatch):
    monkeypatch.setenv("HVD_TPU_COMPRESSION", "none")
    n = hvd.size()
    x = hvd.shard(np.ones((n, 8), np.float32))  # user-held jax.Array
    hvd.allreduce(x, average=False, name="mkuser.1")
    # The user's array must remain fully usable afterwards.
    assert not x.is_deleted()
    out2 = np.asarray(hvd.allreduce(x, average=False, name="mkuser.2"))
    np.testing.assert_array_equal(out2, np.full((n, 8), float(n)))


# ---------------------------------------------------------------------------
# Hierarchical ICI×DCN allreduce
# ---------------------------------------------------------------------------

def test_hierarchical_matches_flat_psum(hvd, monkeypatch):
    # Flat vs hierarchical are bitwise-equal only uncompressed (the
    # quantized pipelines use different exchange topologies).
    monkeypatch.setenv("HVD_TPU_COMPRESSION", "none")
    n = hvd.size()
    # Integer-valued floats: exact under any summation order, so flat
    # vs hierarchical compare bitwise, not just allclose.
    base = [np.arange(n * 5, dtype=np.float32).reshape(n, 5) * (j + 1)
            for j in range(3)]
    inputs = [hvd.shard(t) for t in base]

    mk.set_enabled(True)
    flat = [np.asarray(o) for o in hvd.grouped_allreduce(
        inputs, average=True, name="mkhier.flat")]

    monkeypatch.setenv("HVD_TPU_HIERARCHICAL", "on")
    monkeypatch.setenv("HVD_TPU_VIRTUAL_SLICES", "2")
    hier0 = mk.stats.hier_launches
    hier = [np.asarray(o) for o in hvd.grouped_allreduce(
        inputs, average=True, name="mkhier.hier")]
    assert mk.stats.hier_launches > hier0, \
        "hierarchical kernel did not run on the declared 2-slice mesh"
    for a, b in zip(flat, hier):
        _bitwise_equal(a, b)


@pytest.mark.parametrize("slices", [2, 4])
def test_hierarchical_slice_counts(hvd, monkeypatch, slices):
    monkeypatch.setenv("HVD_TPU_COMPRESSION", "none")
    n = hvd.size()
    monkeypatch.setenv("HVD_TPU_HIERARCHICAL", "on")
    monkeypatch.setenv("HVD_TPU_VIRTUAL_SLICES", str(slices))
    mk.set_enabled(True)
    # Ragged flat length (13 not divisible by ici_size) exercises the
    # pad/unpad inside the kernel.
    pr = hvd.shard(np.arange(n * 13, dtype=np.float32).reshape(n, 13))
    out = np.asarray(hvd.allreduce(
        pr, average=False, name=f"mkhier.s{slices}"))
    ref = np.broadcast_to(
        np.arange(n * 13, dtype=np.float32).reshape(n, 13).sum(axis=0),
        (n, 13))
    np.testing.assert_array_equal(out, ref)


def test_hierarchical_dcn_compression(hvd, monkeypatch):
    monkeypatch.setenv("HVD_TPU_COMPRESSION", "none")
    n = hvd.size()
    monkeypatch.setenv("HVD_TPU_HIERARCHICAL", "on")
    monkeypatch.setenv("HVD_TPU_VIRTUAL_SLICES", "2")
    monkeypatch.setenv("HVD_TPU_DCN_COMPRESS", "bf16")
    mk.set_enabled(True)
    # Small integers: partial sums fit bf16's mantissa exactly, so the
    # compressed DCN leg is still exact here (the general case is
    # lossy by design — that is the bandwidth trade).
    pr = hvd.shard(np.ones((n, 8), np.float32))
    out = np.asarray(hvd.allreduce(pr, average=False, name="mkdcn"))
    np.testing.assert_array_equal(out, np.full((n, 8), float(n)))


def test_hierarchical_off_by_default(hvd):
    hier0 = mk.stats.hier_launches
    mk.set_enabled(True)
    n = hvd.size()
    hvd.allreduce(hvd.shard(np.ones((n, 4), np.float32)),
                  average=False, name="mkflat")
    assert mk.stats.hier_launches == hier0


def test_replica_hierarchy_detection(monkeypatch):
    from horovod_tpu.core import topology

    devs = jax.devices()
    assert topology.replica_hierarchy(devs) is None  # flat CPU mesh
    monkeypatch.setenv("HVD_TPU_HIERARCHICAL", "on")
    monkeypatch.setenv("HVD_TPU_VIRTUAL_SLICES", "2")
    h = topology.replica_hierarchy(devs)
    assert h is not None and h.n_slices == 2
    assert h.ici_size == len(devs) // 2
    assert h.ici_groups[0] == tuple(range(h.ici_size))
    assert h.dcn_groups[0] == (0, h.ici_size)
    # Off wins over declared slices.
    monkeypatch.setenv("HVD_TPU_HIERARCHICAL", "off")
    assert topology.replica_hierarchy(devs) is None
    # Non-tiling virtual slice count degrades to flat.
    monkeypatch.setenv("HVD_TPU_HIERARCHICAL", "on")
    monkeypatch.setenv("HVD_TPU_VIRTUAL_SLICES", "3")
    assert topology.replica_hierarchy(devs) is None
    monkeypatch.setenv("HVD_TPU_HIERARCHICAL", "bogus")
    with pytest.raises(ValueError):
        topology.replica_hierarchy(devs)


# ---------------------------------------------------------------------------
# Executable cache
# ---------------------------------------------------------------------------

def test_executable_reuse_across_cycles(hvd):
    n = hvd.size()
    inputs = [hvd.shard(np.ones((n, 8), np.float32)) for _ in range(3)]
    mk.set_enabled(True)

    def cycle(i):
        return hvd.grouped_allreduce(inputs, average=True,
                                     name=f"mkreuse.{i}")

    cycle(0)
    builds0, hits0 = mk.stats.builds, mk.stats.cache_hits
    cycle(1)  # same structure, different names → same executable
    assert mk.stats.builds == builds0, \
        "steady-state cycle recompiled its megakernel"
    assert mk.stats.cache_hits > hits0


def test_plan_digest_recorded(hvd):
    n = hvd.size()
    mk.set_enabled(True)
    x = hvd.shard(np.ones((n, 7), np.float32))
    hvd.allreduce(x, average=True, name="mkdigest")
    # The compiled executable is recorded under the PR 2 fusion-plan
    # digest: digest → spec → digest round-trips.
    with mk._lock:
        digests = dict(mk._digests)
    assert digests, "no plan digest recorded for a cold compile"
    for spec, digest in digests.items():
        assert mk.spec_for_digest(digest) == spec


def test_fusion_threshold_flushes_executables(hvd):
    import horovod_tpu.core.state as state_mod

    mk.set_enabled(True)
    x = np.ones(4, np.float32)
    hvd.allreduce(x, average=True, name="mkflush.1")
    assert mk.cache_size() > 0
    flushes0 = mk.stats.flushes
    st = state_mod.global_state()
    st.coordinator.set_fusion_threshold(32 << 20)
    assert mk.cache_size() == 0
    assert mk.stats.flushes > flushes0
    # And the executor rebuilds transparently afterwards.
    out = np.asarray(hvd.allreduce(x, average=True, name="mkflush.2"))
    np.testing.assert_array_equal(out, x)


# ---------------------------------------------------------------------------
# Satellite folds + vectorized ragged allgather
# ---------------------------------------------------------------------------

def test_ragged_allgather_vectorized(hvd):
    n = hvd.size()
    sizes = [3, 0, 2, 1, 4, 2, 1, 3][:n]
    parts = [np.arange(s * 2, dtype=np.float32).reshape(s, 2) + 100 * i
             for i, s in enumerate(sizes)]
    out = np.asarray(hvd.allgather(list(parts), name="mkragged"))
    np.testing.assert_array_equal(out, np.concatenate(parts, axis=0))


def test_ragged_allgather_all_empty(hvd):
    n = hvd.size()
    parts = [np.zeros((0, 3), np.float32) for _ in range(n)]
    out = np.asarray(hvd.allgather(list(parts), name="mkempty"))
    assert out.shape == (0, 3)


def test_reducescatter_average_fold(hvd):
    n = hvd.size()
    x = np.arange(n * 2 * 3, dtype=np.float32).reshape(n * 2, 3)
    out = np.asarray(hvd.reducescatter(x, average=True, name="mkrs.f"))
    ref = np.stack([x[r * 2:(r + 1) * 2] for r in range(n)])
    np.testing.assert_allclose(out, ref)  # mean of n identical copies
    # Integer AVERAGE floor-divides, matching _divide's contract.
    xi = np.full((n, 2), 5, np.int32)
    outi = np.asarray(hvd.reducescatter(xi, op=hvd.Average,
                                        name="mkrs.i"))
    np.testing.assert_array_equal(
        outi, np.full((n, 1, 2), (5 * n) // n, np.int32))


def test_broadcast_replicated_fold(hvd):
    x = np.arange(5.0, dtype=np.float32)
    out = np.asarray(hvd.broadcast(x, 0, name="mkbc.f"))
    np.testing.assert_array_equal(out, x)
    xi = np.arange(5, dtype=np.int32)
    outi = np.asarray(hvd.broadcast(xi, 0, name="mkbc.i"))
    np.testing.assert_array_equal(outi, xi)


def test_eager_fallback_disables_megakernel(hvd, monkeypatch):
    monkeypatch.setenv("HVD_TPU_COMPRESSION", "none")
    mk.set_enabled(False)
    launches0 = mk.stats.launches
    n = hvd.size()
    out = np.asarray(hvd.allreduce(
        hvd.shard(np.ones((n, 4), np.float32)), average=True,
        name="mkoff"))
    np.testing.assert_array_equal(out, np.ones((n, 4), np.float32))
    assert mk.stats.launches == launches0


# ---------------------------------------------------------------------------
# Quantized allreduce (ISSUE 6): int8/int4 wire reduction inside the
# megakernels, stochastic rounding, error-feedback residuals
# ---------------------------------------------------------------------------

from horovod_tpu.ops import compression as comp  # noqa: E402


def _rows_of(base, n):
    return np.concatenate([t.reshape(n, -1) for t in base], axis=1)


def _single_group_steps(hvd, inputs, base_name, op, steps=2, attempts=5):
    """Run ``steps`` grouped cycles under FRESH names until every cycle
    of an attempt landed in exactly ONE fused launch.  A concurrent
    background tick can legally split a group across two fused
    responses (see grouped_allreduce_async); the eager-quantized
    reference models the single-group packing, so a split attempt is
    retried rather than mis-compared."""
    for attempt in range(attempts):
        name = f"{base_name}.a{attempt}"
        results = []
        clean = True
        for _ in range(steps):
            launches0 = mk.stats.launches
            outs = hvd.grouped_allreduce(inputs, op=op, name=name)
            clean &= (mk.stats.launches - launches0) == 1
            results.append(outs)
        if clean:
            return results
    pytest.skip("background tick split every attempt's fusion group")


@pytest.mark.parametrize("codec", ["int8", "int4"])
@pytest.mark.parametrize("dtype", [np.float32, "bfloat16"])
def test_quantized_matches_eager_reference(hvd, monkeypatch, codec, dtype):
    """The fused quantized kernel must equal the eager-quantized
    REFERENCE (ops/compression.reference_allreduce) BITWISE — per
    codec, per dtype — including the error-feedback chain across two
    steps."""
    monkeypatch.setenv("HVD_TPU_COMPRESSION", codec)
    n = hvd.size()
    dt = jnp.bfloat16 if dtype == "bfloat16" else np.float32
    rng = np.random.default_rng(3)
    base = [np.asarray(jnp.asarray(
        rng.standard_normal((n, 48))).astype(dt)) for _ in range(3)]
    inputs = [hvd.shard(t) for t in base]
    rows = jnp.concatenate(
        [jnp.asarray(t).reshape(n, -1) for t in base], axis=1)
    fmt = comp.wire_format(codec)
    mk.set_enabled(True)

    outs, outs2 = _single_group_steps(
        hvd, inputs, f"qref.{codec}.{dtype}", hvd.Sum, steps=2)
    ref, res = comp.reference_allreduce(rows, fmt, 0)
    got = np.concatenate([np.asarray(o)[0].reshape(-1) for o in outs])
    assert np.asarray(ref).tobytes() == got.tobytes()

    # Step 2: the residual state carried by the executor must chain
    # exactly like the reference's.
    ref2, _ = comp.reference_allreduce(rows, fmt, 1, residuals=res)
    got2 = np.concatenate([np.asarray(o)[0].reshape(-1) for o in outs2])
    assert np.asarray(ref2).tobytes() == got2.tobytes()


def test_quantized_eager_executor_matches_megakernel(hvd, monkeypatch):
    """HVD_TPU_MEGAKERNEL=0 keeps the quantized semantics: the eager
    fallback runs the reference math with the same residual store and
    tick counter, so eager ≡ fused bitwise (fresh names → fresh
    ticks)."""
    monkeypatch.setenv("HVD_TPU_COMPRESSION", "int8")
    n = hvd.size()
    rng = np.random.default_rng(4)
    base = [rng.standard_normal((n, 32)).astype(np.float32)
            for _ in range(3)]
    inputs = [hvd.shard(t) for t in base]

    def tick_keys():
        with mk._lock:
            return len(mk._ticks)

    # Both legs must pack as ONE group (a background-tick split changes
    # the quantized grouping — see _single_group_steps); each clean leg
    # mints exactly one new tick key.
    for attempt in range(5):
        mk.set_enabled(False)
        t0 = tick_keys()
        eager = [np.asarray(o) for o in hvd.grouped_allreduce(
            inputs, average=True, name=f"qeager.e{attempt}")]
        eager_clean = tick_keys() - t0 == 1
        mk.set_enabled(True)
        t0 = tick_keys()
        fused = [np.asarray(o) for o in hvd.grouped_allreduce(
            inputs, average=True, name=f"qeager.m{attempt}")]
        if eager_clean and tick_keys() - t0 == 1:
            break
    else:
        pytest.skip("background tick split every attempt's group")
    for a, b in zip(eager, fused):
        _bitwise_equal(a, b)


def test_quantized_replicated_layout(hvd, monkeypatch):
    """Replicated (sp_rep) contributions quantize with SHARED noise so
    the result stays replicated; matches the reference's shared-noise
    mode bitwise."""
    monkeypatch.setenv("HVD_TPU_COMPRESSION", "int8")
    n = hvd.size()
    rng = np.random.default_rng(5)
    x = rng.standard_normal(64).astype(np.float32)
    mk.set_enabled(True)
    out = np.asarray(hvd.allreduce(x.copy(), average=False,
                                   name="qrep.1"))
    rows = np.broadcast_to(x[None], (n, 64))
    ref, _ = comp.reference_allreduce(rows, comp.wire_format("int8"), 0,
                                      shared_noise=True)
    assert np.asarray(ref).tobytes() == out.tobytes()


def test_quantized_process_set(hvd, monkeypatch):
    monkeypatch.setenv("HVD_TPU_COMPRESSION", "int8")
    ps = hvd.add_process_set([0, 2, 5])
    x = np.linspace(-2, 2, 48).astype(np.float32)
    mk.set_enabled(True)
    out = np.asarray(hvd.allreduce(x.copy(), average=False, name="qps.1",
                                   process_set=ps))
    rows = np.broadcast_to(x[None], (3, 48))
    ref, _ = comp.reference_allreduce(rows, comp.wire_format("int8"), 0,
                                      shared_noise=True)
    assert np.asarray(ref).tobytes() == out.tobytes()
    hvd.remove_process_set(ps)


def test_stochastic_rounding_bitwise_deterministic(hvd, monkeypatch):
    """Fixed HVD_TPU_QUANT_SEED + executor state reset ⇒ bitwise
    identical results across re-runs (the noise is a pure function of
    (seed, per-group tick, position))."""
    monkeypatch.setenv("HVD_TPU_COMPRESSION", "int8")
    monkeypatch.setenv("HVD_TPU_QUANT_SEED", "1234")
    n = hvd.size()
    rng = np.random.default_rng(6)
    base = rng.standard_normal((n, 40)).astype(np.float32)
    x = hvd.shard(base)
    mk.set_enabled(True)

    def two_steps():
        a = np.asarray(hvd.allreduce(x, average=True, name="qdet"))
        b = np.asarray(hvd.allreduce(x, average=True, name="qdet"))
        return a, b

    a1, b1 = two_steps()
    mk.flush("test: determinism reset")  # clears residuals AND ticks
    a2, b2 = two_steps()
    _bitwise_equal(a1, a2)
    _bitwise_equal(b1, b2)
    # A different seed must change the bits (the test has teeth).
    monkeypatch.setenv("HVD_TPU_QUANT_SEED", "99")
    mk.flush("test: reseed")
    a3 = np.asarray(hvd.allreduce(x, average=True, name="qdet"))
    assert np.asarray(a3).tobytes() != a1.tobytes()


def test_error_feedback_residual_carryover(hvd, monkeypatch):
    """EF makes the RUNNING MEAN of repeated reductions of the same
    value converge on the exact answer (the error telescopes); with EF
    off the quantization error persists.  Also: the executor owns
    exactly one flat residual buffer per group."""
    monkeypatch.setenv("HVD_TPU_COMPRESSION", "int8")
    n = hvd.size()
    rng = np.random.default_rng(8)
    base = rng.standard_normal((n, 33)).astype(np.float32)
    exact = base.sum(axis=0)
    x = hvd.shard(base)
    mk.set_enabled(True)
    res0 = mk.residual_count()

    outs = [np.asarray(hvd.allreduce(x, average=False, name="qef"))[0]
            for _ in range(8)]
    assert mk.residual_count() == res0 + 1
    running = np.mean(outs, axis=0)
    first_err = np.abs(outs[0] - exact).max()
    mean_err = np.abs(running - exact).max()
    assert mean_err < first_err or first_err == 0.0

    # EF off: no residual state is created.
    monkeypatch.setenv("HVD_TPU_QUANT_ERROR_FEEDBACK", "0")
    mk.flush("test: ef off")
    np.asarray(hvd.allreduce(x, average=False, name="qnoef"))
    assert mk.residual_count() == 0


def test_residual_flush_on_fusion_threshold_change(hvd, monkeypatch):
    monkeypatch.setenv("HVD_TPU_COMPRESSION", "int8")
    import horovod_tpu.core.state as state_mod

    n = hvd.size()
    mk.set_enabled(True)
    x = hvd.shard(np.ones((n, 24), np.float32))
    np.asarray(hvd.allreduce(x, average=True, name="qflush"))
    assert mk.residual_count() > 0
    st = state_mod.global_state()
    st.coordinator.set_fusion_threshold(16 << 20)
    assert mk.residual_count() == 0, \
        "plan invalidation must flush the error-feedback residuals"
    assert mk.cache_size() == 0


def test_compression_state_checkpoint_roundtrip(hvd, monkeypatch):
    """compression_state()/load_compression_state(): restoring a
    snapshot resumes the EF chain exactly — the replayed step is
    bitwise identical to the original continuation."""
    monkeypatch.setenv("HVD_TPU_COMPRESSION", "int8")
    n = hvd.size()
    rng = np.random.default_rng(9)
    x = hvd.shard(rng.standard_normal((n, 48)).astype(np.float32))
    mk.set_enabled(True)
    np.asarray(hvd.allreduce(x, average=False, name="qckpt"))  # step 0
    snap = hvd.compression_state()
    assert snap["residuals"] and snap["ticks"]
    out1 = np.asarray(hvd.allreduce(x, average=False, name="qckpt"))
    mk.flush("test: simulate relaunch")
    hvd.load_compression_state(snap)
    out1b = np.asarray(hvd.allreduce(x, average=False, name="qckpt"))
    _bitwise_equal(out1, out1b)


def test_per_tensor_policy_partitions_groups(hvd, monkeypatch):
    """Per-tensor selection: rules route one tensor uncompressed while
    its groupmates quantize — the fusion group splits into one fused
    launch per wire format, and the uncompressed tensor stays exact."""
    monkeypatch.delenv("HVD_TPU_COMPRESSION", raising=False)
    n = hvd.size()
    rng = np.random.default_rng(10)
    emb = rng.standard_normal((n, 64)).astype(np.float32)
    # Integer-valued floats: exact under any psum association, so the
    # uncompressed bucket can be checked for EXACT equality.
    ln = np.tile(np.arange(32, dtype=np.float32), (n, 1))
    inputs = [hvd.shard(emb), hvd.shard(ln)]
    hvd.set_compression(default="int8",
                        rules=[(r"\.ln_scale$", "none")])
    try:
        mk.set_enabled(True)
        launches0 = mk.stats.launches
        quant0 = mk.stats.quant_launches
        hs = [hvd.allreduce_async(inputs[0], op=hvd.Sum,
                                  name="qpol.emb"),
              hvd.allreduce_async(inputs[1], op=hvd.Sum,
                                  name="qpol.ln_scale")]
        outs = [hvd.synchronize(h) for h in hs]
        assert mk.stats.launches - launches0 == 2, \
            "mixed-format group must split into one launch per format"
        assert mk.stats.quant_launches - quant0 == 1
        # The rule-matched tensor rode the exact psum.
        np.testing.assert_array_equal(
            np.asarray(outs[1])[0], ln[0] * n)
        # The embedding was quantized (teeth: its result differs from
        # the exact sum but stays within the codebook's error bound).
        got = np.asarray(outs[0])[0]
        exact = emb.sum(axis=0)
        assert got.tobytes() != exact.tobytes()
        assert np.abs(got - exact).max() < 1.0
    finally:
        hvd.set_compression()


def test_quantized_hierarchical_per_leg(hvd, monkeypatch):
    """Per-leg composition on a 2-virtual-slice mesh: ICI full
    precision + DCN inheriting the group's int8 (the default), then an
    explicitly quantized ICI leg — both within the codebook error
    bound, deterministic under a fixed seed, still one dispatch."""
    monkeypatch.setenv("HVD_TPU_COMPRESSION", "int8")
    monkeypatch.setenv("HVD_TPU_HIERARCHICAL", "on")
    monkeypatch.setenv("HVD_TPU_VIRTUAL_SLICES", "2")
    n = hvd.size()
    rng = np.random.default_rng(11)
    base = rng.standard_normal((n, 80)).astype(np.float32)
    exact = base.sum(axis=0)
    x = hvd.shard(base)
    mk.set_enabled(True)

    hier0 = mk.stats.hier_launches
    out = np.asarray(hvd.allreduce(x, average=False, name="qhier.dcn"))
    assert mk.stats.hier_launches > hier0
    assert np.abs(out[0] - exact).max() < 1.0
    out_b = np.asarray(hvd.allreduce(x, average=False, name="qhier.dcn2"))
    # Same (seed, tick 0) under different names: the hierarchical
    # path's noise is name-independent, so equal inputs reduce equally.
    _bitwise_equal(out, out_b)

    monkeypatch.setenv("HVD_TPU_ICI_COMPRESS", "int8")
    out_ici = np.asarray(hvd.allreduce(x, average=False,
                                       name="qhier.ici"))
    assert np.abs(out_ici[0] - exact).max() < 1.5
    assert out_ici.tobytes() != out.tobytes()  # different pipeline


def test_dcn_quant_without_policy(hvd, monkeypatch):
    """HVD_TPU_DCN_COMPRESS=int8 quantizes ONLY the cross-slice leg —
    no policy, no residuals; the ICI legs stay full precision."""
    monkeypatch.setenv("HVD_TPU_COMPRESSION", "none")
    monkeypatch.setenv("HVD_TPU_HIERARCHICAL", "on")
    monkeypatch.setenv("HVD_TPU_VIRTUAL_SLICES", "2")
    monkeypatch.setenv("HVD_TPU_DCN_COMPRESS", "int8")
    n = hvd.size()
    rng = np.random.default_rng(12)
    base = rng.standard_normal((n, 64)).astype(np.float32)
    x = hvd.shard(base)
    mk.set_enabled(True)
    res0 = mk.residual_count()
    quant0 = mk.stats.quant_launches
    out = np.asarray(hvd.allreduce(x, average=False, name="qdcnonly"))
    assert mk.stats.quant_launches > quant0
    assert mk.residual_count() == res0  # leg codecs carry no EF state
    assert np.abs(out[0] - base.sum(axis=0)).max() < 1.0


@pytest.mark.parametrize("codec, lo, hi", [("int8", 3.0, 4.0),
                                           ("int4", 6.0, 8.0)])
def test_wire_bytes_accounting_and_telemetry(hvd, monkeypatch, codec,
                                             lo, hi):
    """Bytes-on-wire accounting: int8 must record ~4x (int4 ~8x) fewer
    wire than logical bytes, the collective.wire_bytes histogram must
    see the launch, and the compression.ratio gauge must report the
    ratio."""
    from horovod_tpu import telemetry

    monkeypatch.setenv("HVD_TPU_COMPRESSION", codec)
    n = hvd.size()
    mk.set_enabled(True)
    w0, l0 = mk.stats.wire_bytes, mk.stats.logical_bytes
    x = hvd.shard(np.ones((n, 256), np.float32))
    np.asarray(hvd.allreduce(x, average=True, name=f"qwire.{codec}"))
    wire = mk.stats.wire_bytes - w0
    logical = mk.stats.logical_bytes - l0
    assert logical > 0 and wire > 0
    ratio = logical / wire
    assert lo <= ratio <= hi, ratio
    snap = telemetry.metrics()
    assert snap["collective.wire_bytes"]["count"] >= 1
    assert snap["compression.ratio"]["value"] >= 1.0
    assert snap["megakernel.quant_launches"]["value"] >= 1


def test_quantized_one_dispatch_per_group(hvd, monkeypatch):
    """The tentpole's zero-extra-dispatch claim: quantize → exchange →
    dequantize → residual update all compile into the ONE fused
    executable per group, steady state included."""
    monkeypatch.setenv("HVD_TPU_COMPRESSION", "int8")
    n = hvd.size()
    inputs = [hvd.shard(np.full((n, 32), float(j + 1), np.float32))
              for j in range(4)]
    mk.set_enabled(True)

    def cyc():
        hs = [hvd.allreduce_async(t, average=True, name=f"qdisp.{j}")
              for j, t in enumerate(inputs)]
        return [hvd.synchronize(h) for h in hs]

    cyc()
    cyc()
    launches0 = mk.stats.launches
    with xla_dispatch.exact_scope():
        with xla_dispatch.record(all_threads=True) as scope:
            cyc()
    groups = mk.stats.launches - launches0
    assert groups >= 1
    assert scope.count == groups, (
        f"quantized steady-state cycle issued {scope.count} dispatches "
        f"for {groups} fusion group(s)")


def test_int_dtypes_and_non_sum_ops_never_quantize(hvd, monkeypatch):
    monkeypatch.setenv("HVD_TPU_COMPRESSION", "int8")
    n = hvd.size()
    mk.set_enabled(True)
    quant0 = mk.stats.quant_launches
    xi = hvd.shard(np.full((n, 32), 3, np.int32))
    outi = np.asarray(hvd.allreduce(xi, average=False, name="qint"))
    np.testing.assert_array_equal(outi[0], np.full(32, 3 * n))
    xf = hvd.shard(np.arange(n * 32, dtype=np.float32).reshape(n, 32))
    outm = np.asarray(hvd.allreduce(xf, op=hvd.Max, name="qmax"))
    np.testing.assert_array_equal(
        outm[0], np.arange(n * 32, dtype=np.float32).reshape(n, 32)
        .max(axis=0))
    assert mk.stats.quant_launches == quant0


def test_dcn_none_opts_out_of_inheritance(hvd, monkeypatch):
    """An EXPLICIT HVD_TPU_DCN_COMPRESS=none pins the DCN leg to full
    precision even when the group's policy is quantized (unset = the
    inheritance default) — review finding: the opt-out must exist."""
    monkeypatch.setenv("HVD_TPU_COMPRESSION", "int8")
    monkeypatch.setenv("HVD_TPU_HIERARCHICAL", "on")
    monkeypatch.setenv("HVD_TPU_VIRTUAL_SLICES", "2")
    monkeypatch.setenv("HVD_TPU_DCN_COMPRESS", "none")
    n = hvd.size()
    mesh_key = tuple(jax.devices())
    fmt = mk._compression.wire_format("int8")
    hier = mk.hierarchy_for(mesh_key, "psum", np.float32, group_fmt=fmt)
    assert hier is not None
    assert hier.dcn_quant is None and hier.wire_dtype is None
    # Unset: the group's quantized format inherits onto the DCN leg.
    monkeypatch.delenv("HVD_TPU_DCN_COMPRESS")
    hier2 = mk.hierarchy_for(mesh_key, "psum", np.float32,
                             group_fmt=fmt)
    assert hier2.dcn_quant is not None \
        and hier2.dcn_quant.name == "int8"
    # And end to end: the pinned-none run reduces exactly for
    # integer-valued floats on the ICI+DCN full-precision pipeline...
    base = np.arange(n * 32, dtype=np.float32).reshape(n, 32)
    monkeypatch.setenv("HVD_TPU_DCN_COMPRESS", "none")
    monkeypatch.setenv("HVD_TPU_COMPRESSION", "none")
    out = np.asarray(hvd.allreduce(hvd.shard(base), average=False,
                                   name="qoptout"))
    np.testing.assert_array_equal(out[0], base.sum(axis=0))
