"""Pallas flash-attention kernel vs the O(seq²) reference.

Style follows the reference's self-verifying collective tests
(test/test_tensorflow.py:34-63): compute both ways, compare with a float
tolerance.  Runs in Pallas interpreter mode on the CPU test mesh.
"""

import re

import jax
import jax.numpy as jnp
import pytest

from horovod_tpu.ops.flash_attention import (flash_attention,
                                             flash_attention_with_lse,
                                             mha_reference)

TOL = 5e-5


@pytest.fixture(autouse=True)
def _force_pallas_interpreter(monkeypatch):
    """These tests verify the Pallas kernels themselves: disable the
    dense-jnp CPU fallback that the rest of the suite rides."""
    monkeypatch.setenv("HVD_TPU_FLASH_INTERPRET", "1")


def _qkv(b=2, h=3, s=128, d=32, dtype=jnp.float32, seed=0):
    ks = jax.random.split(jax.random.PRNGKey(seed), 3)
    return tuple(jax.random.normal(k, (b, h, s, d), dtype) for k in ks)


@pytest.mark.parametrize("causal", [False, True])
@pytest.mark.parametrize("block", [32, 64, 128])
def test_forward_matches_reference(causal, block):
    q, k, v = _qkv()
    o = flash_attention(q, k, v, causal=causal, block_q=block,
                        block_k=block)
    ref = mha_reference(q, k, v, causal=causal)
    assert jnp.max(jnp.abs(o - ref)) < TOL


@pytest.mark.parametrize("causal", [False, True])
def test_backward_matches_reference(causal):
    q, k, v = _qkv(s=96, d=16)
    w = jnp.cos(jnp.arange(16))

    def f(q, k, v):
        return jnp.sum(flash_attention(q, k, v, causal=causal, block_q=32,
                                       block_k=32) * w)

    def g(q, k, v):
        return jnp.sum(mha_reference(q, k, v, causal=causal) * w)

    got = jax.grad(f, (0, 1, 2))(q, k, v)
    want = jax.grad(g, (0, 1, 2))(q, k, v)
    for a, b in zip(got, want):
        assert jnp.max(jnp.abs(a - b)) < 1e-4


def test_uneven_blocks():
    # seq not a multiple of the block size exercises the pad/mask tail.
    q, k, v = _qkv(s=80, d=16)
    o = flash_attention(q, k, v, causal=True, block_q=32, block_k=32)
    ref = mha_reference(q, k, v, causal=True)
    assert jnp.max(jnp.abs(o - ref)) < TOL

    w = jnp.cos(jnp.arange(16))
    got = jax.grad(
        lambda q, k, v: jnp.sum(flash_attention(
            q, k, v, causal=True, block_q=32, block_k=32) * w),
        (0, 1, 2))(q, k, v)
    want = jax.grad(
        lambda q, k, v: jnp.sum(mha_reference(q, k, v, causal=True) * w),
        (0, 1, 2))(q, k, v)
    for a, b in zip(got, want):
        assert jnp.max(jnp.abs(a - b)) < 1e-4


def test_cross_attention_q_shorter_than_kv():
    q, _, _ = _qkv(s=32)
    _, k, v = _qkv(s=128, seed=1)
    o = flash_attention(q, k, v)
    ref = mha_reference(q, k, v)
    assert jnp.max(jnp.abs(o - ref)) < TOL


def test_q_block_offset_matches_shifted_causal_mask():
    # A q shard whose global rows start at 64 (ring-attention layout).
    q, k, v = _qkv(s=128)
    q_shard = q[:, :, 64:96]
    o, lse = flash_attention_with_lse(q_shard, k, v, causal=True,
                                      q_block_offset=64, block_q=32,
                                      block_k=32)
    ref = mha_reference(q_shard, k, v, causal=True, q_block_offset=64)
    assert jnp.max(jnp.abs(o - ref)) < TOL
    assert lse.shape == (2, 3, 32)
    assert bool(jnp.all(jnp.isfinite(lse)))


def test_fully_masked_rows_are_zero_not_nan():
    # q_block_offset placing all queries before every key masks everything.
    q, k, v = _qkv(s=32)
    o, lse = flash_attention_with_lse(q, k, v, causal=True,
                                      q_block_offset=-1000)
    assert bool(jnp.all(o == 0.0))
    assert bool(jnp.all(jnp.isneginf(lse)))


def test_lse_matches_reference_logsumexp():
    q, k, v = _qkv(s=64, d=16)
    _, lse = flash_attention_with_lse(q, k, v, block_q=32, block_k=32)
    s = jnp.einsum("bhqd,bhkd->bhqk", q, k) * (16 ** -0.5)
    ref_lse = jax.scipy.special.logsumexp(s, axis=-1)
    assert jnp.max(jnp.abs(lse - ref_lse)) < TOL


@pytest.mark.parametrize("causal", [False, True])
def test_streaming_kernels_match_reference(causal, monkeypatch):
    # Force the long-seq streaming kernels (3D grid + VMEM scratch,
    # causal DMA-elision index maps) at test-size shapes; short shapes
    # otherwise dispatch to the resident kernels.
    monkeypatch.setenv("HVD_TPU_FLASH_RESIDENT_SEQ", "0")
    q, k, v = _qkv(s=96, d=16)
    o = flash_attention(q, k, v, causal=causal, block_q=32, block_k=32)
    ref = mha_reference(q, k, v, causal=causal)
    assert jnp.max(jnp.abs(o - ref)) < TOL

    w = jnp.cos(jnp.arange(16))

    def f(q, k, v):
        return jnp.sum(flash_attention(q, k, v, causal=causal,
                                       block_q=32, block_k=32) * w)

    def ref_f(q, k, v):
        return jnp.sum(mha_reference(q, k, v, causal=causal) * w)

    got = jax.grad(f, argnums=(0, 1, 2))(q, k, v)
    want = jax.grad(ref_f, argnums=(0, 1, 2))(q, k, v)
    for a, b in zip(got, want):
        assert jnp.max(jnp.abs(a - b)) < 5e-4


def test_bfloat16_inputs():
    q, k, v = _qkv(dtype=jnp.bfloat16)
    o = flash_attention(q, k, v, causal=True)
    assert o.dtype == jnp.bfloat16
    ref = mha_reference(q, k, v, causal=True)
    diff = jnp.max(jnp.abs(o.astype(jnp.float32)
                           - ref.astype(jnp.float32)))
    assert diff < 0.05  # bf16 mantissa tolerance


# ---------------------------------------------------------------------------
# The resident kernels on the model's own [b, s, heads x head_dim] layout
# ---------------------------------------------------------------------------

from horovod_tpu.ops import flash_attention as F  # noqa: E402


def _fused(b, s, h, d, dtype, seed=0):
    ks = jax.random.split(jax.random.PRNGKey(seed), 2)
    return (jax.random.normal(ks[0], (b, s, 3 * h * d), dtype),
            jax.random.normal(ks[1], (b, s, h * d), dtype))


def _fused_reference(qkv, g, h, causal):
    """o, lse and d(qkv) by the O(seq^2) reference, in float32."""
    qkv, g = qkv.astype(jnp.float32), g.astype(jnp.float32)

    def ref(qkv):
        q, k, v = F._split_qkv(qkv, h)
        return F._to_rows(mha_reference(q, k, v, causal=causal))

    q, k, v = F._split_qkv(qkv, h)
    _, lse = F._dense_forward(q, k, v, q.shape[-1] ** -0.5, causal, 0)
    o, vjp = jax.vjp(ref, qkv)
    return o, lse, vjp(g)[0]


# head_dim 64: two heads to a 128-lane block; 128: one; 32: four.  300
# tokens are padded to three 128-token blocks, walked as 128 x 128 tiles
# (a loop, then the diagonal tile; padded keys in the last); 512 are one
# 512-wide q tile against two 256-token k tiles in straight-line code.
@pytest.mark.parametrize("dtype,tol", [(jnp.float32, 2e-5),
                                       (jnp.bfloat16, 0.06)])
@pytest.mark.parametrize("causal", [False, True])
@pytest.mark.parametrize("h,d,s", [(2, 64, 512), (4, 64, 300),
                                   (1, 128, 300), (4, 32, 128)])
def test_qkv_layout_matches_reference(h, d, s, causal, dtype, tol):
    qkv, g = _fused(2, s, h, d, dtype)
    assert F._resident_ok(h, d, s, s, 0)
    o, res = F._flash_qkv_fwd(qkv, h, d ** -0.5, causal, True)
    lse = res[2]
    (dqkv,) = F._flash_qkv_bwd(h, d ** -0.5, causal, True, res, g)
    assert o.shape == g.shape and o.dtype == dtype
    assert dqkv.shape == qkv.shape and dqkv.dtype == dtype
    ro, rlse, rd = _fused_reference(qkv, g, h, causal)
    f32 = lambda x: x.astype(jnp.float32)
    assert jnp.max(jnp.abs(f32(o) - ro)) < tol
    # lse travels lane-dense, [b, lane blocks, heads a block, padded seq].
    assert lse.shape[:3] == (2, h * d // 128, 128 // d)
    got_lse = lse.reshape(2, h, -1)[:, :, :s]
    assert jnp.max(jnp.abs(got_lse - rlse)) < (1e-4 if dtype == jnp.float32
                                              else 0.05)
    # q, k and v gradients, each against its own scale.
    for got, want in zip(jnp.split(f32(dqkv), 3, -1), jnp.split(rd, 3, -1)):
        assert jnp.max(jnp.abs(got - want)) < tol * max(
            1.0, float(jnp.max(jnp.abs(want))))


def test_qkv_entry_differentiates_like_the_heads_entry():
    # The public entries agree: fused [b, s, 3hd] and [b, h, s, d] (whose
    # resident shapes reach the same kernels through a thin wrapper).
    qkv, g = _fused(1, 256, 2, 64, jnp.float32, seed=3)

    def fused(qkv):
        return jnp.sum(F.flash_attention_qkv(qkv, 2, causal=True) * g)

    def heads(qkv):
        q, k, v = F._split_qkv(qkv, 2)
        return jnp.sum(F._to_rows(flash_attention(q, k, v, causal=True))
                       * g)

    a, b = jax.grad(fused)(qkv), jax.grad(heads)(qkv)
    assert jnp.max(jnp.abs(a - b)) < 1e-5


def test_fused_gradient_block_equals_three_outputs(monkeypatch):
    # d(qkv) leaves the backward kernel as one [seq, dq | dk | dv] block
    # held in VMEM over a batch element's cells; past the VMEM budget it
    # leaves as three arrays and a concatenate.  Same numbers either way.
    qkv, g = _fused(2, 256, 4, 64, jnp.float32, seed=7)
    o, res = F._flash_qkv_fwd(qkv, 4, 0.125, True, True)
    (in_place,) = F._flash_qkv_bwd(4, 0.125, True, True, res, g)
    monkeypatch.setattr(F, "_FUSED_GRAD_VMEM", 0)
    (joined,) = F._flash_qkv_bwd(4, 0.125, True, True, res, g)
    assert in_place.shape == joined.shape == qkv.shape
    assert jnp.array_equal(in_place, joined)


@pytest.mark.parametrize("causal", [False, True])
def test_fused_backward_equals_two_pass_math_on_same_residuals(causal):
    # One walk over the tile pairs (S, P, dP, dS once; delta in-kernel)
    # against the two-pass dense math, on the kernel's OWN o and lse.
    b, s, h, d = 1, 256, 2, 64
    qkv, g = _fused(b, s, h, d, jnp.float32, seed=5)
    o, (_, _, lse) = F._flash_qkv_fwd(qkv, h, d ** -0.5, causal, True)
    (dqkv,) = F._flash_qkv_bwd(h, d ** -0.5, causal, True, (qkv, o, lse), g)
    q, k, v = F._split_qkv(qkv, h)
    want = F._dense_backward(
        (q, k, v, F._to_heads(o, h), lse.reshape(b, h, s)),
        F._to_heads(g, h), sm_scale=d ** -0.5, causal=causal,
        q_block_offset=0)
    for got, ref in zip(jnp.split(dqkv, 3, -1), want):
        assert jnp.max(jnp.abs(got - F._to_rows(ref))) < 2e-5


def _transposed_activations(jaxpr, min_size):
    """Every ``transpose`` of an array of at least min_size elements,
    anywhere in the jaxpr (sub-jaxprs included)."""
    found = []

    def walk(jp):
        for eqn in jp.eqns:
            if (eqn.primitive.name == "transpose"
                    and eqn.invars[0].aval.size >= min_size):
                found.append(eqn.invars[0].aval.shape)
            for sub in jax.core.jaxprs_in_params(eqn.params):
                walk(sub)

    walk(jaxpr.jaxpr)
    return found


@pytest.mark.parametrize("n_heads,path_taken", [(4, True), (3, False)])
def test_attention_block_transposes_no_activation(n_heads, path_taken):
    # 4 heads of 64: two lane blocks, the kernels read the projection in
    # place.  3 heads of 64: no whole number of head pairs (an odd local
    # head count under TP), so the [b, h, s, d] entry and its transposes.
    from horovod_tpu.models.transformer import (ParallelAxes,
                                                TransformerConfig,
                                                _attention_block,
                                                init_transformer)

    cfg = TransformerConfig(vocab_size=64, d_model=64 * n_heads,
                            n_heads=n_heads, n_layers=1, d_ff=128,
                            max_seq_len=128, block_q=32, block_k=32)
    lp = jax.tree_util.tree_map(
        lambda leaf: leaf[0],
        init_transformer(jax.random.PRNGKey(0), cfg)["layers"])
    x = jnp.ones((2, 128, cfg.d_model), jnp.float32)
    ax = ParallelAxes(data=None)

    def loss(x, lp):
        return jnp.sum(_attention_block(x, lp, cfg, ax, 0.0)[0])

    jaxpr = jax.make_jaxpr(jax.grad(loss, argnums=(0, 1)))(x, lp)
    # Activations are [2, 128, >= 192]; the weights' own transposes in the
    # projections' backward ([d, d] matrices) are not activations' but are
    # as large, so look at rank: an activation transpose is 4-d.
    moved = [s for s in _transposed_activations(jaxpr, 2 * 128 * 64)
             if len(s) == 4]
    assert (moved == []) == path_taken, moved
    assert F._resident_ok(n_heads, 64, 128, 128, 0) == path_taken


# ---------------------------------------------------------------------------
# Grouped queries inside a window: the streaming forward a serving model's
# prompt takes (models/afmoe.py)
# ---------------------------------------------------------------------------

def _gqa_reference(q, k, v, window):
    """Exact: every query head over its key/value head, causal, at most
    the ``window`` newest keys."""
    h, s, d = q.shape
    r = h // k.shape[0]
    kk, vv = jnp.repeat(k, r, axis=0), jnp.repeat(v, r, axis=0)
    scores = jnp.einsum("hqd,hkd->hqk", q, kk) * d ** -0.5
    i, j = jnp.arange(s)[:, None], jnp.arange(s)[None, :]
    seen = j <= i
    if window:
        seen = seen & (i - j < window)
    return jnp.einsum("hqk,hkd->hqd", jax.nn.softmax(
        jnp.where(seen, scores, -jnp.inf), axis=-1), vv)


# Heads in groups of 2, 6 and 1; no window, a window inside one block, one
# that spans blocks and is no multiple of them, one wider than the sequence;
# unequal blocks; a sequence that is no whole number of blocks.
@pytest.mark.parametrize("h,g,s,d,window,bq,bk", [
    (4, 2, 64, 16, 0, 16, 16), (4, 2, 64, 16, 24, 16, 16),
    (6, 1, 80, 16, 17, 16, 32), (4, 4, 50, 8, 5, 16, 16),
    (8, 2, 128, 32, 40, 32, 16), (4, 2, 48, 16, 500, 16, 16),
    (2, 1, 96, 16, 32, 32, 32)])
def test_grouped_queries_in_a_window_match_the_exact_attention(
        h, g, s, d, window, bq, bk):
    from horovod_tpu.ops.flash_attention import gqa_window_attention

    kq, kk, kv = jax.random.split(jax.random.PRNGKey(s + window), 3)
    q = jax.random.normal(kq, (h, s, d), jnp.float32)
    k = jax.random.normal(kk, (g, s, d), jnp.float32)
    v = jax.random.normal(kv, (g, s, d), jnp.float32)
    o = gqa_window_attention(q, k, v, window=window, block_q=bq,
                             block_k=bk, interpret=True)
    assert jnp.max(jnp.abs(o - _gqa_reference(q, k, v, window))) < TOL


def test_key_blocks_outside_the_window_are_never_read():
    """Keys a whole block or more behind every query's window are NaN:
    a block the kernel fetched and masked would still poison the sums
    (0 x NaN); one it skips cannot."""
    from horovod_tpu.ops.flash_attention import gqa_window_attention

    h, g, s, d, window, block = 4, 2, 128, 16, 16, 16
    kq, kk, kv = jax.random.split(jax.random.PRNGKey(3), 3)
    q = jax.random.normal(kq, (h, s, d), jnp.float32)
    k = jax.random.normal(kk, (g, s, d), jnp.float32)
    v = jax.random.normal(kv, (g, s, d), jnp.float32)
    want = _gqa_reference(q, k, v, window)[:, -block:]
    # The last block of queries sees keys s - block - window + 1 .. s - 1.
    dead = s - block - window + 1
    dead -= dead % block
    k = k.at[:, :dead].set(jnp.nan)
    v = v.at[:, :dead].set(jnp.nan)
    o = gqa_window_attention(q, k, v, window=window, block_q=block,
                             block_k=block, interpret=True)[:, -block:]
    assert bool(jnp.all(jnp.isfinite(o)))
    assert jnp.max(jnp.abs(o - want)) < TOL
    with pytest.raises(ValueError, match="whole groups"):
        gqa_window_attention(q[:3], k, v, interpret=True)


# ---------------------------------------------------------------------------
# The resident kernels through the TPU's own compiler, for a described v5e
# (no chip: nothing runs; Mosaic refuses here what it would refuse there)
# ---------------------------------------------------------------------------

@pytest.fixture(scope="module")
def v5e_chip():
    import os

    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache
    from jax.sharding import SingleDeviceSharding

    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # no libtpu here, or another process holds it
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    # An executable for a described chip cannot be read back from the
    # session's persistent cache; keep it out.
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", True)
    compilation_cache.reset_cache()


# gpt2m-train-1k's own shape; the resident limit (VMEM: the gradients leave
# as three arrays there); one head of 128 to a lane block; a sequence that
# is an odd number of 128-token blocks, in float32.
@pytest.mark.parametrize("b,s,h,d,dtype", [
    (8, 1024, 16, 64, jnp.bfloat16), (2, 4096, 16, 64, jnp.bfloat16),
    (2, 2048, 8, 128, jnp.bfloat16), (2, 640, 4, 64, jnp.float32)])
def test_resident_kernels_compile_for_the_v5e(v5e_chip, b, s, h, d, dtype):
    qkv = jax.ShapeDtypeStruct((b, s, 3 * h * d), dtype, sharding=v5e_chip)
    g = jax.ShapeDtypeStruct((b, s, h * d), dtype, sharding=v5e_chip)

    def fwd_bwd(qkv, g):
        o, res = F._flash_qkv_fwd(qkv, h, d ** -0.5, True, False)
        return o, F._flash_qkv_bwd(h, d ** -0.5, True, False, res, g)

    text = jax.jit(fwd_bwd).lower(qkv, g).compile().as_text()
    assert text.count("tpu_custom_call") >= 2


# The selective-scan kernel (ops/ssm_scan.py) at the widths
# `phi4flash-serve-reason` prefills at: the longest bucket, a short one, and
# the shortest (padded to one 8-step chunk).  Here and not in a file of its
# own: a second file's fixture could not describe the chip while this one
# holds libtpu.
@pytest.mark.parametrize("t", [2048, 64, 2])
def test_scan_kernel_compiles_for_the_v5e(v5e_chip, t):
    from horovod_tpu.ops import ssm_scan as S

    def sd(*shape, dtype=jnp.float32):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=v5e_chip)

    text = jax.jit(
        lambda x, dt, b, c, a, s0, n: S._pallas_scan(x, dt, b, c, a, s0, n,
                                                     False)
    ).lower(sd(t, 5120), sd(t, 5120), sd(t, 16), sd(t, 16), sd(16, 5120),
            sd(16, 5120), sd(dtype=jnp.int32)).compile().as_text()
    assert text.count("tpu_custom_call") == 1 and "ssm_scan" in text


# The two kernels of ops/ssd.py at the widths `granite4h-serve-sessions`
# runs them at (here for the same reason as the scan kernel above).  The
# chunked scan at the longest bucket, a short one and the shortest; the
# one-step kernel over the cell's WHOLE state store (36 layers x 64 slots
# x 2 MiB), two layers in a row, which must go in and come out as one
# buffer: the compiled program aliases all 4.83 GB of it and keeps under a
# megabyte of temporaries.
@pytest.mark.parametrize("t", [2048, 64, 2])
def test_ssd_chunk_scan_compiles_for_the_v5e(v5e_chip, t):
    from horovod_tpu.ops import ssd

    def sd(*shape, dtype=jnp.float32):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=v5e_chip)

    bf = jnp.bfloat16
    text = jax.jit(
        lambda x, dt, a, b, c, s0, n: ssd._pallas_chunk_scan(
            x, dt, a, b, c, s0, n, 256, False)
    ).lower(sd(t, 64, 64, dtype=bf), sd(t, 64), sd(64), sd(t, 128, dtype=bf),
            sd(t, 128, dtype=bf), sd(32, 128, 128),
            sd(dtype=jnp.int32)).compile().as_text()
    assert text.count("tpu_custom_call") == 1 and "ssd_chunk_scan" in text


def test_ssd_step_compiles_for_the_v5e_and_never_copies_the_store(v5e_chip):
    from horovod_tpu.ops import ssd

    def sd(*shape, dtype=jnp.float32):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=v5e_chip)

    def two_layers(store, x, dt, a, b, c, d, alive):
        for layer in (0, 35):
            decay, dtx = ssd._step_operands(x, dt, a, store.shape[1:])
            y, store = ssd._pallas_step(store, decay, dtx, b, c, alive,
                                        layer, False)
        return y, store

    bf = jnp.bfloat16
    compiled = jax.jit(two_layers, donate_argnums=(0,)).lower(
        sd(36, 64, 32, 128, 128), sd(64, 64, 64, dtype=bf), sd(64, 64),
        sd(64), sd(64, 128, dtype=bf), sd(64, 128, dtype=bf), sd(64),
        sd(64, dtype=jnp.bool_)).compile()
    text = compiled.as_text()
    assert text.count("tpu_custom_call") == 2 and "ssd_step" in text
    memory = compiled.memory_analysis()
    store = 36 * 64 * 32 * 128 * 128 * 4
    assert memory.alias_size_in_bytes >= store
    assert memory.temp_size_in_bytes < 1 << 20


# The paged latent-attention kernel (ops/latent_paged_attention.py) at the
# two cells' shapes (here for the same reason as the scan kernel above):
# `longcat-serve-turns` (128 slots x 128 pages, 8 cache layers) and
# `axk1-serve-decode` (64 x 256, 7), two layers in a row over the WHOLE
# store, which stays an argument: nothing of its 2.7 / 2.35 GB is copied,
# and neither a sort, a gather nor a conditional is left around the call.
@pytest.mark.parametrize("slots,pps,layers", [(128, 128, 8), (64, 256, 7)])
def test_latent_paged_attention_compiles_for_the_v5e(v5e_chip, slots, pps,
                                                     layers):
    from horovod_tpu.ops import latent_paged_attention as lpa

    def sd(*shape, dtype=jnp.bfloat16):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=v5e_chip)

    def two_layers(q, entry, store, table, lengths):
        order, n_live = lpa.live_first(lengths)
        return sum(lpa.latent_paged_attention(
            q, entry, store, table, lengths, layer, scale=0.07, kv_rank=512,
            order=order, n_live=n_live, interpret=False).astype(jnp.float32)
            for layer in (0, layers - 1))

    compiled = jax.jit(two_layers).lower(
        sd(slots, 64, 640), sd(slots, 640),
        sd(layers, slots * pps + 1, 16, 640),
        sd(slots, pps, dtype=jnp.int32),
        sd(slots, dtype=jnp.int32)).compile()
    text = compiled.as_text()
    assert text.count("tpu_custom_call") == 2
    assert "latent_paged_attn" in text
    assert not any(op in text for op in (" sort(", " gather(",
                                         " conditional("))
    assert compiled.memory_analysis().temp_size_in_bytes < 64 << 20


# The three kernels a decode iteration adds with a lightning indexer
# (ops/sparse_latent_attention.py) at `dsv32-serve-longdoc`'s shapes: 32
# slots of 1088 pages of 16, five cache layers, 128 heads on a 640-wide
# latent store beside a 128-wide store of index keys, 64 indexer heads, the
# 2048 best of 17408 positions.  The selection compiles with its 63 counting
# passes as loops, not unrolled.
def test_sparse_latent_kernels_compile_for_the_v5e(v5e_chip):
    from horovod_tpu.ops import sparse_latent_attention as sla

    slots, pps, page, layers, pages = 32, 1088, 16, 5, 24416
    assert sla.block_pages(page, pps, 128, 640, 2) == 16

    def decode_layer(q, entry, store, q_i, w_i, keys, table, lengths):
        total = 0.0
        for layer in range(layers):
            scores = sla.index_paged_scores(q_i, w_i, keys, table, lengths,
                                            layer)
            selected = sla.select_paged(scores, lengths, 2048)
            total = total + sla.sparse_paged_attention(
                q, entry, store, table, lengths, layer, selected,
                scale=0.135, kv_rank=512).astype(jnp.float32)
        return total

    def sd(*shape, dtype=jnp.bfloat16):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=v5e_chip)

    compiled = jax.jit(decode_layer).lower(
        sd(slots, 128, 640), sd(slots, 640),
        sd(layers, pages, page, 640), sd(slots, 64, 128),
        sd(slots, 64, dtype=jnp.float32), sd(layers, pages, page, 128),
        sd(slots, pps, dtype=jnp.int32), sd(slots, dtype=jnp.int32)
    ).compile()
    text = compiled.as_text()
    for name in ("dsa_index_score", "dsa_select", "dsa_sparse_attn"):
        assert len(re.findall(r"%" + name + r"(\.\d+)? = ", text)) == layers
    # Neither store is sliced, gathered or copied: the kernels take them
    # whole, with the layer as a scalar.
    for width in (640, 128):
        whole = f"bf16[{layers},{pages},{page},{width}]"
        assert not [line for line in text.splitlines()
                    if whole in line and (" copy(" in line
                                          or " gather(" in line
                                          or " slice(" in line)]
    assert compiled.memory_analysis().temp_size_in_bytes < 64 << 20



# The prompt's attention of `trinity-serve-mixed` (models/afmoe.py: 48 query
# on 8 key/value heads of 128, a window of 4096 or none) at its longest
# bucket, its shortest, and the engine's capacity: ONE kernel, no head is
# repeated, under 0.3 GB of temporaries (the float32 scores of the blockwise
# twin are 6.4 GB a layer at 8192).
@pytest.mark.parametrize("s,window", [(8192, 4096), (8192, 0), (256, 4096),
                                      (9216, 4096)])
def test_gqa_window_kernel_compiles_for_the_v5e(v5e_chip, s, window):
    from horovod_tpu.ops.flash_attention import gqa_window_attention

    bf = jnp.bfloat16
    q = jax.ShapeDtypeStruct((48, s, 128), bf, sharding=v5e_chip)
    kv = jax.ShapeDtypeStruct((8, s, 128), bf, sharding=v5e_chip)
    compiled = jax.jit(lambda q, k, v: gqa_window_attention(
        q, k, v, window=window)).lower(q, kv, kv).compile()
    text = compiled.as_text()
    assert text.count("tpu_custom_call") == 1 and "gqa_flash_fwd" in text
    assert compiled.memory_analysis().temp_size_in_bytes < 300e6


# The decode's attention of `trinity-serve-mixed` (ops/gqa_paged_attention.
# py) at the cell's two groups: the full group's table of 576 pages a slot
# over its one layer, the window group's ring of 257 over its four; two
# layers in a row over the WHOLE stores (0.45 and 1.45 GB each of keys and
# of values), which stay arguments: nothing of them is copied, and neither a
# gather nor a conditional nor a sort is left around the calls.
@pytest.mark.parametrize("entries,layers,pages,window", [
    (576, 1, 13764, 0), (257, 4, 11054, 4096)])
def test_gqa_paged_attention_compiles_for_the_v5e(v5e_chip, entries, layers,
                                                  pages, window):
    from horovod_tpu.ops import gqa_paged_attention as gpa

    def sd(*shape, dtype=jnp.bfloat16):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=v5e_chip)

    def two_layers(q, k_self, v_self, k_pages, v_pages, table, lengths):
        order, n_live = gpa.live_first(lengths)
        return sum(gpa.gqa_paged_attention(
            q * (1 + i), k_self, v_self, k_pages, v_pages, table, lengths,
            layer, heads=48, scale=128 ** -0.5, window=window, order=order,
            n_live=n_live, interpret=False).astype(jnp.float32)
            for i, layer in enumerate((0, layers - 1)))

    compiled = jax.jit(two_layers).lower(
        sd(64, 48 * 128), sd(64, 1024), sd(64, 1024),
        sd(layers, pages, 16, 1024), sd(layers, pages, 16, 1024),
        sd(64, entries, dtype=jnp.int32), sd(64, dtype=jnp.int32)).compile()
    text = compiled.as_text()
    assert text.count("tpu_custom_call") == 2
    assert "gqa_paged_attn" in text
    assert not any(op in text for op in (" sort(", " gather(",
                                         " conditional("))
    assert compiled.memory_analysis().temp_size_in_bytes < 64 << 20


# The decode's attention of `phi4flash-serve-reason` through the same kernel
# under the differential head map (models/hybrid_ssm.py): 40 query heads
# paired onto 20 key heads of 64, a pair's double-width value kept, float32
# out; the full group's table of 384 pages a slot over its one layer, the
# window group's ring of 33 over its eight.  The block rule gives 25 pages,
# 400 tokens: no multiple of 128, which Mosaic must take.
@pytest.mark.parametrize("entries,layers,window", [(384, 1, 0),
                                                   (33, 8, 512)])
def test_gqa_paged_attention_compiles_with_the_differential_head_map(
        v5e_chip, entries, layers, window):
    from horovod_tpu.models import hybrid_ssm as hs
    from horovod_tpu.ops import gqa_paged_attention as gpa

    cfg = hs.HybridSSMConfig()
    pages = 64 * entries + 1
    assert gpa.block_pages(16, entries, 40, cfg.kv_width, 2) == 25

    def sd(*shape, dtype=jnp.bfloat16):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=v5e_chip)

    def two_layers(q, k_self, v_self, k_pages, v_pages, table, lengths):
        order, n_live = gpa.live_first(lengths)
        return sum(gpa.gqa_paged_attention(
            q * (1 + i), k_self, v_self, k_pages, v_pages, table, lengths,
            layer, heads=40, scale=64 ** -0.5, window=window,
            key_head=hs.key_head_of(cfg), value_heads=10,
            out_dtype=jnp.float32, order=order, n_live=n_live,
            interpret=False)
            for i, layer in enumerate((0, layers - 1)))

    compiled = jax.jit(two_layers).lower(
        sd(64, 2560), sd(64, 1280), sd(64, 1280),
        sd(layers, pages, 16, 1280), sd(layers, pages, 16, 1280),
        sd(64, entries, dtype=jnp.int32), sd(64, dtype=jnp.int32)).compile()
    text = compiled.as_text()
    assert text.count("tpu_custom_call") == 2
    assert "gqa_paged_attn" in text
    assert not any(op in text for op in (" sort(", " gather(",
                                         " conditional("))
    assert compiled.memory_analysis().temp_size_in_bytes < 64 << 20


# The gated delta rule's two kernels (ops/gated_delta.py) at the shapes of
# `olmohybrid-serve-chat96` (here for the same reason as the scan kernels
# above): the chunked scan at the longest bucket, a short one and the
# shortest (30 heads of 96 x 192, chunks of 64); the one-step kernel over
# the cell's WHOLE state store (12 layers x 96 slots x 2.2 MB), two layers
# in a row, which must go in and come out as one buffer: the compiled
# program aliases all 2.55 GB of it and keeps under two megabytes of
# temporaries.
@pytest.mark.parametrize("t", [2048, 64, 2])
def test_gdn_chunk_scan_compiles_for_the_v5e(v5e_chip, t):
    from horovod_tpu.ops import gated_delta as gd

    def sd(*shape, dtype=jnp.float32):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=v5e_chip)

    bf = jnp.bfloat16
    text = jax.jit(
        lambda q, k, v, g, beta, s0, n: gd._pallas_chunk_scan(
            q, k, v, g, beta, s0, n, 64, False)
    ).lower(sd(t, 30, 96, dtype=bf), sd(t, 30, 96, dtype=bf),
            sd(t, 30, 192, dtype=bf), sd(t, 30), sd(t, 30), sd(15, 96, 384),
            sd(dtype=jnp.int32)).compile().as_text()
    assert text.count("tpu_custom_call") == 1 and "gdn_chunk_scan" in text


def test_gdn_step_compiles_for_the_v5e_and_never_copies_the_store(v5e_chip):
    from horovod_tpu.ops import gated_delta as gd

    def sd(*shape, dtype=jnp.float32):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=v5e_chip)

    def two_layers(store, q, k, v, g, beta, alive):
        for layer in (0, 11):
            o, store = gd._pallas_step(store, q, k, v, g, beta, alive,
                                       layer, False)
        return o, store

    bf = jnp.bfloat16
    compiled = jax.jit(two_layers, donate_argnums=(0,)).lower(
        sd(12, 96, 15, 96, 384), sd(96, 30, 96, dtype=bf),
        sd(96, 30, 96, dtype=bf), sd(96, 30, 192, dtype=bf), sd(96, 30),
        sd(96, 30), sd(96, dtype=jnp.bool_)).compile()
    text = compiled.as_text()
    assert text.count("tpu_custom_call") == 2 and "gdn_step" in text
    memory = compiled.memory_analysis()
    store = 12 * 96 * 15 * 96 * 384 * 4
    assert memory.alias_size_in_bytes >= store
    assert memory.temp_size_in_bytes < 2 << 20


# The multi-head full layers of `olmohybrid-serve-chat96` through the paged
# kernel as it stands: 30 query heads on 30 key/value heads of 128 (a group
# of ONE: rows of 3840 values, which the block-diagonal form multiplies
# thirty times over), the pool's table of 176 pages a slot over its four
# layers; the block rule gives 8 pages.
def test_gqa_paged_attention_compiles_at_thirty_heads_on_thirty(v5e_chip):
    from horovod_tpu.ops import gqa_paged_attention as gpa

    assert gpa.block_pages(16, 176, 30, 3840, 2) == 8

    def sd(*shape, dtype=jnp.bfloat16):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=v5e_chip)

    def two_layers(q, k_self, v_self, k_pages, v_pages, table, lengths):
        order, n_live = gpa.live_first(lengths)
        return sum(gpa.gqa_paged_attention(
            q * (1 + i), k_self, v_self, k_pages, v_pages, table, lengths,
            layer, heads=30, scale=128 ** -0.5, order=order, n_live=n_live,
            interpret=False).astype(jnp.float32)
            for i, layer in enumerate((0, 3)))

    compiled = jax.jit(two_layers).lower(
        sd(96, 3840), sd(96, 3840), sd(96, 3840),
        sd(4, 3051, 16, 3840), sd(4, 3051, 16, 3840),
        sd(96, 176, dtype=jnp.int32), sd(96, dtype=jnp.int32)).compile()
    text = compiled.as_text()
    assert text.count("tpu_custom_call") == 2
    assert "gqa_paged_attn" in text
    assert not any(op in text for op in (" sort(", " gather(",
                                         " conditional("))
    assert compiled.memory_analysis().temp_size_in_bytes < 64 << 20


# The WHOLE decode program of `granite4h-serve-sessions` as its serving
# model builds it (models/mamba2_hybrid.py), from shapes: 64 slots x 192
# pages of 16, four paged layers of 512-wide keys and values (1.61 GB),
# 4.89 GB of state.  The four attention layers each call the paged kernel
# over the WHOLE stores and the 36 state-space layers the step kernel; no
# conditional picks a rung, nothing gathers pages into a view, and the four
# stores are updated where they lie.
def test_granites_decode_program_compiles_for_the_v5e(v5e_chip, monkeypatch):
    import functools
    import re

    from horovod_tpu.models import mamba2_hybrid as mh
    from horovod_tpu.ops import gqa_paged_attention as gpa
    from horovod_tpu.ops import ssd

    # The program is built for the chip described, not for this backend:
    # both kernels asked for by name (False: compiled, not interpreted).
    monkeypatch.setattr(mh, "PAGED_INTERPRET", False)
    monkeypatch.setattr(mh, "ssd_step", functools.partial(ssd.ssd_step,
                                                          interpret=False))
    cfg = mh.Mamba2HybridConfig()
    model = cfg.serving_model()
    slots, page, pps = 64, 16, 192
    assert gpa.block_pages(page, pps, 32, cfg.kv_width, 2) == 64

    def sd(*shape, dtype=jnp.bfloat16):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=v5e_chip)

    entry = model.cache_entry()
    assert [s["kind"] for s in entry["slot_stores"]] == ["state", "state"]
    store = (entry["n_layers"], slots * pps + 1, page, cfg.kv_width)
    stores = (sd(*store), sd(*store),
              *(sd(s["shape"][0], slots, *s["shape"][1:], dtype=s["dtype"])
                for s in entry["slot_stores"]))
    params = jax.tree_util.tree_map(
        lambda x: sd(*x.shape, dtype=x.dtype), jax.eval_shape(
            lambda: mh.init_mamba2_hybrid(jax.random.PRNGKey(0), cfg)))

    def step(params, k, v, state, tail, table, lengths, tokens):
        (logits,), new = model.decode(params, (k, v, state, tail), table,
                                      lengths, tokens)
        return (logits, *new)

    i32 = functools.partial(sd, dtype=jnp.int32)
    compiled = jax.jit(step, donate_argnums=(1, 2, 3, 4)).lower(
        params, *stores, i32(slots, pps), i32(slots), i32(slots)).compile()
    text = compiled.as_text()
    assert len(re.findall(r"%gqa_paged_attn(\.\d+)? = ", text)) == 4
    assert len(re.findall(r"%ssd_step(\.\d+)? = ", text)) == 36
    assert text.count('custom_call_target="tpu_custom_call"') == 40
    assert " conditional(" not in text and " while(" not in text
    paged = "bf16[%d,%d,%d,%d]" % store
    assert paged in text
    assert not [line for line in text.splitlines()
                if " gather(" in line and paged in line]
    memory = compiled.memory_analysis()
    held = sum(s.size * s.dtype.itemsize for s in stores)
    assert memory.alias_size_in_bytes >= held          # 6.50 GB, in place
    assert memory.temp_size_in_bytes < 256 << 20


# The LM's loss (models/transformer.py::next_token_nll) with both gradients at
# `gpt2m-train-1k`'s width, through the TPU's compiler: ONE float32
# [8192, 50257] array is ever written (the logits; 1.65 GB of temporaries
# where log_softmax + take_along_axis wrote the log-probabilities too, 3.3),
# and the two backward products read it under their own time.  Here for the
# same reason as the scan kernel's: this file holds libtpu.
def test_lm_loss_writes_its_logits_once_on_the_v5e(v5e_chip):
    import re

    from horovod_tpu.models.transformer import next_token_nll

    rows, d, vocab = 8192, 1024, 50257
    h = jax.ShapeDtypeStruct((8, 1024, d), jnp.bfloat16, sharding=v5e_chip)
    w = jax.ShapeDtypeStruct((d, vocab), jnp.bfloat16, sharding=v5e_chip)
    t = jax.ShapeDtypeStruct((8, 1024), jnp.int32, sharding=v5e_chip)

    def step(h, w, t):
        # An activation before and an update after, as a train step has.
        loss, (dh, dw) = jax.value_and_grad(
            lambda h, w: jnp.mean(next_token_nll(h, w, t)),
            argnums=(0, 1))(jnp.tanh(h), w)
        return loss, dh, w - dw

    compiled = jax.jit(step).lower(h, w, t).compile()
    entry = compiled.as_text().split("ENTRY", 1)[1]
    wide = re.findall(
        r"f32\[(?:8,1024|8192),50257\]\S* (?:fusion|convolution)\(", entry)
    assert len(wide) == 1, wide
    assert compiled.memory_analysis().temp_size_in_bytes < rows * vocab * 4.2
