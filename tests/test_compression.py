"""Gradient compression (hvd.Compression.{none,fp16,bf16}).

The reference snapshot predates Horovod's compression API; these tests
pin the contract Horovod later standardized: gradients are cast down for
the wire and restored after, the result keeps the original dtype, and
the compressed reduction stays within the wire dtype's tolerance of the
uncompressed one — on both the static (fused psum) and eager
(async-handle) paths.
"""

import jax
import jax.numpy as jnp
import numpy as np
import optax
import pytest

import horovod_tpu as hvd_api
from horovod_tpu.models.mnist import (MnistMLP, cross_entropy_loss,
                                      init_params, synthetic_mnist)
from horovod_tpu.ops.compression import Compression
from horovod_tpu.parallel.training import make_train_step, shard_batch


def test_compress_roundtrip_dtypes():
    t = jnp.arange(8, dtype=jnp.float32) / 3.0
    for comp in (Compression.fp16, Compression.bf16):
        wire, ctx = comp.compress(t)
        assert wire.dtype == comp.wire_dtype
        back = comp.decompress(wire, ctx)
        assert back.dtype == jnp.float32
        np.testing.assert_allclose(np.asarray(back), np.asarray(t),
                                   rtol=1e-2)


def test_non_float_and_narrow_tensors_pass_through():
    idx = jnp.arange(8, dtype=jnp.int32)
    wire, ctx = Compression.bf16.compress(idx)
    assert wire.dtype == jnp.int32 and ctx is None
    half = jnp.ones((4,), jnp.bfloat16)
    wire, ctx = Compression.fp16.compress(half)
    assert wire.dtype == jnp.bfloat16 and ctx is None
    assert Compression.none.compress(idx) == (idx, None)


def _loss_fn(model):
    def loss_fn(params, batch):
        images, labels = batch
        return cross_entropy_loss(model.apply({"params": params}, images),
                                  labels)
    return loss_fn


@pytest.mark.parametrize("comp", [Compression.bf16, Compression.fp16])
def test_static_path_compressed_matches_uncompressed(hvd, comp):
    """Inside shard_map: compressed fused reduction ~= exact, and the
    updated parameters keep their f32 dtype."""
    model = MnistMLP(hidden=32)
    params = init_params(model)
    opt = optax.sgd(0.1)
    images, labels = synthetic_mnist(64)
    batch = shard_batch((jnp.asarray(images), jnp.asarray(labels)))

    outs = []
    for compression in (None, comp):
        dopt = hvd_api.DistributedOptimizer(opt, compression=compression)
        step = make_train_step(_loss_fn(model), dopt, donate=False)
        p, _, _ = step(params, dopt.init(params), batch)
        outs.append(p)
    for exact, compressed in zip(jax.tree_util.tree_leaves(outs[0]),
                                 jax.tree_util.tree_leaves(outs[1])):
        assert compressed.dtype == exact.dtype
        # One SGD step at lr 0.1: wire-dtype error on the gradient only.
        np.testing.assert_allclose(np.asarray(compressed),
                                   np.asarray(exact), atol=5e-3)


def test_compressed_average_divides_after_decompress():
    """Averaging divides in the RESTORED dtype (f32) after decompress,
    matching the ZeRO-1 path's numerics — not in the narrow wire dtype
    (advisor round-3 finding).  A 5-replica mesh makes the two orders
    bit-distinguishable (division by 5 is inexact in bfloat16)."""
    from jax.sharding import PartitionSpec as P

    from horovod_tpu.parallel.data import allreduce_gradients

    hvd_api.init(devices=jax.devices()[:5])
    try:
        n = hvd_api.size()
        assert n == 5
        mesh = hvd_api.mesh()
        # Full 8-bit-mantissa value (255/128): the 5-way sum cannot be
        # held exactly in bf16, so sum/5 is inexact in bf16 but has a
        # closer f32 representation — the two division orders differ.
        g = jnp.full((n, 1, 4), 1.9921875, jnp.float32)

        def step(avg):
            def body(x):
                x = jnp.squeeze(x, 0)
                out = allreduce_gradients(
                    {"w": x}, average=avg,
                    compression=Compression.bf16)["w"]
                return out[None]
            return jax.jit(jax.shard_map(
                body, mesh=mesh, in_specs=P("hvd"), out_specs=P("hvd"),
                check_vma=False))

        avg = np.asarray(step(True)(hvd_api.shard(g)))[0, 0]
        summed = np.asarray(step(False)(hvd_api.shard(g)))[0, 0]
        # New order: decompress (exact bf16->f32) then divide in f32.
        expected = summed / np.float32(n)
        # Old order: divide the wire-dtype sum in bf16, then decompress.
        old = np.asarray((jnp.asarray(summed).astype(jnp.bfloat16)
                          / jnp.asarray(n, jnp.bfloat16))
                         .astype(jnp.float32))
        assert not np.array_equal(old, expected), "test lost its teeth"
        np.testing.assert_array_equal(avg, expected)
    finally:
        hvd_api.shutdown()


def test_eager_path_compressed_allreduce_average(hvd):
    """Eager DistributedOptimizer path: bf16-compressed grads still
    average to the exact value for exactly-representable inputs."""
    dopt = hvd_api.DistributedOptimizer(optax.sgd(1.0),
                                        compression=Compression.bf16)
    params = {"w": jnp.zeros((4,), jnp.float32)}
    st = dopt.init(params)
    grads = {"w": jnp.full((4,), 2.0, jnp.float32)}  # exact in bf16
    updates, _ = dopt.update(grads, st, params)
    out = optax.apply_updates(params, updates)["w"]
    assert out.dtype == jnp.float32
    np.testing.assert_allclose(np.asarray(out), -2.0)


def test_compression_composes_with_fusion_thresholds(hvd):
    """Bucketed and unbucketed compressed reductions agree exactly (the
    wire dtype is the same either way; bucketing is not a semantic
    change)."""
    model = MnistMLP(hidden=32)
    params = init_params(model)
    images, labels = synthetic_mnist(64)
    batch = shard_batch((jnp.asarray(images), jnp.asarray(labels)))

    outs = []
    for threshold in (0, 1 << 26):
        dopt = hvd_api.DistributedOptimizer(optax.sgd(0.1),
                                            fusion_threshold=threshold,
                                            compression=Compression.bf16)
        step = make_train_step(_loss_fn(model), dopt, donate=False)
        p, _, _ = step(params, dopt.init(params), batch)
        outs.append(p)
    for a, b in zip(jax.tree_util.tree_leaves(outs[0]),
                    jax.tree_util.tree_leaves(outs[1])):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), rtol=1e-6)


def test_torch_frontend_accepts_compression(hvd):
    """The torch frontend takes the same compression kwarg GPU Horovod
    scripts pass: wire is fp16, result restores the torch dtype, and the
    in-place variant writes back the decompressed value."""
    torch = pytest.importorskip("torch")
    import horovod_tpu.frontends.torch as thvd

    t = torch.full((4,), 3.0)
    out = thvd.allreduce(t, average=True, compression=thvd.Compression.fp16)
    assert out.dtype == torch.float32
    np.testing.assert_allclose(out.numpy(), 3.0)

    t2 = torch.full((4,), 5.0)
    thvd.allreduce_(t2, average=True, compression=thvd.Compression.bf16)
    np.testing.assert_allclose(t2.numpy(), 5.0)

    # poll-then-synchronize on a non-inplace compressed handle: poll must
    # not discard the decompression context (regression: poll used to pop
    # the entry, so synchronize returned the raw bf16 wire array).
    h = thvd.allreduce_async(torch.full((4,), 7.0), average=True,
                             compression=thvd.Compression.bf16)
    while not thvd.poll(h):
        pass
    out3 = thvd.synchronize(h)
    assert out3.dtype == torch.float32
    np.testing.assert_allclose(out3.numpy(), 7.0)

    # Same poll-then-synchronize sequence on an IN-PLACE compressed
    # handle (regression: poll's write-back used to pop the whole record,
    # so synchronize crashed on the raw bf16 wire array).
    t3 = torch.full((4,), 9.0)
    h2 = thvd.allreduce_async_(t3, average=True,
                               compression=thvd.Compression.bf16)
    while not thvd.poll(h2):
        pass
    np.testing.assert_allclose(t3.numpy(), 9.0)  # poll wrote back
    out4 = thvd.synchronize(h2)
    assert out4.dtype == torch.float32
    np.testing.assert_allclose(out4.numpy(), 9.0)

    model = torch.nn.Linear(2, 1, bias=False)
    opt = thvd.DistributedOptimizer(
        torch.optim.SGD(model.parameters(), lr=0.1),
        named_parameters=model.named_parameters(),
        compression=thvd.Compression.bf16)
    loss = model(torch.ones((2, 2))).sum()
    loss.backward()
    opt.step()  # hooks fired compressed allreduces; step must not raise


def test_tf_frontend_accepts_compression(hvd):
    tf = pytest.importorskip("tensorflow")
    import horovod_tpu.frontends.tensorflow as tfhvd

    out = tfhvd.allreduce(tf.constant([2.0, 4.0]), average=True,
                          compression=tfhvd.Compression.bf16)
    assert out.dtype == tf.float32
    np.testing.assert_allclose(out.numpy(), [2.0, 4.0])

    # DistributedGradientTape takes the same kwarg (GPU Horovod parity).
    w = tf.Variable([[2.0]])
    with tfhvd.DistributedGradientTape(
            tf.GradientTape(), compression=tfhvd.Compression.fp16) as tape:
        loss = w * w
    (g,) = tape.gradient(loss, [w])
    assert g.dtype == tf.float32
    np.testing.assert_allclose(g.numpy(), [[4.0]])


def test_sparse_leaves_bypass_compression(hvd):
    """IndexedSlices exchange as an uncompressed allgather: indices must
    never be cast; gathered values keep their dtype."""
    from horovod_tpu.ops.sparse import IndexedSlices

    dopt = hvd_api.DistributedOptimizer(optax.sgd(1.0),
                                        compression=Compression.fp16)
    dense = jnp.zeros((4, 2), jnp.float32)
    params = {"emb": dense}
    st = dopt.init(params)
    grads = {"emb": IndexedSlices(values=jnp.ones((1, 2), jnp.float32),
                                  indices=jnp.array([1]),
                                  dense_shape=(4, 2))}
    updates, _ = dopt.update(grads, st, params)
    out = optax.apply_updates(params, updates)["emb"]
    assert out.dtype == jnp.float32
    # All 8 replicas contributed the same row; averaged update is -1.
    np.testing.assert_allclose(np.asarray(out)[1], -1.0)
    np.testing.assert_allclose(np.asarray(out)[0], 0.0)


# ---------------------------------------------------------------------------
# Quantized wire formats (ISSUE 6): registry, policy, standalone codec
# ---------------------------------------------------------------------------

from horovod_tpu.ops import compression as comp


def test_resolve_error_names_every_compressor():
    with pytest.raises(ValueError) as ei:
        comp.resolve("int7")
    msg = str(ei.value)
    for name in ("none", "fp16", "bf16", "int8", "int4"):
        assert name in msg, msg
    # And the registry resolves every advertised name.
    for name in comp.valid_names():
        assert comp.resolve(name) is not None


def test_quant_compressor_rejects_wrap_api():
    """int8/int4 cannot wrap a sum collective the way casts do; the
    error must point at the correct selection API."""
    with pytest.raises(ValueError, match="set_compression"):
        Compression.int8.compress(jnp.ones(4))
    with pytest.raises(ValueError, match="int4"):
        Compression.int4.compress(jnp.ones(4))


@pytest.mark.parametrize("codec", ["int8", "int4"])
def test_standalone_quantize_roundtrip(codec):
    rng = np.random.default_rng(0)
    t = jnp.asarray(rng.standard_normal((5, 37)).astype(np.float32))
    cls = comp.resolve(codec)
    wire_data, ctx = cls.quantize(t)
    back = cls.dequantize(wire_data, ctx)
    assert back.shape == t.shape and back.dtype == t.dtype
    # Error bounded by one (power-of-two) quantization step per block.
    step = 2.0 * np.abs(np.asarray(t)).max() / comp._levels(cls.bits)
    assert np.abs(np.asarray(back) - np.asarray(t)).max() <= step


def test_pack_int4_roundtrip():
    q = jnp.asarray(np.random.default_rng(1).integers(
        -7, 8, size=(3, 64)).astype(np.int8))
    np.testing.assert_array_equal(
        np.asarray(comp.unpack_int4(comp.pack_int4(q))), np.asarray(q))


def test_wire_pack_roundtrip():
    fmt = comp.wire_format("int8")
    rng = np.random.default_rng(2)
    rows = jnp.asarray(rng.standard_normal((4, 512)).astype(np.float32))
    q, s = comp.quantize_blocks(rows, fmt, comp.step_key(0, 0))
    w = comp.wire_pack(q, s, fmt)
    assert w.dtype == jnp.uint8
    assert w.shape[-1] == comp.wire_bytes_per_chunk(512, fmt)
    q2, s2 = comp.wire_unpack(w, 512, fmt)
    np.testing.assert_array_equal(np.asarray(q), np.asarray(q2))
    assert np.asarray(s).tobytes() == np.asarray(s2).tobytes()


def test_pow2_scales_are_exact_in_bf16():
    fmt = comp.wire_format("int8")
    rng = np.random.default_rng(3)
    rows = jnp.asarray((rng.standard_normal((2, 1024)) * 100)
                       .astype(np.float32))
    _, s = comp.quantize_blocks(rows, fmt, comp.step_key(0, 0))
    sf = np.asarray(s.astype(jnp.float32))
    nz = sf[sf > 0]
    # Every scale is a power of two → mantissa bits all zero → the
    # bfloat16 wire cast was lossless.
    m, _ = np.frexp(nz)
    assert np.all(m == 0.5)


def test_stochastic_rounding_unbiased():
    """floor(x + u8-dither) over many draws averages to x (the SR
    contract the convergence story rests on)."""
    fmt = comp.wire_format("int8")
    x = jnp.full((1, 256), 0.35, jnp.float32) * 2.0  # 0.7 of a step
    draws = []
    for tick in range(200):
        q, s = comp.quantize_blocks(x, fmt, comp.step_key(0, tick))
        draws.append(np.asarray(comp.dequantize_blocks(q, s, fmt))[0, 0])
    assert abs(np.mean(draws) - 0.7) < 0.02


def test_wire_format_applicability():
    # Quantization: floats only, above the min-elems floor.
    assert comp.wire_format_for("int8", np.float32, 1024).bits == 8
    assert comp.wire_format_for("int8", np.int32, 1024) is None
    assert comp.wire_format_for("int8", np.float32, 4) is None
    assert comp.wire_format_for("int4", jnp.bfloat16, 1024).bits == 4
    # Casts keep the dtype-narrowing rule.
    assert comp.wire_format_for("bf16", np.float32, 8).wire_dtype \
        == "bfloat16"
    assert comp.wire_format_for("bf16", jnp.bfloat16, 1024) is None
    assert comp.wire_format_for("none", np.float32, 1024) is None


def test_policy_precedence_and_process_sets(monkeypatch):
    monkeypatch.setenv(comp.DEFAULT_ENV, "bf16")
    try:
        # Env default applies without a policy.
        assert comp.policy_name_for("anything", 0) == "bf16"
        hvd_policy = comp.CompressionPolicy(
            default="int8",
            rules=[(r"embedding", "int4"), (r"\bln\b|bias", "none")],
            process_sets={3: "none"})
        assert hvd_policy.name_for("model.embedding.w", 0) == "int4"
        assert hvd_policy.name_for("model.ln.scale", 0) == "none"
        assert hvd_policy.name_for("dense.kernel", 0) == "int8"
        # Rules win over the per-set override; the override wins over
        # the default.
        assert hvd_policy.name_for("model.embedding.w", 3) == "int4"
        assert hvd_policy.name_for("dense.kernel", 3) == "none"
        # Typos fail at construction with the full name list.
        with pytest.raises(ValueError, match="int8"):
            comp.CompressionPolicy(default="int9")
        with pytest.raises(ValueError):
            comp.CompressionPolicy(rules=[("x", "bogus")])
    finally:
        comp.set_compression()


def test_set_compression_flushes_executor_state(hvd):
    from horovod_tpu.ops import megakernel as mk

    flushes0 = mk.stats.flushes
    comp.set_compression(default="int8")
    try:
        assert mk.stats.flushes > flushes0
        assert comp.policy_name_for("w", 0) == "int8"
    finally:
        comp.set_compression()
    assert comp.get_compression() is None


def test_validate_env_rejects_typos(monkeypatch):
    monkeypatch.setenv("HVD_TPU_COMPRESSION", "int9")
    with pytest.raises(ValueError, match="HVD_TPU_COMPRESSION"):
        comp.validate_env()
    monkeypatch.setenv("HVD_TPU_COMPRESSION", "int8")
    monkeypatch.setenv("HVD_TPU_QUANT_ROUNDING", "sometimes")
    with pytest.raises(ValueError, match="ROUNDING"):
        comp.validate_env()
    monkeypatch.setenv("HVD_TPU_QUANT_ROUNDING", "nearest")
    monkeypatch.setenv("HVD_TPU_QUANT_BLOCK", "33")
    with pytest.raises(ValueError, match="even block"):
        comp.validate_env()
    monkeypatch.setenv("HVD_TPU_QUANT_BLOCK", "128")
    monkeypatch.setenv("HVD_TPU_DCN_COMPRESS", "gzip")
    with pytest.raises(ValueError, match="HVD_TPU_DCN_COMPRESS"):
        comp.validate_env()
    monkeypatch.setenv("HVD_TPU_DCN_COMPRESS", "int4")
    comp.validate_env()  # all well-formed now


def test_validate_env_runs_at_init(monkeypatch):
    """A typo'd compressor must fail hvd.init(), not the first
    collective (the satellite fix: the old error was bare and late)."""
    import jax

    import horovod_tpu as hvd_api

    monkeypatch.setenv("HVD_TPU_COMPRESSION", "int9")
    with pytest.raises(ValueError, match="expected one of"):
        hvd_api.init(devices=jax.devices())


def test_env_fingerprint_covers_spmd_knobs(monkeypatch):
    fp0 = comp.env_fingerprint()
    assert "HVD_TPU_COMPRESSION=<unset>" in fp0 \
        or "HVD_TPU_COMPRESSION=" in fp0
    monkeypatch.setenv("HVD_TPU_COMPRESSION", "int8")
    monkeypatch.setenv("HVD_TPU_VIRTUAL_SLICES", "2")
    fp1 = comp.env_fingerprint()
    assert fp1 != fp0
    assert "HVD_TPU_COMPRESSION=int8" in fp1
    assert "HVD_TPU_VIRTUAL_SLICES=2" in fp1


def test_handshake_fingerprint_warning(monkeypatch, capsys):
    """The control-plane HELLO carries the env fingerprint; a divergent
    knob makes the controller print a WARNING naming the rank and the
    knob (the env-knob uniformity contract, validated not just
    documented)."""
    import struct

    from horovod_tpu.ops import transport as tp

    def hello_payload(fp: str) -> bytes:
        hb = b"host1"
        fpb = fp.encode("utf-8")
        return (struct.pack("<i", 3) + struct.pack("<H", len(hb)) + hb
                + struct.pack("<H", len(fpb)) + fpb)

    # Identical fingerprints: silent.
    tp._check_env_fingerprint(
        3, hello_payload(comp.env_fingerprint()), 11)
    assert "WARNING" not in capsys.readouterr().err

    # Divergent knob: warn, naming rank and knob with both values.
    monkeypatch.setenv("HVD_TPU_COMPRESSION", "none")
    theirs = comp.env_fingerprint().replace(
        "HVD_TPU_COMPRESSION=none", "HVD_TPU_COMPRESSION=int8")
    tp._check_env_fingerprint(3, hello_payload(theirs), 11)
    err = capsys.readouterr().err
    assert "WARNING" in err and "rank 3" in err
    assert "HVD_TPU_COMPRESSION" in err and "int8" in err

    # Pre-fingerprint HELLO (short payload): tolerated silently.
    tp._check_env_fingerprint(1, struct.pack("<i", 1), 4)
    assert "WARNING" not in capsys.readouterr().err
