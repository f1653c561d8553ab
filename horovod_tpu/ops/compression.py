"""Gradient compression for the allreduce wire (≙ hvd.Compression).

The reference snapshot (v0.13.0) predates Horovod's compression API; this
implements the contract Horovod later standardized (horovod.torch
``Compression.fp16``) *and* extends it with true low-bit quantized
reduction (cf. the original paper's fp16 compression, arXiv:1802.05799,
and EQuARX's in-XLA quantized allreduce, arXiv:2506.17615):

* **Cast compressors** (``fp16``/``bf16``): gradients are cast down
  before the collective and restored after, halving the bytes every
  allreduce moves.  Safe to wrap around a sum (casting commutes with
  addition up to rounding).
* **Quantized wire formats** (``int8``/``int4``): block-wise scaled
  integer codebooks with stochastic rounding and error-feedback
  residuals.  A sum of int8 *codes* is meaningless, so these cannot
  wrap a collective the way cast compressors do — they are compiled
  INTO the fused pack→reduce→unpack megakernels
  (ops/megakernel.py) as a two-phase exchange:

      phase 1   each replica splits its local vector into n chunks,
                quantizes block-wise, and all_to_alls the *wire* payload
                (int8 codes / packed int4 nibbles + bfloat16 scales);
      reduce    each replica dequantizes the n received chunks and
                accumulates its chunk of the sum in float32;
      phase 2   the reduced chunk is re-quantized and all_gathered in
                wire format, then dequantized everywhere.

  Every byte crossing a link is in wire format — the bandwidth shape of
  a ring allreduce with ``bits/8 + 2/block`` bytes per element instead
  of 4.  Quantization error is handled twice over: stochastic rounding
  makes each step unbiased, and the **error-feedback residual** (the
  difference between what a replica meant to send and what its peers
  decoded) is carried by the executor and added to the next step's
  contribution, so the error telescopes instead of accumulating
  (the EF-SGD lineage).  The residual store is real HBM — one
  flat full-precision buffer per fusion group, held across steps by
  ``ops/megakernel.py`` for the fused AND eager-reference paths alike —
  and is accounted by the hvd-mem device-memory ledger as
  ``megakernel.residuals`` (docs/memory.md): its absolute byte size is
  re-synced on every store/take/flush, so a name churn that
  re-partitions groups and mints fresh residuals shows up as ledger
  growth ``hvd.MemoryWatch`` names.

Per-tensor / per-process-set selection rides a small policy registry
(:func:`set_compression`): regex rules map tensor names to compressor
names (embeddings → int8, layernorm/scalars → none), with per-set
overrides; ``HVD_TPU_COMPRESSION`` sets the process-wide default.

TPU note: prefer :data:`Compression.bf16` for casts — bfloat16 keeps
float32's exponent range and is the MXU-native dtype.  ``fp16`` is
provided for drop-in parity with GPU Horovod scripts: every
``DistributedOptimizer`` (the core optax wrapper and the torch/keras/
tensorflow frontends) and the torch/tf ``allreduce`` functions accept
the same ``compression=`` kwarg.

Usage (core JAX surface)::

    opt = hvd.DistributedOptimizer(optax.sgd(0.01),
                                   compression=hvd.Compression.bf16)

or explicitly around a single collective::

    compressor = hvd.Compression.bf16
    t, ctx = compressor.compress(tensor)
    out = compressor.decompress(hvd.allreduce(t, average=True), ctx)

Quantized reduction (wire-level; see docs/tensor-fusion.md)::

    hvd.set_compression(default="int8",
                        rules=[(r".*(bias|scale|ln)", "none")])
    # or: HVD_TPU_COMPRESSION=int8
"""

from __future__ import annotations

import os
import re
from dataclasses import dataclass
from typing import Dict, List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp

__all__ = ["Compression", "Compressor", "NoneCompressor", "FP16Compressor",
           "BF16Compressor", "Int8Compressor", "Int4Compressor",
           "WireFormat", "set_compression", "get_compression",
           "CompressionPolicy", "resolve", "wire_format_for",
           "reference_allreduce"]

# Env contract (docs/performance.md, docs/tensor-fusion.md).  All of
# these change the compiled SPMD program and MUST be uniform across
# ranks — core/state.init validates them and the control-plane
# handshake cross-checks the fingerprint (env_fingerprint()).
DEFAULT_ENV = "HVD_TPU_COMPRESSION"          # default wire compressor
BLOCK_ENV = "HVD_TPU_QUANT_BLOCK"            # scaling-block elements
ROUNDING_ENV = "HVD_TPU_QUANT_ROUNDING"      # stochastic | nearest
EF_ENV = "HVD_TPU_QUANT_ERROR_FEEDBACK"      # 1 (default) | 0
SEED_ENV = "HVD_TPU_QUANT_SEED"              # stochastic-rounding seed
MIN_ELEMS_ENV = "HVD_TPU_QUANT_MIN_ELEMS"    # quantization floor

_DEFAULT_BLOCK = 256
_DEFAULT_MIN_ELEMS = 16


class Compressor:
    """Interface: ``compress(tensor) -> (tensor, ctx)`` before the wire,
    ``decompress(tensor, ctx)`` after.  Pure casts — safe both inside jit
    (the static psum path) and on eager numpy-backed arrays."""

    @staticmethod
    def compress(tensor):
        raise NotImplementedError

    @staticmethod
    def decompress(tensor, ctx):
        raise NotImplementedError


class NoneCompressor(Compressor):
    """Identity (≙ Horovod's Compression.none)."""

    @staticmethod
    def compress(tensor):
        return tensor, None

    @staticmethod
    def decompress(tensor, ctx):
        return tensor


class _CastCompressor(Compressor):
    wire_dtype: jnp.dtype = None  # set by subclasses

    @classmethod
    def compress(cls, tensor):
        tensor = jnp.asarray(tensor)
        dtype = tensor.dtype
        # Only floating inputs wider than the wire dtype are compressed;
        # integer/bool tensors and already-narrow floats pass through
        # (casting int64 indices to fp16 would corrupt them).
        if (jnp.issubdtype(dtype, jnp.floating)
                and jnp.dtype(dtype).itemsize
                > jnp.dtype(cls.wire_dtype).itemsize):
            return tensor.astype(cls.wire_dtype), dtype
        return tensor, None

    @classmethod
    def decompress(cls, tensor, ctx):
        if ctx is None:
            return tensor
        return jnp.asarray(tensor).astype(ctx)


class FP16Compressor(_CastCompressor):
    """float16 wire dtype (≙ Horovod's Compression.fp16).  Mind the 5-bit
    exponent: loss-scale or prefer bf16 on TPU."""

    wire_dtype = jnp.float16


class BF16Compressor(_CastCompressor):
    """bfloat16 wire dtype — float32 exponent range, MXU-native; the
    recommended cast compressor on TPU."""

    wire_dtype = jnp.bfloat16


class _QuantCompressor(Compressor):
    """Block-wise integer codebook (int8/int4).

    A quantized code stream cannot be summed, so this class does NOT
    implement the wrap-a-collective ``compress``/``decompress`` contract
    — attempting to raises with the correct API.  Select quantized
    reduction through :func:`set_compression` / ``HVD_TPU_COMPRESSION``
    instead; the megakernel executor compiles the quantize → exchange →
    dequantize pipeline into the fused reduction.  The eager
    :meth:`quantize`/:meth:`dequantize` pair is the standalone codec
    (storage, allgather-style exchanges, tests)."""

    bits: int = 0  # set by subclasses

    @classmethod
    def compress(cls, tensor):
        raise ValueError(
            f"{cls.__name__} is a wire-level quantized reduction format: "
            f"int codes cannot wrap a sum collective the way fp16/bf16 "
            f"casts do.  Select it with hvd.set_compression(default="
            f"'int{cls.bits}', ...) or HVD_TPU_COMPRESSION=int{cls.bits}; "
            f"the fused executor (ops/megakernel.py) compiles the "
            f"quantization into the reduction itself.")

    decompress = compress

    @classmethod
    def quantize(cls, tensor, *, key=None):
        """Standalone block-wise quantization of ``tensor`` →
        ``(wire, ctx)`` where ``wire`` is the int8 code array (packed
        nibbles for int4) and ctx carries scales/shape/dtype for
        :meth:`dequantize`.  Deterministic (round-to-nearest) unless a
        PRNG ``key`` requests stochastic rounding."""
        fmt = wire_format(cls.__name__.replace("Compressor", "").lower())
        t = jnp.asarray(tensor)
        flat = t.reshape(-1)
        pad = (-flat.shape[0]) % fmt.block
        if pad:
            flat = jnp.concatenate([flat, jnp.zeros((pad,), flat.dtype)])
        use = fmt if key is not None else \
            WireFormat(kind="quant", name=fmt.name, bits=fmt.bits,
                       block=fmt.block, stochastic=False,
                       error_feedback=False)
        q, s = quantize_blocks(flat[None], use, key)
        return (q[0], s[0]), (t.dtype, t.shape, use)

    @classmethod
    def dequantize(cls, wire, ctx):
        dtype, shape, fmt = ctx
        q, s = wire
        out = dequantize_blocks(q[None], s[None], fmt)[0]
        n = 1
        for d in shape:
            n *= d
        return out[:n].reshape(shape).astype(dtype)


class Int8Compressor(_QuantCompressor):
    """8-bit block-scaled codebook: ~3.97x fewer wire bytes than fp32
    (1 B/element + 2 B bfloat16 scale per block)."""

    bits = 8


class Int4Compressor(_QuantCompressor):
    """4-bit block-scaled codebook (two codes per wire byte): ~7.9x
    fewer wire bytes than fp32.  Needs error feedback for training
    parity — see docs/performance.md for the convergence caveats."""

    bits = 4


class Compression:
    """Namespace matching Horovod's ``hvd.Compression`` surface."""

    none = NoneCompressor
    fp16 = FP16Compressor
    bf16 = BF16Compressor
    int8 = Int8Compressor
    int4 = Int4Compressor


def valid_names() -> Tuple[str, ...]:
    """Every name :func:`resolve` accepts (the registry's vocabulary)."""
    return tuple(
        n for n in vars(Compression)
        if not n.startswith("_")
        and isinstance(getattr(Compression, n), type)
        and issubclass(getattr(Compression, n), Compressor))


def resolve(name: str):
    """Compressor by env-style name — the lookup behind
    ``HVD_TPU_COMPRESSION`` / ``HVD_TPU_DCN_COMPRESS`` /
    ``HVD_TPU_ICI_COMPRESS`` and any other string-keyed configuration
    surface.  A typo raises naming every valid choice."""
    key = str(name).strip().lower()
    comp = getattr(Compression, key, None)
    if not (isinstance(comp, type) and issubclass(comp, Compressor)):
        raise ValueError(
            f"unknown compressor {name!r}: expected one of "
            f"{', '.join(sorted(valid_names()))}")
    return comp


def wire_dtype_for(name: str, dtype):
    """The narrowed wire dtype ``name`` implies for tensors of
    ``dtype``, or ``None`` when cast compression does not apply
    (identity/quantized compressors, non-float payloads, already-narrow
    floats) — the same applicability rule as
    :meth:`_CastCompressor.compress`, decidable from the dtype alone so
    jitted kernels can fold the casts at trace time."""
    comp = resolve(name)
    wire = getattr(comp, "wire_dtype", None)
    if wire is None:
        return None
    if (jnp.issubdtype(jnp.dtype(dtype), jnp.floating)
            and jnp.dtype(dtype).itemsize > jnp.dtype(wire).itemsize):
        return wire
    return None


# ---------------------------------------------------------------------------
# Wire formats (the executor's static view of one compressor choice)
# ---------------------------------------------------------------------------

@dataclass(frozen=True)
class WireFormat:
    """Everything about one compressor choice that changes the traced
    program — hashable, part of the megakernel GroupSpec cache key and
    of the fusion-plan digest the executable is recorded under."""

    kind: str                  # "cast" | "quant"
    name: str                  # registry name ("bf16", "int8", ...)
    bits: int                  # wire bits per element (16 / 8 / 4)
    wire_dtype: str = ""       # cast only: "bfloat16" / "float16"
    block: int = 0             # quant only: scaling-block elements
    stochastic: bool = True    # quant only: stochastic rounding
    error_feedback: bool = True  # quant only: EF residuals


def quant_block() -> int:
    return max(2, int(os.environ.get(BLOCK_ENV, str(_DEFAULT_BLOCK))))


def quant_seed() -> int:
    return int(os.environ.get(SEED_ENV, "0") or 0)


def _rounding() -> str:
    mode = os.environ.get(ROUNDING_ENV, "stochastic").strip().lower()
    if mode not in ("stochastic", "nearest"):
        raise ValueError(
            f"{ROUNDING_ENV}={mode!r}: expected stochastic or nearest")
    return mode


def wire_format(name: str) -> Optional[WireFormat]:
    """The :class:`WireFormat` of compressor ``name`` (dtype-independent
    form; ``None`` for the identity compressor)."""
    comp = resolve(name)
    if comp is NoneCompressor:
        return None
    cast = getattr(comp, "wire_dtype", None)
    if cast is not None:
        return WireFormat(kind="cast", name=name.strip().lower(),
                          bits=8 * jnp.dtype(cast).itemsize,
                          wire_dtype=jnp.dtype(cast).name,
                          stochastic=False, error_feedback=False)
    return WireFormat(
        kind="quant", name=name.strip().lower(), bits=comp.bits,
        block=quant_block(), stochastic=_rounding() == "stochastic",
        error_feedback=os.environ.get(EF_ENV, "1") != "0")


def wire_format_for(name: str, dtype, numel: int) -> Optional[WireFormat]:
    """``wire_format`` gated by applicability: compression applies only
    to floating payloads wider than the wire format, and quantization
    additionally skips tiny tensors (scalars, layernorm vectors —
    ``HVD_TPU_QUANT_MIN_ELEMS``) where a per-block scale would cost more
    than it saves."""
    fmt = wire_format(name)
    if fmt is None:
        return None
    dt = jnp.dtype(dtype)
    if not jnp.issubdtype(dt, jnp.floating):
        return None
    if fmt.kind == "cast":
        if dt.itemsize * 8 <= fmt.bits:
            return None
        return fmt
    floor = int(os.environ.get(MIN_ELEMS_ENV, str(_DEFAULT_MIN_ELEMS)))
    if numel < max(floor, 1):
        return None
    return fmt


# ---------------------------------------------------------------------------
# Per-tensor / per-process-set selection policy
# ---------------------------------------------------------------------------

class CompressionPolicy:
    """Name-pattern → compressor registry (the per-tensor selection
    surface).  Precedence: first matching rule > the process set's
    override > the default.  All fields are resolved at construction so
    a typo fails at ``set_compression`` time with the full name list."""

    def __init__(self, default: Optional[str] = None,
                 rules: Sequence[Tuple[str, str]] = (),
                 process_sets: Optional[Dict[int, str]] = None):
        self.default = (default.strip().lower()
                        if default is not None else None)
        if self.default is not None:
            resolve(self.default)
        self.rules: List[Tuple[re.Pattern, str]] = []
        for pattern, name in rules or ():
            resolve(name)
            self.rules.append((re.compile(pattern), name.strip().lower()))
        self.process_sets = {int(k): v.strip().lower()
                             for k, v in (process_sets or {}).items()}
        for name in self.process_sets.values():
            resolve(name)

    def name_for(self, tensor_name: str, process_set_id: int = 0) -> str:
        for pattern, name in self.rules:
            if pattern.search(tensor_name):
                return name
        if process_set_id in self.process_sets:
            return self.process_sets[process_set_id]
        if self.default is not None:
            return self.default
        return os.environ.get(DEFAULT_ENV, "none")


_policy: Optional[CompressionPolicy] = None


def set_compression(default: Optional[str] = None,
                    rules: Optional[Sequence[Tuple[str, str]]] = None,
                    process_sets: Optional[Dict[int, str]] = None) -> None:
    """Install the process-wide wire-compression policy for the dynamic
    collective path (``None``/no args restores the env default).

    MUST be called identically on every rank — like the env knobs, the
    policy selects the compiled SPMD program.  Installing a policy
    flushes the executor's compiled kernels and error-feedback
    residuals (a residual accumulated under one codebook is meaningless
    under another)."""
    global _policy
    if default is None and not rules and not process_sets:
        _policy = None
    else:
        _policy = CompressionPolicy(default, rules or (), process_sets)
    from . import megakernel as _megakernel

    _megakernel.flush("compression policy change")


def get_compression() -> Optional[CompressionPolicy]:
    return _policy


def policy_name_for(tensor_name: str, process_set_id: int = 0) -> str:
    """The effective compressor NAME for one tensor (rules > set
    override > default > env)."""
    p = _policy
    if p is not None:
        return p.name_for(tensor_name, process_set_id)
    return os.environ.get(DEFAULT_ENV, "none")


def policy_format_for(tensor_name: str, process_set_id: int,
                      dtype, numel: int) -> Optional[WireFormat]:
    """Policy lookup + applicability gate in one step (what the
    executor partitions fusion groups by)."""
    return wire_format_for(policy_name_for(tensor_name, process_set_id),
                           dtype, numel)


# ---------------------------------------------------------------------------
# Block-wise quantization primitives (trace-safe jnp; shared verbatim by
# the megakernel bodies and the eager reference so the two are bitwise
# comparable)
# ---------------------------------------------------------------------------

def _levels(bits: int) -> int:
    return (1 << (bits - 1)) - 1  # 127 for int8, 7 for int4


def pack_int4(q):
    """Pack int8 values in [-7, 7] into nibbles: two codes per wire
    byte, even/odd interleaved (last dim must be even)."""
    u = (q.astype(jnp.int16) + 8).astype(jnp.uint8)
    return (u[..., 0::2] | (u[..., 1::2] << 4)).astype(jnp.uint8)


def unpack_int4(p):
    lo = (p & 0xF).astype(jnp.int8) - 8
    hi = (p >> 4).astype(jnp.int8) - 8
    return jnp.stack([lo, hi], axis=-1).reshape(
        p.shape[:-1] + (p.shape[-1] * 2,))


def _dither(key, shape):
    """The stochastic-rounding dither: an 8-bit discrete uniform on
    {0, 1/256, ..., 255/256}.  256 rounding levels bias an element by
    at most 2^-9 of a quantization step — far below the codebooks'
    resolution — while costing a quarter of a float32 uniform's
    threefry work (the dominant quantization cost on the CPU bench)."""
    return (jax.random.bits(key, shape, jnp.uint8)
            .astype(jnp.float32) * jnp.float32(1.0 / 256.0))


def _pow2_scale(amax, bits: int):
    """The smallest power of two ``s`` with ``amax <= levels * s``,
    computed with INTEGER exponent arithmetic on the float bits.

    Power-of-two scales are the load-bearing determinism choice: every
    multiply/divide by the scale is exact, the bfloat16 wire cast is
    exact, and — because no float rounding is involved anywhere in the
    scale path — no XLA algebraic rewrite (constant-division strength
    reduction, convert folding, ...) can produce different bits in
    different surrounding programs.  A float formulation (amax/levels)
    measurably diverged between the fused kernel and the eager
    reference compilation.  Cost: at most one extra bit of
    quantization step vs the optimal scale, which stochastic rounding
    and error feedback absorb (docs/tensor-fusion.md)."""
    a = jax.lax.bitcast_convert_type(amax, jnp.uint32)
    E = (a >> 23).astype(jnp.int32) - 127
    m_field = (a & jnp.uint32(0x7FFFFF)).astype(jnp.int32)
    if bits == 8:
        # levels=127: 127*2^(E-6) covers mantissas up to 1.984375.
        base, thresh = 6, int(0.984375 * (1 << 23))
    else:
        # levels=7: 7*2^(E-2) covers mantissas up to 1.75.
        base, thresh = 2, int(0.75 * (1 << 23))
    p = E - base + jnp.where(m_field > thresh, 1, 0)
    pe = jnp.clip(p + 127, 1, 254).astype(jnp.uint32)
    scale = jax.lax.bitcast_convert_type(pe << 23, jnp.float32)
    return jnp.where(amax > 0, scale, jnp.float32(0.0))


def quantize_blocks(rows, fmt: WireFormat, key=None):
    """Block-wise quantize ``rows[..., m]`` (m % fmt.block == 0) →
    ``(wire, scales)``: int8 codes (packed nibbles for int4) plus one
    bfloat16 power-of-two scale per block (:func:`_pow2_scale`) —
    exactly the bytes a peer needs to decode.  Stochastic rounding
    (floor(x + u), u~U[0,1)) keeps each element unbiased; ``key`` must
    be supplied when fmt.stochastic."""
    lead, m = rows.shape[:-1], rows.shape[-1]
    lv = float(_levels(fmt.bits))
    b = rows.astype(jnp.float32).reshape(lead + (m // fmt.block, fmt.block))
    scale = _pow2_scale(jnp.max(jnp.abs(b), axis=-1), fmt.bits)
    x = b / jnp.where(scale > 0, scale, jnp.float32(1.0))[..., None]
    if fmt.stochastic:
        x = jnp.floor(x + _dither(key, x.shape))
    else:
        # floor(x + 1/2) (round-half-up), not round-to-nearest-even:
        # bitwise-deterministic like RNE but an order of magnitude
        # cheaper on the CPU backend's scalarized round lowering.
        x = jnp.floor(x + jnp.float32(0.5))
    q = jnp.clip(x, -lv, lv).astype(jnp.int8).reshape(lead + (m,))
    if fmt.bits == 4:
        q = pack_int4(q)
    return q, scale.astype(jnp.bfloat16)


def dequantize_blocks(wire, scales, fmt: WireFormat):
    """Inverse of :func:`quantize_blocks` in float32 (the accumulation
    dtype): decode codes, multiply by the block scales."""
    q = unpack_int4(wire) if fmt.bits == 4 else wire
    lead, m = q.shape[:-1], q.shape[-1]
    b = q.astype(jnp.float32).reshape(lead + (m // fmt.block, fmt.block))
    out = b * scales.astype(jnp.float32)[..., None]
    return out.reshape(lead + (m,))


def wire_bytes_per_chunk(m: int, fmt: WireFormat) -> int:
    """Bytes one m-element chunk occupies on the wire: packed codes
    plus 2-byte bfloat16 block scales — the exact frame
    :func:`wire_pack` builds."""
    return m * fmt.bits // 8 + (m // fmt.block) * 2


def wire_pack(q, s, fmt: WireFormat):
    """Frame codes + scales as ONE uint8 wire buffer per chunk row —
    one collective moves the whole frame (codes and scales in two
    separate exchanges would double the per-collective latency)."""
    qb = q if fmt.bits == 4 else jax.lax.bitcast_convert_type(q, jnp.uint8)
    sb = jax.lax.bitcast_convert_type(s, jnp.uint8).reshape(
        s.shape[:-1] + (2 * s.shape[-1],))
    return jnp.concatenate([qb, sb], axis=-1)


def wire_unpack(w, m: int, fmt: WireFormat):
    """Split a :func:`wire_pack` frame back into ``(codes, scales)``
    for an m-element chunk."""
    q_len = m * fmt.bits // 8
    n_blocks = m // fmt.block
    qb = w[..., :q_len]
    q = qb if fmt.bits == 4 else jax.lax.bitcast_convert_type(qb, jnp.int8)
    sb = w[..., q_len:q_len + 2 * n_blocks]
    s = jax.lax.bitcast_convert_type(
        sb.reshape(sb.shape[:-1] + (n_blocks, 2)), jnp.bfloat16)
    return q, s


def step_key(seed, tick):
    """The per-step PRNG root: every stochastic-rounding draw of one
    fused launch descends from fold_in(PRNGKey(seed), tick), so a fixed
    seed + the executor's per-group tick give bitwise-reproducible
    noise (tests/test_megakernel.py)."""
    return jax.random.fold_in(jax.random.PRNGKey(seed), tick)


def _noise_key(key, tag: int, pos):
    """Leg/participant key derivation: ``tag`` separates phases/legs,
    ``pos`` decorrelates participants (may be a traced axis index)."""
    return jax.random.fold_in(jax.random.fold_in(key, tag), pos)


def padded_length(T: int, n: int, block: int) -> int:
    """T rounded up so each of the n exchange chunks is a whole number
    of scaling blocks."""
    unit = n * block
    return -(-T // unit) * unit


def ordered_sum(rows):
    """Accumulate ``rows[0] + rows[1] + ...`` as an explicit sequential
    chain instead of ``jnp.sum(axis=0)``: XLA may vectorize a reduce
    with a different float association per surrounding program, and the
    megakernel↔reference BITWISE contract needs the exact same addition
    order in both compilations (n is small and static — the chain costs
    the same n−1 adds)."""
    acc = rows[0]
    for i in range(1, rows.shape[0]):
        acc = acc + rows[i]
    return acc


# ---------------------------------------------------------------------------
# The quantized reduction itself
# ---------------------------------------------------------------------------
# Two formulations of the same math:
#   * quantized_reduce_collective — lax collectives, runs INSIDE a
#     shard_map megakernel body (one XLA program per fusion group);
#   * reference_allreduce — pure eager jnp over the stacked rows, the
#     specification the kernel is tested bitwise against and the eager
#     executor fallback when HVD_TPU_MEGAKERNEL=0.
# Both call the exact helpers above in the exact same order.

def quantized_reduce_collective(vin, fmt: WireFormat, key, *, axis,
                                n: int, my_chunk, noise_pos,
                                groups=None, error_feedback=False,
                                phase2_feedback=False):
    """Two-phase quantized allreduce of the local vector ``vin`` [Tp]
    (pre-padded: Tp % (n * fmt.block) == 0) over ``axis`` (optionally
    ``axis_index_groups``-scoped).  Returns ``(reduced [Tp] float32,
    new_residual [Tp] vin.dtype | None)``."""
    dtype = vin.dtype
    C = vin.shape[0] // n
    c = vin.reshape(n, C)
    q, s = quantize_blocks(c, fmt, _noise_key(key, 1, noise_pos))
    wx = jax.lax.all_to_all(wire_pack(q, s, fmt), axis, split_axis=0,
                            concat_axis=0, axis_index_groups=groups)
    qx, sx = wire_unpack(wx, C, fmt)
    red = ordered_sum(dequantize_blocks(qx, sx, fmt))  # [C] f32
    q2, s2 = quantize_blocks(red[None], fmt, _noise_key(key, 2, my_chunk))
    wg = jax.lax.all_gather(wire_pack(q2, s2, fmt), axis, axis=0,
                            tiled=True, axis_index_groups=groups)
    qg, sg = wire_unpack(wg, C, fmt)
    out = dequantize_blocks(qg, sg, fmt).reshape(-1)  # [Tp] f32
    r_new = None
    if error_feedback:
        r_new = vin - dequantize_blocks(q, s, fmt).reshape(-1).astype(dtype)
        if phase2_feedback:
            # The chunk owner also knows phase 2's error; feeding it
            # back through the owner's own residual re-enters the sum
            # next step (the telescoping EF argument covers both).
            e2 = (red - dequantize_blocks(q2, s2, fmt)[0]).astype(dtype)
            start = my_chunk * C
            cur = jax.lax.dynamic_slice(r_new, (start,), (C,))
            r_new = jax.lax.dynamic_update_slice(r_new, cur + e2, (start,))
    return out, r_new


def quantized_gather_sum(frag, fmt: WireFormat, key, *, axis, pos,
                         groups=None):
    """Single-shot quantized sum of a fragment across a (small) group:
    quantize locally, all_gather the wire payload, dequantize and sum
    in float32 — the DCN leg of the hierarchical allreduce (a handful
    of slices, so one exchange beats the two-phase latency)."""
    q, s = quantize_blocks(frag[None], fmt, _noise_key(key, 3, pos))
    wg = jax.lax.all_gather(wire_pack(q, s, fmt), axis, axis=0,
                            tiled=True, axis_index_groups=groups)
    qg, sg = wire_unpack(wg, frag.shape[0], fmt)
    return ordered_sum(dequantize_blocks(qg, sg, fmt))


def quantized_all_gather(frag, fmt: WireFormat, key, *, axis, pos,
                         groups=None):
    """All_gather in wire format: quantize the local fragment, gather
    the codes+scales, dequantize everything — the final (ICI) leg of a
    fully-quantized hierarchical allreduce."""
    q, s = quantize_blocks(frag[None], fmt, _noise_key(key, 4, pos))
    wg = jax.lax.all_gather(wire_pack(q, s, fmt), axis, axis=0,
                            tiled=True, axis_index_groups=groups)
    qg, sg = wire_unpack(wg, frag.shape[0], fmt)
    return dequantize_blocks(qg, sg, fmt).reshape(-1)


def quantized_scatter_sum(v, fmt: WireFormat, key, *, axis, n: int,
                          noise_pos, groups=None):
    """Quantized reduce-scatter (phase 1 of the two-phase exchange,
    standalone): returns this participant's reduced chunk [C] float32 —
    the ICI leg of a fully-quantized hierarchical allreduce."""
    C = v.shape[0] // n
    c = v.reshape(n, C)
    q, s = quantize_blocks(c, fmt, _noise_key(key, 1, noise_pos))
    wx = jax.lax.all_to_all(wire_pack(q, s, fmt), axis, split_axis=0,
                            concat_axis=0, axis_index_groups=groups)
    qx, sx = wire_unpack(wx, C, fmt)
    return ordered_sum(dequantize_blocks(qx, sx, fmt))


def reference_allreduce(rows, fmt: WireFormat, tick: int, *,
                        seed: Optional[int] = None, residuals=None,
                        shared_noise: bool = False):
    """Eager-quantized reference: the exact math of the fused quantized
    megakernel, computed from the stacked per-replica rows.

    ``rows``: [n, T] (row i = replica i's contribution); ``residuals``:
    [n, T] or None.  Returns ``(reduced [T] rows.dtype, new_residuals
    [n, T] | None)`` — ``reduced`` is what every replica decodes (the
    allreduce SUM; callers fold AVERAGE themselves), bitwise identical
    to the megakernel's output under the same (seed, tick)."""
    rows = jnp.asarray(rows)
    n, T = rows.shape
    dtype = rows.dtype
    Tp = padded_length(T, n, fmt.block)
    vin = rows if residuals is None else rows + jnp.asarray(residuals)
    if Tp != T:
        vin = jnp.pad(vin, ((0, 0), (0, Tp - T)))
    C = Tp // n
    key = step_key(quant_seed() if seed is None else seed, tick)
    ef = fmt.error_feedback
    phase2 = ef and not shared_noise
    qs, ss = [], []
    for i in range(n):
        q, s = quantize_blocks(
            vin[i].reshape(n, C), fmt,
            _noise_key(key, 1, 0 if shared_noise else i))
        qs.append(q)
        ss.append(s)
    deq = jnp.stack([dequantize_blocks(q, s, fmt)
                     for q, s in zip(qs, ss)])     # [contrib, chunk, C]
    red = ordered_sum(deq)                         # [chunk, C] float32
    deq2 = []
    for d in range(n):
        q2, s2 = quantize_blocks(red[d][None], fmt, _noise_key(key, 2, d))
        deq2.append(dequantize_blocks(q2, s2, fmt)[0])
    out = jnp.concatenate(deq2)[:T].astype(dtype)
    r_new = None
    if ef:
        r_new = vin - deq.reshape(n, Tp).astype(dtype)
        if phase2:
            e2 = (red - jnp.stack(deq2)).astype(dtype)
            for i in range(n):
                cur = jax.lax.dynamic_slice(r_new[i], (i * C,), (C,))
                r_new = r_new.at[i].set(jax.lax.dynamic_update_slice(
                    r_new[i], cur + e2[i], (i * C,)))
        r_new = r_new[:, :T]
    return out, r_new


# ---------------------------------------------------------------------------
# Init-time validation (the env-knob uniformity contract)
# ---------------------------------------------------------------------------

_SPMD_ENV_KNOBS = (
    DEFAULT_ENV, "HVD_TPU_DCN_COMPRESS", "HVD_TPU_ICI_COMPRESS",
    BLOCK_ENV, ROUNDING_ENV, EF_ENV, SEED_ENV, MIN_ELEMS_ENV,
    "HVD_TPU_HIERARCHICAL", "HVD_TPU_VIRTUAL_SLICES",
    "HVD_TPU_MEGAKERNEL",
    # Backward/communication overlap (parallel/overlap.py): selects
    # which compiled programs a training step runs — monolithic vs
    # bucketed sub-programs — so a rank diverging on it must be named
    # at startup exactly like the compression/topology knobs.
    "HVD_TPU_OVERLAP",
    # MPMD pipeline schedule (parallel/pipeline.py): selects the
    # dispatch ORDER of the per-stage executables (1f1b vs gpipe,
    # interleave depth) — rank-divergent orders would desynchronize
    # the per-stage partial-cycle negotiation.
    "HVD_TPU_PIPELINE_SCHEDULE", "HVD_TPU_PIPELINE_INTERLEAVE",
    # Tree control-plane overlay (ops/tree.py): these select the wire
    # conversation itself (who connects to whom, which frames flow), so
    # a divergent rank would deadlock the handshake — name it at init.
    "HVD_TPU_TREE", "HVD_TPU_TREE_FANOUT", "HVD_TPU_TREE_THRESHOLD",
    # Fused computation-collective kernels (ops/fused.py): mode and
    # chunk count are part of the compiled SPMD program's identity —
    # a rank with a different chunk plan compiles a DIFFERENT program
    # for the same collective, so divergence must be named at startup.
    "HVD_TPU_FUSE", "HVD_TPU_FUSE_CHUNKS",
)


def validate_env() -> None:
    """Fail init — not the first collective — on a malformed compression
    knob, with the full valid-name list in the error."""
    for knob in (DEFAULT_ENV, "HVD_TPU_DCN_COMPRESS",
                 "HVD_TPU_ICI_COMPRESS"):
        value = os.environ.get(knob)
        if value:
            try:
                resolve(value)
            except ValueError as e:
                raise ValueError(f"{knob}={value!r}: {e}") from None
    _rounding()
    for knob in (BLOCK_ENV, SEED_ENV, MIN_ELEMS_ENV):
        value = os.environ.get(knob)
        if value:
            try:
                int(value)
            except ValueError:
                raise ValueError(
                    f"{knob}={value!r}: expected an integer") from None
    block = quant_block()
    if block % 2:
        raise ValueError(f"{BLOCK_ENV}={block}: the int4 nibble packing "
                         f"needs an even block size")


def env_fingerprint() -> str:
    """Canonical ``knob=value`` line of every SPMD-program-affecting
    compression/topology knob — exchanged in the control-plane HELLO
    handshake so rank-divergent settings are caught AT INIT (a divergent
    knob means divergent compiled programs: silent garbage or a hang).
    Values are the *effective* ones (unset == default)."""
    parts = []
    for knob in _SPMD_ENV_KNOBS:
        parts.append(f"{knob}={os.environ.get(knob, '') or '<unset>'}")
    return ";".join(parts)
