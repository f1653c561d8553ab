"""Flash attention as Pallas TPU kernels (forward + backward).

The reference framework has no attention code at all (SURVEY.md §5,
"Long-context / sequence parallelism: absent") — this is a beyond-parity
component that the long-context stack (:mod:`..parallel.sequence`) builds
on.  The softmax runs online (one pass over K/V, O(seq) memory instead
of O(seq²)), matmuls take the operands in their own dtype (bfloat16
stays MXU-friendly) and accumulate in float32 via
``preferred_element_type``, the softmax is float32, and the backward
uses the saved log-sum-exp rows plus ``delta = rowsum(dO * O)``, so
nothing quadratic is ever materialized.

Two entries, two layouts:

* :func:`flash_attention_qkv` — ``qkv : [batch, seq, 3 x heads x
  head_dim]``, the model's fused projection as it lies (q | k | v along
  the last axis, each heads-major), result ``[batch, seq, heads x
  head_dim]``.  What :mod:`..models.transformer` calls.
* :func:`flash_attention` / :func:`flash_attention_with_lse` —
  ``q, k, v : [batch, heads, seq, head_dim]``, any q/kv lengths and a
  ``q_block_offset``; what ring attention and Ulysses call.

Which shapes take which kernels:

* **Resident** (ONE forward and ONE backward kernel): self-attention
  (``q_len == kv_len``, no offset) whose head_dim divides 128 with a
  whole number of heads to a 128-lane block (two heads of 64, one of
  128), sequence padded to 128 within ``HVD_TPU_FLASH_RESIDENT_SEQ``
  (4096).  A grid cell owns one (batch, 128-lane block): it reads its
  q, k and v columns straight out of the ``[batch, seq, lanes]``
  activations (no transpose, no padded copy: every load, store and DMA
  is 128 lanes full) and holds the whole sequence in VMEM.  The heads
  of a block are separated inside the contractions (the other head's
  lanes of q and dO are zeroed), never by a lane shuffle.  Scores are
  kept transposed (keys in sublanes, queries in lanes), so the
  softmax's reductions run over sublanes and ``lse`` / ``delta`` travel
  lane-dense (``[batch, lane blocks, heads a block, seq]``), never as a
  minor dimension of 1.  Only tiles the diagonal crosses are masked.
  The backward is one pass: S, P, dP and dS once a tile pair, five
  matmuls, dQ accumulated in float32 VMEM, ``delta`` computed in the
  kernel.  **The path chooses its own tiles from the shape**
  (:func:`_resident_tiles`); ``block_q``/``block_k`` are not read here.
  The ``[b, h, s, d]`` entry reaches the same kernels through a thin
  wrapper (two transposes each way).
* **Streaming** (a forward, a dK/dV and a dQ kernel over a 3-D grid,
  state in VMEM scratch): everything else — sequences beyond the
  resident limit, cross attention, ring attention's offset chunks, a
  head_dim that does not divide 128, an odd number of heads to fill a
  lane block (e.g. an odd local head count under tensor parallelism).
  ``block_q``/``block_k`` are its tiles; VMEM stays O(block).

On non-TPU backends (the CPU test mesh) the default is a dense-jnp exact
attention with the same (o, lse) contract — the Pallas interpreter is
~1000x slower and only exercises the kernels, which the kernel tests do
explicitly via ``interpret=True`` / ``HVD_TPU_FLASH_INTERPRET=1``.
"""

from __future__ import annotations

import functools
import math
import os
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

DEFAULT_MASK_VALUE = -0.7 * float(jnp.finfo(jnp.float32).max)


def kernel_runs(interpret) -> bool:
    """THE rule of every Pallas kernel under ``ops/``: the kernel on the
    TPU, or wherever a caller asks for it by name (``interpret`` not
    ``None``; ``True`` is the interpreter, a test's); elsewhere the
    caller's twin in plain ``jnp``.  The one place the backend is asked."""
    return interpret is not None or jax.default_backend() == "tpu"


def _interpret_default() -> bool:
    return not kernel_runs(None)


def _dense_default() -> bool:
    """On non-TPU backends, ``interpret=None`` resolves to a dense-jnp
    path (mathematically identical exact attention) instead of the Pallas
    interpreter, which executes ~1000x slower and exists only to test the
    kernels themselves.  Kernel tests opt back in with ``interpret=True``
    or ``HVD_TPU_FLASH_INTERPRET=1``."""
    force_interpret = os.environ.get(
        "HVD_TPU_FLASH_INTERPRET", "").lower() in ("1", "true", "yes")
    return not kernel_runs(True if force_interpret else None)


def _dense_mask(s, *, causal, q_block_offset, q_len, k_len):
    if not causal:
        return s
    q_pos = q_block_offset + jnp.arange(q_len)[:, None]
    k_pos = jnp.arange(k_len)[None, :]
    return jnp.where(q_pos >= k_pos, s, -jnp.inf)


def _dense_forward(q, k, v, sm_scale, causal, q_block_offset):
    """(o, lse) via exact dense attention — same contract as the kernel."""
    s = jnp.einsum("bhqd,bhkd->bhqk", q.astype(jnp.float32),
                   k.astype(jnp.float32)) * sm_scale
    s = _dense_mask(s, causal=causal, q_block_offset=q_block_offset,
                    q_len=q.shape[2], k_len=k.shape[2])
    lse = jax.scipy.special.logsumexp(s, axis=-1)  # -inf for masked rows
    p = jnp.where(jnp.isneginf(lse)[..., None], 0.0,
                  jnp.exp(s - lse[..., None]))
    o = jnp.einsum("bhqk,bhkd->bhqd", p,
                   v.astype(jnp.float32)).astype(q.dtype)
    return o, lse


def _dense_backward(res, g, *, sm_scale, causal, q_block_offset):
    """Flash-backward math, densely: uses the caller's (possibly globally
    accumulated) ``o``/``lse`` so ring attention's per-chunk gradients
    stay normalized across the whole sequence."""
    q, k, v, o, lse = res
    qf, kf, vf = (x.astype(jnp.float32) for x in (q, k, v))
    gf, of = g.astype(jnp.float32), o.astype(jnp.float32)
    s = jnp.einsum("bhqd,bhkd->bhqk", qf, kf) * sm_scale
    s = _dense_mask(s, causal=causal, q_block_offset=q_block_offset,
                    q_len=q.shape[2], k_len=k.shape[2])
    p = jnp.where(jnp.isneginf(lse)[..., None], 0.0,
                  jnp.exp(s - lse[..., None]))
    dv = jnp.einsum("bhqk,bhqd->bhkd", p, gf)
    delta = jnp.sum(gf * of, axis=-1)                 # [b, h, q]
    dp = jnp.einsum("bhqd,bhkd->bhqk", gf, vf)
    ds = p * (dp - delta[..., None]) * sm_scale
    dq = jnp.einsum("bhqk,bhkd->bhqd", ds, kf).astype(q.dtype)
    dk = jnp.einsum("bhqk,bhqd->bhkd", ds, qf).astype(k.dtype)
    return dq, dk, dv.astype(v.dtype)


def _apply_mask(s, *, q_start, k_start, kv_actual, kv_padded, causal,
                q_block_offset, window: int = 0):
    """Shared score mask of the three streaming kernels: padded keys (past
    ``kv_actual``) and, when ``causal``, future positions and, within a
    ``window`` (forward only), the keys ``window`` or more positions back.
    Forward and backward MUST mask identically or gradients silently
    diverge."""
    block_q, block_k = s.shape
    if not causal and kv_actual == kv_padded:
        return s
    k_pos = k_start + jax.lax.broadcasted_iota(jnp.int32,
                                               (block_q, block_k), 1)
    valid = k_pos < kv_actual
    if causal:
        q_pos = (q_start + q_block_offset
                 + jax.lax.broadcasted_iota(jnp.int32,
                                            (block_q, block_k), 0))
        valid = jnp.logical_and(valid, q_pos >= k_pos)
        if window:
            valid = jnp.logical_and(valid, q_pos - k_pos < window)
    return jnp.where(valid, s, DEFAULT_MASK_VALUE)


# ---------------------------------------------------------------------------
# Forward kernel
# ---------------------------------------------------------------------------

def _resident_max_seq() -> int:
    """Self-attention up to this (padded) length may use the resident
    kernels (one grid cell holds a whole sequence of one 128-lane block
    in VMEM and walks the tiles in-kernel: no per-tile grid step, no
    re-read of K/V).  Beyond it the streaming kernels bound VMEM at
    O(block).  Read at TRACE time: changing the env after a function was
    jit-compiled does not re-route its cached executable; tests force a
    path by setting the env before tracing."""
    return int(os.environ.get("HVD_TPU_FLASH_RESIDENT_SEQ", "4096"))


_LANES = 128
_NT_DIMS = (((1,), (1,)), ((), ()))   # contract the lane axis of both


def _resident_tiles(s_pad: int) -> tuple:
    """(tile_q, tile_k) of the resident kernels, from the padded
    sequence alone.

    A score tile is ``[tile_k, tile_q]`` float32, keys in sublanes and
    queries in lanes.  tile_q is as wide as the sequence allows, up to
    1024: the tile_q // tile_k k tiles the diagonal crosses are then
    walked in straight-line code with static masks, each against only
    the q columns that can see it, and the loop's serial chain (matmul,
    softmax, matmul) is entered once for many independent 128 x 128
    matmuls.  Tiles far past the register file spill to VMEM, which
    costs less than the loop trips they save.  Measured on the v5e at
    ``[8, 1024, 16 x 64]`` bfloat16 (PERF.md, PR 28), forward / backward
    ms a step: 6.5 / 17.4 at 1024 x 128 and at 1024 x 256, 8.1 / 18.2 at
    512 x 256, 25 / 37 at 128 x 128 in loops.  tile_k is 256 where it
    divides: the same time as 128, and half the straight-line code to
    trace and lower.  No shape this path takes wants another rule yet
    (head_dim 128 and float32 walk the same tiles; no cell measures
    them)."""
    tq = max(t for t in (128, 256, 512, 1024) if s_pad % t == 0)
    return tq, min(tq, 256)


def _resident_pad(seq_len: int) -> int:
    """The sequence padded to whole 128-token blocks."""
    return -(-seq_len // _LANES) * _LANES


def _resident_ok(heads: int, head_dim: int, q_len: int, kv_len: int,
                 q_block_offset: int) -> bool:
    """Whether self-attention of this shape runs on the resident
    kernels: whole head groups to a 128-lane block, one sequence for
    queries and keys, and short enough to sit in VMEM."""
    if head_dim > _LANES or _LANES % head_dim:
        return False
    return (heads % (_LANES // head_dim) == 0 and q_len == kv_len
            and q_block_offset == 0
            and _resident_pad(q_len) <= _resident_max_seq())


def _head_operands(x, dst_ref, lane_head, heads):
    """``dst_ref[h] = x`` with every lane outside head ``h`` zeroed: the
    heads of a lane block are separated in the contraction itself (a
    zero lane adds nothing), never by a lane shuffle."""
    for h in range(heads):
        dst_ref[h] = x if heads == 1 else jnp.where(
            lane_head == h, x, jnp.zeros_like(x))


def _walk_k_tiles(i, step, carry, *, tq, tk, n_k, causal, kv_actual):
    """``carry = step(j, carry, valid, c0)`` over the k tiles that q tile
    ``i`` sees, the one walk of the forward and the backward (they MUST
    mask alike).  Tiles wholly under the diagonal come in a loop, with no
    mask.  The tq // tk tiles the diagonal crosses follow in straight-line
    code: each meets only the q columns from its own first key on
    (``c0``), under one static triangle, which also hides any padded key
    from every real query.  Without a causal mask only a padded last
    tile is masked."""
    sub = jax.lax.broadcasted_iota(jnp.int32, (tk, tq), 0)
    padded = kv_actual < n_k * tk
    n_plain = i * (tq // tk) if causal else n_k - padded
    carry = jax.lax.fori_loop(
        0, n_plain, lambda j, c: step(j, c, None, 0), carry)
    if causal:
        lane = jax.lax.broadcasted_iota(jnp.int32, (tk, tq), 1)
        for t in range(tq // tk):
            c0 = t * tk
            carry = step(n_plain + t, carry, (lane >= sub)[:, :tq - c0], c0)
    elif padded:
        carry = step(n_k - 1, carry, sub < kv_actual - (n_k - 1) * tk, 0)
    return carry


def _lse_block_spec(heads, s_pad):
    return pl.BlockSpec((None, None, heads, s_pad),
                        lambda b, g: (b, g, 0, 0))


def _fwd_kernel_resident(q_ref, k_ref, v_ref, o_ref, lse_ref, qm_ref,
                         vt_ref, *, heads: int, sm_scale: float,
                         fold_scale: bool, causal: bool, tq: int, tk: int,
                         kv_actual: int):
    """Grid cell (batch, lane block): causal/dense attention of the
    ``heads`` heads whose head_dim columns fill this 128-lane block of
    the ``[seq, heads x head_dim]`` activations, whole sequence in VMEM.

    Scores are held transposed, ``[tile_k, tile_q]``: the online
    softmax's statistics are then ``[1, tile_q]`` rows (reductions run
    over sublanes, ``lse`` leaves lane-dense) and the accumulator is
    ``[head_dim, tile_q]``, so a head is a sublane range of ``V^T`` and
    of the accumulator."""
    s_pad, lanes = q_ref.shape
    d = lanes // heads
    n_q, n_k = s_pad // tq, s_pad // tk
    f32 = jnp.float32
    lane_head = jax.lax.broadcasted_iota(jnp.int32, (1, lanes), 1) // d

    q = q_ref[...]
    if fold_scale:
        q = q * sm_scale                       # a power of two: exact
    _head_operands(q, qm_ref, lane_head, heads)
    vt_ref[...] = v_ref[...].astype(f32).T.astype(vt_ref.dtype)

    def tile(i, j, carry, valid, c0):
        """Online-softmax step of q tile i's columns ``[c0, tq)`` against
        k tile j; ``valid`` masks the scores where given."""
        qs = pl.ds(pl.multiple_of(i * tq + c0, _LANES), tq - c0)
        ks = pl.ds(pl.multiple_of(j * tk, tk), tk)
        k = k_ref[ks, :]
        # Every head's score matmul is issued before the first softmax:
        # the MXU then works on the next head while the VPU is in this
        # one's (a sixth of the forward's time, PERF.md PR 28).
        scores = [jax.lax.dot_general(k, qm_ref[h, qs, :], _NT_DIMS,
                                      preferred_element_type=f32)
                  for h in range(heads)]
        out = []
        for h in range(heads):
            m_prev, l_prev, acc = (x[:, c0:] for x in carry[h])
            st = scores[h]
            if not fold_scale:
                st = st * sm_scale
            if valid is not None:
                st = jnp.where(valid, st, DEFAULT_MASK_VALUE)
            m_new = jnp.maximum(m_prev, jnp.max(st, axis=0, keepdims=True))
            pt = jnp.exp(st - m_new)
            alpha = jnp.exp(m_prev - m_new)
            l_new = alpha * l_prev + jnp.sum(pt, axis=0, keepdims=True)
            acc = alpha * acc + jnp.dot(
                vt_ref[h * d:(h + 1) * d, ks], pt.astype(vt_ref.dtype),
                preferred_element_type=f32)
            new = (m_new, l_new, acc)
            out.append(new if c0 == 0 else tuple(
                jnp.concatenate([old[:, :c0], x], axis=1)
                for old, x in zip(carry[h], new)))
        return tuple(out)

    def q_tile(i, _):
        carry = tuple((jnp.full((1, tq), -jnp.inf, f32),
                       jnp.zeros((1, tq), f32), jnp.zeros((d, tq), f32))
                      for _ in range(heads))
        carry = _walk_k_tiles(i, functools.partial(tile, i), carry, tq=tq,
                              tk=tk, n_k=n_k, causal=causal,
                              kv_actual=kv_actual)
        qs = pl.ds(pl.multiple_of(i * tq, tq), tq)
        rows = []
        for h, (m, l, acc) in enumerate(carry):
            rows.append(acc * (1.0 / l))
            lse_ref[h:h + 1, qs] = m + jnp.log(l)
        o_t = rows[0] if heads == 1 else jnp.concatenate(rows, axis=0)
        o_ref[qs, :] = o_t.T.astype(o_ref.dtype)
        return 0

    jax.lax.fori_loop(0, n_q, q_tile, 0)


def _bwd_kernel_resident(q_ref, k_ref, v_ref, o_ref, do_ref, lse_ref,
                         *refs, heads: int, sm_scale: float,
                         fold_scale: bool, causal: bool, tq: int, tk: int,
                         kv_actual: int, fused_blocks: int):
    """Grid cell (batch, lane block): dQ, dK and dV of the block's heads
    in ONE walk over the live tiles, the forward's walk.  S, P, dP and
    dS are computed once a tile (five matmuls), transposed as in the
    forward so ``lse`` and ``delta`` are lane-dense rows; dQ, dK and dV
    accumulate in float32 VMEM, and ``delta = rowsum(dO * O)`` is
    computed here.

    With ``fused_blocks`` (the number of lane blocks of one operand)
    the three gradients leave as ONE ``[seq, dq | dk | dv]`` block that
    stays in VMEM over the batch element's cells, each cell writing its
    own columns: the fused projection's cotangent as its matmuls take
    it, with no concatenate behind the kernel."""
    if fused_blocks:
        dqkv_ref, *scratch = refs
        first = pl.program_id(1) * _LANES
        outs = [(dqkv_ref, pl.ds(pl.multiple_of(
            first + n * fused_blocks * _LANES, _LANES), _LANES))
            for n in range(3)]
    else:
        scratch = refs[3:]
        outs = [(ref, slice(None)) for ref in refs[:3]]
    qm_ref, dom_ref, km_ref, delta_ref, dq_acc, dk_acc, dv_acc = scratch
    s_pad, lanes = q_ref.shape
    d = lanes // heads
    n_q, n_k = s_pad // tq, s_pad // tk
    f32 = jnp.float32
    dt = q_ref.dtype
    lane_head = jax.lax.broadcasted_iota(jnp.int32, (1, lanes), 1) // d

    q = q_ref[...]
    if fold_scale:
        q = q * sm_scale
    _head_operands(q, qm_ref, lane_head, heads)
    _head_operands(do_ref[...], dom_ref, lane_head, heads)
    _head_operands(k_ref[...], km_ref, lane_head, heads)
    for acc in (dq_acc, dk_acc, dv_acc):
        acc[...] = jnp.zeros_like(acc)

    def delta_tile(i, _):
        qs = pl.ds(pl.multiple_of(i * tk, tk), tk)
        prod_t = (do_ref[qs, :].astype(f32) * o_ref[qs, :].astype(f32)).T
        for h in range(heads):
            delta_ref[h:h + 1, qs] = jnp.sum(
                prod_t[h * d:(h + 1) * d, :], axis=0, keepdims=True)
        return 0

    jax.lax.fori_loop(0, n_k, delta_tile, 0)

    def tile(i, j, carry, valid, c0):
        """q tile i's columns ``[c0, tq)`` against k tile j."""
        qs = pl.ds(pl.multiple_of(i * tq + c0, _LANES), tq - c0)
        ks = pl.ds(pl.multiple_of(j * tk, tk), tk)
        k = k_ref[ks, :]
        v = v_ref[ks, :]
        dq = dk = dv = 0.0
        for h in range(heads):
            qm = qm_ref[h, qs, :]
            dom = dom_ref[h, qs, :]
            st = jax.lax.dot_general(k, qm, _NT_DIMS,
                                     preferred_element_type=f32)
            if not fold_scale:
                st = st * sm_scale
            if valid is not None:
                st = jnp.where(valid, st, DEFAULT_MASK_VALUE)
            pt = jnp.exp(st - lse_ref[h:h + 1, qs])
            dpt = jax.lax.dot_general(v, dom, _NT_DIMS,
                                      preferred_element_type=f32)
            dst = pt * (dpt - delta_ref[h:h + 1, qs])
            if not fold_scale:
                dst = dst * sm_scale
            dv += jnp.dot(pt.astype(dt), dom, preferred_element_type=f32)
            dk += jnp.dot(dst.astype(dt), qm, preferred_element_type=f32)
            dq += jnp.dot(dst.T.astype(dt), km_ref[h, ks, :],
                          preferred_element_type=f32)
        dq_acc[qs, :] += dq
        dk_acc[ks, :] += dk
        dv_acc[ks, :] += dv
        return carry

    def q_tile(i, carry):
        return _walk_k_tiles(i, functools.partial(tile, i), carry, tq=tq,
                             tk=tk, n_k=n_k, causal=causal,
                             kv_actual=kv_actual)

    jax.lax.fori_loop(0, n_q, q_tile, 0)

    def put(n, acc, scale=None):
        ref, cols = outs[n]
        x = acc[...]
        ref[:, cols] = (x if scale is None else x * scale).astype(ref.dtype)

    put(0, dq_acc, sm_scale if fold_scale else None)
    put(1, dk_acc)
    put(2, dv_acc)


def _resident_statics(seq_len, head_dim, sm_scale, causal):
    tq, tk = _resident_tiles(_resident_pad(seq_len))
    # 1/8 (head_dim 64) is a power of two: scaling q by it is exact in
    # any float dtype; any other scale stays on the float32 scores.
    fold = math.frexp(sm_scale)[0] == 0.5
    return dict(heads=_LANES // head_dim, sm_scale=sm_scale,
                fold_scale=fold, causal=causal, tq=tq, tk=tk,
                kv_actual=seq_len)


def _pad_operands(xs, s_pad):
    """Zero-pad axis 1 (the sequence of ``[b, s, lanes]``) of each
    operand to s_pad; one array given several times is padded once."""
    done = {}
    for x in xs:
        if id(x) not in done:
            pad = s_pad - x.shape[1]
            done[id(x)] = jnp.pad(x, ((0, 0), (0, pad), (0, 0))) if pad else x
    return [done[id(x)] for x in xs]


def _lane_block_spec(s_pad, first_block):
    return pl.BlockSpec((None, s_pad, _LANES),
                        lambda b, g: (b, 0, first_block + g))


def _compiler_params(n_bytes: int, revisits_output: bool = False):
    # Whole-sequence blocks are double-buffered by the pipeline; leave
    # as much again for Mosaic's own temporaries.  A cell of a batch
    # element that shares its output block with the next must follow it.
    return pltpu.CompilerParams(
        dimension_semantics=("parallel",
                             "arbitrary" if revisits_output else "parallel"),
        vmem_limit_bytes=int(min(max(2 * n_bytes, 32 << 20), 100 << 20)))


# The fused-gradient block is [seq, 3 x width] for a whole batch element,
# twice (the pipeline's two buffers); past this it leaves as three arrays.
_FUSED_GRAD_VMEM = 16 << 20


@functools.partial(jax.jit, static_argnums=(3, 4, 5, 6, 7, 8), inline=True)
def _resident_forward(q, k, v, first_blocks, n_blocks, head_dim, sm_scale,
                      causal, interpret):
    """Forward on ``[batch, seq, lanes]`` activations.  ``q``, ``k`` and
    ``v`` may be one array (the fused projection): ``first_blocks``
    says at which 128-lane block of its array each begins, ``n_blocks``
    how many blocks (head groups) there are.  Returns ``o [b, s_pad,
    n_blocks x 128]`` and ``lse [b, n_blocks, heads a block, s_pad]``
    float32, both still padded to the tile."""
    batch, seq_len, _ = q.shape
    st = _resident_statics(seq_len, head_dim, sm_scale, causal)
    s_pad = _resident_pad(seq_len)
    q, k, v = _pad_operands((q, k, v), s_pad)
    heads = st["heads"]
    item = jnp.dtype(q.dtype).itemsize
    block = s_pad * _LANES * item
    return pl.pallas_call(
        functools.partial(_fwd_kernel_resident, **st),
        grid=(batch, n_blocks),
        in_specs=[_lane_block_spec(s_pad, f) for f in first_blocks],
        out_specs=[
            _lane_block_spec(s_pad, 0),
            _lse_block_spec(heads, s_pad),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((batch, s_pad, n_blocks * _LANES), q.dtype),
            jax.ShapeDtypeStruct((batch, n_blocks, heads, s_pad),
                                 jnp.float32),
        ],
        scratch_shapes=[
            pltpu.VMEM((heads, s_pad, _LANES), q.dtype),
            pltpu.VMEM((_LANES, s_pad), q.dtype),
        ],
        compiler_params=_compiler_params((8 + heads + 1) * block),
        interpret=interpret,
    )(q, k, v)


@functools.partial(jax.jit, static_argnums=(6, 7, 8, 9, 10, 11, 12),
                   inline=True)
def _resident_backward(q, k, v, o, do, lse, first_blocks, n_blocks,
                       head_dim, sm_scale, causal, interpret,
                       fused: bool = False):
    """(dq, dk, dv), each ``[b, s_pad, n_blocks x 128]``, from the
    forward's operands, its ``o`` and padded ``lse``, and ``do``; with
    ``fused`` (and room in VMEM) one ``[b, s_pad, 3 x n_blocks x 128]``
    array ``dq | dk | dv``, what the fused projection's backward takes."""
    batch, seq_len, _ = do.shape
    st = _resident_statics(seq_len, head_dim, sm_scale, causal)
    s_pad = lse.shape[-1]
    q, k, v, o, do = _pad_operands((q, k, v, o, do.astype(q.dtype)), s_pad)
    heads = st["heads"]
    item = jnp.dtype(q.dtype).itemsize
    block = s_pad * _LANES * item
    width = n_blocks * _LANES
    in_place = fused and 2 * 3 * n_blocks * block <= _FUSED_GRAD_VMEM
    if in_place:
        out_specs = pl.BlockSpec((None, s_pad, 3 * width),
                                 lambda b, g: (b, 0, 0))
        out_shape = jax.ShapeDtypeStruct((batch, s_pad, 3 * width), q.dtype)
        n_bytes = (10 + 3 * heads + 6 * n_blocks) * block
    else:
        out_specs = [_lane_block_spec(s_pad, 0)] * 3
        out_shape = [jax.ShapeDtypeStruct((batch, s_pad, width),
                                          q.dtype)] * 3
        n_bytes = (16 + 3 * heads) * block
    grads = pl.pallas_call(
        functools.partial(_bwd_kernel_resident, **st,
                          fused_blocks=n_blocks if in_place else 0),
        grid=(batch, n_blocks),
        in_specs=[_lane_block_spec(s_pad, f) for f in first_blocks] + [
            _lane_block_spec(s_pad, 0),
            _lane_block_spec(s_pad, 0),
            _lse_block_spec(heads, s_pad),
        ],
        out_specs=out_specs,
        out_shape=out_shape,
        scratch_shapes=[pltpu.VMEM((heads, s_pad, _LANES), q.dtype)] * 3 + [
            pltpu.VMEM((heads, s_pad), jnp.float32)] + [
            pltpu.VMEM((s_pad, _LANES), jnp.float32)] * 3,
        compiler_params=_compiler_params(n_bytes + 3 * s_pad * _LANES * 4,
                                         revisits_output=in_place),
        interpret=interpret,
    )(q, k, v, o, do, lse)
    if fused and not in_place:
        return jnp.concatenate(grads, axis=-1)
    return grads


def _fwd_kernel(q_ref, k_ref, v_ref, o_ref, lse_ref, m_acc, l_acc, acc,
                *, sm_scale: float, causal: bool, kv_actual: int,
                kv_padded: int, q_block_offset: int, window: int = 0):
    """Grid cell (batch*head, q_block, k_block): one K block of the
    online softmax, state carried in VMEM scratch across the
    (sequential, innermost) k dimension.  Streaming K/V through the grid
    keeps VMEM O(block) instead of O(seq) — see the backward kernels.

    ``q_block_offset`` shifts the causal comparison for ring attention,
    where the local q shard's global position differs from its local index.
    ``kv_actual`` is the unpadded key count (keys past it are masked).
    ``window`` (with ``causal``): a query sees the ``window`` newest keys,
    itself included; K blocks wholly behind it are skipped like the ones
    wholly ahead.
    """
    block_q = q_ref.shape[0]
    block_k = k_ref.shape[0]
    q_idx = pl.program_id(1)
    k_idx = pl.program_id(2)
    num_k_blocks = pl.num_programs(2)

    @pl.when(k_idx == 0)
    def _init():
        m_acc[:, :] = jnp.full_like(m_acc, -jnp.inf)
        l_acc[:, :] = jnp.zeros_like(l_acc)
        acc[:, :] = jnp.zeros_like(acc)

    # Causal: K blocks entirely in the future contribute nothing.
    live = True
    if causal:
        live = (k_idx * block_k
                < (q_idx + 1) * block_q + q_block_offset)
        if window:
            live = jnp.logical_and(
                live, (k_idx + 1) * block_k
                > q_idx * block_q + q_block_offset - window + 1)

    @pl.when(live)
    def _accumulate():
        # Native-dtype dots: bf16 operands keep the MXU at full rate (an
        # f32 upcast would halve it); scores and state accumulate in
        # f32, and sm_scale goes on the f32 scores, not on bf16 q.
        q = q_ref[:, :]
        k = k_ref[:, :]
        v = v_ref[:, :]
        s = jnp.dot(q, k.T,
                    preferred_element_type=jnp.float32) * sm_scale
        s = _apply_mask(s, q_start=q_idx * block_q,
                        k_start=k_idx * block_k, kv_actual=kv_actual,
                        kv_padded=kv_padded, causal=causal,
                        q_block_offset=q_block_offset, window=window)
        m_prev = m_acc[:, :]
        m_cur = jnp.max(s, axis=-1, keepdims=True)
        m_new = jnp.maximum(m_prev, m_cur)
        p = jnp.exp(s - m_new)
        alpha = jnp.exp(m_prev - m_new)
        m_acc[:, :] = m_new
        l_acc[:, :] = alpha * l_acc[:, :] + jnp.sum(p, axis=-1,
                                                    keepdims=True)
        acc[:, :] = acc[:, :] * alpha + jnp.dot(
            p.astype(v.dtype), v, preferred_element_type=jnp.float32)

    @pl.when(k_idx == num_k_blocks - 1)
    def _emit():
        m, l = m_acc[:, :], l_acc[:, :]
        # Rows with no visible keys: either no block executed (l == 0) or
        # every entry carried the mask value (m stayed at the mask
        # floor).  Emit zeros with lse = -inf rather than dividing by
        # zero / averaging junk.
        no_valid = jnp.logical_or(l == 0.0, m <= DEFAULT_MASK_VALUE * 0.5)
        l_safe = jnp.where(no_valid, 1.0, l)
        o_ref[:, :] = jnp.where(no_valid, 0.0,
                                acc[:, :] / l_safe).astype(o_ref.dtype)
        lse = jnp.where(no_valid, -jnp.inf, m + jnp.log(l_safe))
        lse_ref[:, :] = lse.astype(jnp.float32)


def _to_rows(x):
    """``[b, h, s, d] -> [b, s, h x d]``, the resident kernels' layout."""
    b, h, s, d = x.shape
    return x.transpose(0, 2, 1, 3).reshape(b, s, h * d)


def _to_heads(x, heads):
    """``[b, s, h x d] -> [b, h, s, d]``."""
    b, s, hd = x.shape
    return x.reshape(b, s, heads, hd // heads).transpose(0, 2, 1, 3)


def _pad_seq(x, multiple):
    """Zero-pad the seq (next-to-last) axis up to a block multiple."""
    s = x.shape[-2]
    pad = (-s) % multiple
    if pad == 0:
        return x
    widths = [(0, 0)] * (x.ndim - 2) + [(0, pad), (0, 0)]
    return jnp.pad(x, widths)


def _flash_forward(q, k, v, sm_scale, causal, block_q, block_k,
                   q_block_offset, interpret, window: int = 0,
                   name: Optional[str] = None):
    """``window`` and fewer key/value heads than query heads (``k``/``v``
    ``[batch, kv_heads, kv_len, head_dim]``: query head ``i`` reads head
    ``i // (heads / kv_heads)``, by the K/V block's index, nothing is
    repeated) take the streaming kernel whatever the length; ``name`` is
    the kernel's own in a device trace."""
    batch, heads, q_len, head_dim = q.shape
    kv_heads, kv_len = k.shape[1], k.shape[2]
    group = heads // kv_heads
    plain = group == 1 and not window
    if interpret is None:
        if _dense_default():
            if not plain:
                raise ValueError("the dense twin of grouped or windowed "
                                 "attention is the caller's")
            return _dense_forward(q, k, v, sm_scale, causal,
                                  q_block_offset)
        interpret = _interpret_default()
    if plain and _resident_ok(heads, head_dim, q_len, kv_len,
                              q_block_offset):
        # Thin wrapper: the resident kernels work on [b, s, h x d].
        n_blocks = heads * head_dim // _LANES
        o, lse = _resident_forward(
            _to_rows(q), _to_rows(k), _to_rows(v), (0, 0, 0), n_blocks,
            head_dim, sm_scale, causal, interpret)
        return (_to_heads(o[:, :q_len], heads),
                lse.reshape(batch, heads, -1)[:, :, :q_len])
    block_q = min(block_q, q_len)
    block_k = min(block_k, kv_len)

    # Pad ragged tails up to block multiples; padded keys are masked in the
    # kernel (kv_actual), padded q rows are sliced away below.
    qr = _pad_seq(q.reshape(batch * heads, q_len, head_dim), block_q)
    kr = _pad_seq(k.reshape(batch * kv_heads, kv_len, head_dim), block_k)
    vr = _pad_seq(v.reshape(batch * kv_heads, kv_len, head_dim), block_k)
    q_pad, kv_pad = qr.shape[1], kr.shape[1]

    out_shape = [
        jax.ShapeDtypeStruct((batch * heads, q_pad, head_dim), q.dtype),
        jax.ShapeDtypeStruct((batch * heads, q_pad, 1), jnp.float32),
    ]
    grid = (batch * heads, q_pad // block_q, kv_pad // block_k)
    kernel = functools.partial(
        _fwd_kernel, sm_scale=sm_scale, causal=causal, kv_actual=kv_len,
        kv_padded=kv_pad, q_block_offset=q_block_offset, window=window)

    def kv_row(b):
        """The K/V row of query row ``b`` (batch-major, then heads)."""
        if group == 1:
            return b
        return (b // heads) * kv_heads + (b % heads) // group

    # Causal: K blocks past the diagonal (and, within a window, the ones
    # wholly behind it) are skipped in the kernel (pl.when); clamping
    # their index map to the nearest live block makes the block index
    # repeat, so Pallas elides the dead cells' DMA too.
    if causal:
        def kv_index(b, i, j):
            hi = ((i + 1) * block_q + q_block_offset - 1) // block_k
            lo = (jnp.maximum(i * block_q + q_block_offset - window + 1, 0)
                  // block_k) if window else 0
            return (kv_row(b), jnp.clip(j, lo, jnp.maximum(hi, 0)), 0)
    else:
        def kv_index(b, i, j):
            return (kv_row(b), j, 0)
    o, lse = pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((None, block_q, head_dim),
                         lambda b, i, j: (b, i, 0)),
            pl.BlockSpec((None, block_k, head_dim), kv_index),
            pl.BlockSpec((None, block_k, head_dim), kv_index),
        ],
        out_specs=[
            pl.BlockSpec((None, block_q, head_dim),
                         lambda b, i, j: (b, i, 0)),
            pl.BlockSpec((None, block_q, 1), lambda b, i, j: (b, i, 0)),
        ],
        out_shape=out_shape,
        scratch_shapes=[
            pltpu.VMEM((block_q, 1), jnp.float32),
            pltpu.VMEM((block_q, 1), jnp.float32),
            pltpu.VMEM((block_q, head_dim), jnp.float32),
        ],
        interpret=interpret,
        **({"name": name} if name else {}),
    )(qr, kr, vr)
    return (o[:, :q_len].reshape(batch, heads, q_len, head_dim),
            lse[:, :q_len].reshape(batch, heads, q_len))


# ---------------------------------------------------------------------------
# Backward kernels
# ---------------------------------------------------------------------------


def _bwd_p_ds(q, k, v, do, lse, delta, *, sm_scale, q_start, k_start,
              kv_actual, kv_padded, causal, q_block_offset):
    """(p, ds) for one (q_block, k_block) tile, shared by the two
    streaming backward kernels (dK/dV and dQ).
    p = exp(s - lse); fully-masked rows have lse = -inf -> p = 0;
    masked entries underflow exp(MASK - lse) -> 0.

    q/k/v/do arrive in their input dtype and feed the MXU directly (f32
    accumulation); p/ds come out f32 and the callers cast them back to
    the operand dtype at their own dot sites."""
    s = jnp.dot(q, k.T, preferred_element_type=jnp.float32) * sm_scale
    s = _apply_mask(s, q_start=q_start, k_start=k_start,
                    kv_actual=kv_actual, kv_padded=kv_padded,
                    causal=causal, q_block_offset=q_block_offset)
    p = jnp.exp(s - jnp.where(jnp.isfinite(lse), lse, 0.0))
    p = jnp.where(jnp.isfinite(lse), p, 0.0)
    dp = jnp.dot(do, v.T, preferred_element_type=jnp.float32)
    ds = p * (dp - delta) * sm_scale
    return p, ds


def _bwd_dkdv_kernel(q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref,
                     dk_ref, dv_ref, dk_acc, dv_acc, *, sm_scale: float,
                     causal: bool, kv_actual: int, kv_padded: int,
                     q_block_offset: int):
    """Grid cell (batch*head, k_block, q_block): one q-block contribution
    to this k-block's dK/dV, accumulated in f32 VMEM scratch across the
    (sequential, innermost) q dimension.

    Streaming q block-by-block through the grid keeps the kernel's VMEM
    working set O(block) — a whole-q operand would scale with sequence
    length and blow the vmem limit around seq 8K (seen in practice)."""
    block_q = q_ref.shape[0]
    block_k = k_ref.shape[0]
    k_idx = pl.program_id(1)
    q_idx = pl.program_id(2)
    num_q_blocks = pl.num_programs(2)

    @pl.when(q_idx == 0)
    def _init():
        dk_acc[:, :] = jnp.zeros_like(dk_acc)
        dv_acc[:, :] = jnp.zeros_like(dv_acc)

    # Causal: q blocks strictly before this k block see none of it.
    live = True
    if causal:
        live = ((q_idx + 1) * block_q + q_block_offset
                > k_idx * block_k)

    @pl.when(live)
    def _accumulate():
        k = k_ref[:, :]
        v = v_ref[:, :]
        q = q_ref[:, :]
        do = do_ref[:, :]
        lse = lse_ref[:, :]
        delta = delta_ref[:, :]
        p, ds = _bwd_p_ds(q, k, v, do, lse, delta, sm_scale=sm_scale,
                          q_start=q_idx * block_q,
                          k_start=k_idx * block_k, kv_actual=kv_actual,
                          kv_padded=kv_padded, causal=causal,
                          q_block_offset=q_block_offset)
        dv_acc[:, :] += jnp.dot(p.astype(do.dtype).T, do,
                                preferred_element_type=jnp.float32)
        dk_acc[:, :] += jnp.dot(ds.astype(q.dtype).T, q,
                                preferred_element_type=jnp.float32)

    @pl.when(q_idx == num_q_blocks - 1)
    def _emit():
        dk_ref[:, :] = dk_acc[:, :].astype(dk_ref.dtype)
        dv_ref[:, :] = dv_acc[:, :].astype(dv_ref.dtype)


def _bwd_dq_kernel(q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref,
                   dq_ref, dq_acc, *, sm_scale: float, causal: bool,
                   kv_actual: int, kv_padded: int, q_block_offset: int):
    """Grid cell (batch*head, q_block, k_block): one k-block contribution
    to this q-block's dQ, accumulated in f32 VMEM scratch across the
    (sequential, innermost) k dimension — same streaming rationale as
    :func:`_bwd_dkdv_kernel`."""
    block_q = q_ref.shape[0]
    block_k = k_ref.shape[0]
    q_idx = pl.program_id(1)
    k_idx = pl.program_id(2)
    num_k_blocks = pl.num_programs(2)

    @pl.when(k_idx == 0)
    def _init():
        dq_acc[:, :] = jnp.zeros_like(dq_acc)

    live = True
    if causal:
        live = (k_idx * block_k
                < (q_idx + 1) * block_q + q_block_offset)

    @pl.when(live)
    def _accumulate():
        q = q_ref[:, :]
        do = do_ref[:, :]
        lse = lse_ref[:, :]
        delta = delta_ref[:, :]
        k = k_ref[:, :]
        v = v_ref[:, :]
        _, ds = _bwd_p_ds(q, k, v, do, lse, delta, sm_scale=sm_scale,
                          q_start=q_idx * block_q,
                          k_start=k_idx * block_k, kv_actual=kv_actual,
                          kv_padded=kv_padded, causal=causal,
                          q_block_offset=q_block_offset)
        dq_acc[:, :] += jnp.dot(ds.astype(k.dtype), k,
                                preferred_element_type=jnp.float32)

    @pl.when(k_idx == num_k_blocks - 1)
    def _emit():
        dq_ref[:, :] = dq_acc[:, :].astype(dq_ref.dtype)


def _flash_backward(res, g, *, sm_scale, causal, block_q, block_k,
                    q_block_offset, interpret):
    if interpret is None:
        if _dense_default():
            return _dense_backward(res, g, sm_scale=sm_scale,
                                   causal=causal,
                                   q_block_offset=q_block_offset)
        interpret = _interpret_default()
    q, k, v, o, lse = res
    batch, heads, q_len, head_dim = q.shape
    kv_len = k.shape[2]
    if _resident_ok(heads, head_dim, q_len, kv_len, q_block_offset):
        n_blocks = heads * head_dim // _LANES
        # Padded q rows: dO is zero there, so any finite lse gives them
        # no part in dK or dV.
        lse = jnp.pad(lse, ((0, 0), (0, 0),
                            (0, _resident_pad(q_len) - q_len)))
        grads = _resident_backward(
            _to_rows(q), _to_rows(k), _to_rows(v), _to_rows(o),
            _to_rows(g), lse.reshape(batch, n_blocks, -1, lse.shape[-1]),
            (0, 0, 0), n_blocks, head_dim, sm_scale, causal, interpret)
        return tuple(_to_heads(x[:, :q_len], heads) for x in grads)
    bq = min(block_q, q_len)
    bk = min(block_k, kv_len)

    do = g.astype(q.dtype)  # native dtype into the kernels' MXU dots
    delta = jnp.sum(g.astype(jnp.float32) * o.astype(jnp.float32),
                    axis=-1)  # [B,H,Sq], f32


    flat = lambda x: x.reshape(batch * heads, x.shape[2], -1)
    # Pad tails to block multiples.  Padded q rows carry lse = -inf so
    # their p (and thus every contribution) is exactly zero; padded keys
    # are masked via kv_actual.
    qr = _pad_seq(flat(q), bq)
    kr = _pad_seq(flat(k), bk)
    vr = _pad_seq(flat(v), bk)
    dor = _pad_seq(flat(do), bq)
    lser = flat(lse[..., None])
    pad_q = qr.shape[1] - q_len
    if pad_q:
        lser = jnp.pad(lser, ((0, 0), (0, pad_q), (0, 0)),
                       constant_values=-jnp.inf)
    deltar = _pad_seq(flat(delta[..., None]), bq)
    q_pad, kv_pad = qr.shape[1], kr.shape[1]

    n_qb = q_pad // bq
    # Causal DMA elision, as in the forward: dkdv's dead cells are q
    # blocks before the diagonal (clamp up); dq's are K blocks past it
    # (clamp down).
    if causal:
        def q_index(b, i, j):
            lo = (i * bk - q_block_offset) // bq
            return (b, jnp.maximum(j, jnp.clip(lo, 0, n_qb - 1)), 0)

        def kv_index(b, i, j):
            hi = ((i + 1) * bq + q_block_offset - 1) // bk
            return (b, jnp.minimum(j, jnp.maximum(hi, 0)), 0)
    else:
        def q_index(b, i, j):
            return (b, j, 0)

        kv_index = q_index

    dkdv = pl.pallas_call(
        functools.partial(_bwd_dkdv_kernel, sm_scale=sm_scale,
                          causal=causal, kv_actual=kv_len,
                          kv_padded=kv_pad,
                          q_block_offset=q_block_offset),
        grid=(batch * heads, kv_pad // bk, n_qb),
        in_specs=[
            pl.BlockSpec((None, bq, head_dim), q_index),
            pl.BlockSpec((None, bk, head_dim), lambda b, i, j: (b, i, 0)),
            pl.BlockSpec((None, bk, head_dim), lambda b, i, j: (b, i, 0)),
            pl.BlockSpec((None, bq, head_dim), q_index),
            pl.BlockSpec((None, bq, 1), q_index),
            pl.BlockSpec((None, bq, 1), q_index),
        ],
        out_specs=[
            pl.BlockSpec((None, bk, head_dim), lambda b, i, j: (b, i, 0)),
            pl.BlockSpec((None, bk, head_dim), lambda b, i, j: (b, i, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((batch * heads, kv_pad, head_dim), k.dtype),
            jax.ShapeDtypeStruct((batch * heads, kv_pad, head_dim), v.dtype),
        ],
        scratch_shapes=[
            pltpu.VMEM((bk, head_dim), jnp.float32),
            pltpu.VMEM((bk, head_dim), jnp.float32),
        ],
        interpret=interpret,
    )(qr, kr, vr, dor, lser, deltar)
    dk, dv = dkdv

    dq = pl.pallas_call(
        functools.partial(_bwd_dq_kernel, sm_scale=sm_scale, causal=causal,
                          kv_actual=kv_len, kv_padded=kv_pad,
                          q_block_offset=q_block_offset),
        grid=(batch * heads, q_pad // bq, kv_pad // bk),
        in_specs=[
            pl.BlockSpec((None, bq, head_dim), lambda b, i, j: (b, i, 0)),
            pl.BlockSpec((None, bk, head_dim), kv_index),
            pl.BlockSpec((None, bk, head_dim), kv_index),
            pl.BlockSpec((None, bq, head_dim), lambda b, i, j: (b, i, 0)),
            pl.BlockSpec((None, bq, 1), lambda b, i, j: (b, i, 0)),
            pl.BlockSpec((None, bq, 1), lambda b, i, j: (b, i, 0)),
        ],
        out_specs=pl.BlockSpec((None, bq, head_dim),
                               lambda b, i, j: (b, i, 0)),
        out_shape=jax.ShapeDtypeStruct((batch * heads, q_pad, head_dim),
                                       q.dtype),
        scratch_shapes=[pltpu.VMEM((bq, head_dim), jnp.float32)],
        interpret=interpret,
    )(qr, kr, vr, dor, lser, deltar)

    rs = lambda x, n: x[:, :n].reshape(batch, heads, n, head_dim)
    return rs(dq, q_len), rs(dk, kv_len), rs(dv, kv_len)


# ---------------------------------------------------------------------------
# Public entry points
# ---------------------------------------------------------------------------

@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5, 6, 7, 8))
def _flash(q, k, v, sm_scale, causal, block_q, block_k, q_block_offset,
           interpret):
    o, _ = _flash_forward(q, k, v, sm_scale, causal, block_q, block_k,
                          q_block_offset, interpret)
    return o


def _flash_fwd(q, k, v, sm_scale, causal, block_q, block_k, q_block_offset,
               interpret):
    o, lse = _flash_forward(q, k, v, sm_scale, causal, block_q, block_k,
                            q_block_offset, interpret)
    return o, (q, k, v, o, lse)


def _flash_bwd(sm_scale, causal, block_q, block_k, q_block_offset,
               interpret, res, g):
    return _flash_backward(res, g, sm_scale=sm_scale, causal=causal,
                           block_q=block_q, block_k=block_k,
                           q_block_offset=q_block_offset,
                           interpret=interpret)


_flash.defvjp(_flash_fwd, _flash_bwd)


def flash_attention(q, k, v, *, causal: bool = False,
                    sm_scale: Optional[float] = None, block_q: int = 128,
                    block_k: int = 128, q_block_offset: int = 0,
                    interpret: Optional[bool] = None):
    """Memory-linear attention, differentiable, Pallas-TPU compiled.

    Args:
      q, k, v: ``[batch, heads, seq, head_dim]`` (q_len may differ from
        kv_len).
      causal: apply a lower-triangular mask; future K blocks are skipped
        entirely (compute proportional to the unmasked area).
      sm_scale: softmax temperature; default ``1/sqrt(head_dim)``.
      q_block_offset: global position of q's first row relative to k's
        first row, for sequence-sharded callers (ring attention).
      interpret: True forces Pallas interpreter mode; None (default)
        compiles the kernel on TPU and uses the dense-jnp fallback on
        other backends (e.g. the CPU test mesh).
    """
    if sm_scale is None:
        sm_scale = q.shape[-1] ** -0.5
    # interpret stays None here so _flash_forward/_flash_backward can pick
    # the dense fallback on non-TPU backends.
    return _flash(q, k, v, float(sm_scale), bool(causal), int(block_q),
                  int(block_k), int(q_block_offset),
                  None if interpret is None else bool(interpret))


def flash_attention_with_lse(q, k, v, *, causal: bool = False,
                             sm_scale: Optional[float] = None,
                             block_q: int = 128, block_k: int = 128,
                             q_block_offset: int = 0,
                             interpret: Optional[bool] = None):
    """Forward-only variant returning ``(out, lse)`` for callers that merge
    partial attention across sequence shards (ring attention's online
    softmax across devices)."""
    if sm_scale is None:
        sm_scale = q.shape[-1] ** -0.5
    return _flash_forward(q, k, v, float(sm_scale), bool(causal),
                          int(block_q), int(block_k), int(q_block_offset),
                          None if interpret is None else bool(interpret))


def gqa_window_attention(q, k, v, *, window: int = 0,
                         sm_scale: Optional[float] = None,
                         block_q: int = 512, block_k: int = 1024,
                         interpret: bool = False):
    """Forward-only CAUSAL self-attention of grouped queries through the
    streaming kernel (``name="gqa_flash_fwd"``): ``q [heads, seq,
    head_dim]``, ``k``/``v`` ``[kv_heads, seq, head_dim]``, query head
    ``i`` on key/value head ``i // (heads / kv_heads)``; with ``window``
    a query sees its ``window`` newest keys, itself included, and the K
    blocks wholly outside are neither fetched nor computed.  What a
    serving model's PROMPT takes (``models/afmoe.py``); it has no
    backward.  The caller chooses where it runs (its twin off the TPU is
    the caller's own).  Blocks of 512 queries by 1024 keys: 8.5 ms for 48
    heads over 8192 tokens inside a window of 4096 on a v5e, against 11.6
    at 512 x 512 and 26.5 at 256 x 256 (PERF.md section 6, PR 41)."""
    if q.shape[0] % k.shape[0]:
        raise ValueError("query heads share key/value heads in whole "
                         "groups")
    if sm_scale is None:
        sm_scale = q.shape[-1] ** -0.5
    o, _ = _flash_forward(q[None], k[None], v[None], float(sm_scale), True,
                          int(block_q), int(block_k), 0, bool(interpret),
                          window=int(window), name="gqa_flash_fwd")
    return o[0]


def _split_qkv(qkv, n_heads):
    """The fused projection as three ``[b, h, s, d]`` tensors."""
    return tuple(_to_heads(x, n_heads) for x in jnp.split(qkv, 3, axis=-1))


@functools.partial(jax.custom_vjp, nondiff_argnums=(1, 2, 3, 4))
def _flash_qkv(qkv, n_heads, sm_scale, causal, interpret):
    return _flash_qkv_fwd(qkv, n_heads, sm_scale, causal, interpret)[0]


def _flash_qkv_fwd(qkv, n_heads, sm_scale, causal, interpret):
    batch, seq_len, width = qkv.shape
    if interpret is None and _dense_default():
        o, lse = _dense_forward(*_split_qkv(qkv, n_heads), sm_scale,
                                causal, 0)
        o = _to_rows(o)
        return o, (qkv, o, lse)
    interpret = _interpret_default() if interpret is None else interpret
    n_blocks = width // 3 // _LANES
    o, lse = _resident_forward(
        qkv, qkv, qkv, (0, n_blocks, 2 * n_blocks), n_blocks,
        width // 3 // n_heads, sm_scale, causal, interpret)
    o = o[:, :seq_len]
    return o, (qkv, o, lse)


def _flash_qkv_bwd(n_heads, sm_scale, causal, interpret, res, g):
    qkv, o, lse = res
    batch, seq_len, width = qkv.shape
    if interpret is None and _dense_default():
        grads = _dense_backward(
            (*_split_qkv(qkv, n_heads), _to_heads(o, n_heads), lse),
            _to_heads(g, n_heads), sm_scale=sm_scale, causal=causal,
            q_block_offset=0)
        return (jnp.concatenate([_to_rows(x) for x in grads], axis=-1),)
    interpret = _interpret_default() if interpret is None else interpret
    n_blocks = width // 3 // _LANES
    dqkv = _resident_backward(
        qkv, qkv, qkv, o, g, lse, (0, n_blocks, 2 * n_blocks), n_blocks,
        width // 3 // n_heads, sm_scale, causal, interpret, fused=True)
    return (dqkv[:, :seq_len],)


_flash_qkv.defvjp(_flash_qkv_fwd, _flash_qkv_bwd)


def flash_attention_qkv(qkv, n_heads: int, *, causal: bool = False,
                        sm_scale: Optional[float] = None,
                        block_q: int = 128, block_k: int = 128,
                        interpret: Optional[bool] = None):
    """Self-attention on the model's own activations: ``qkv`` is the
    fused projection ``[batch, seq, 3 x n_heads x head_dim]`` (q, k and
    v side by side, each heads-major), the result ``[batch, seq,
    n_heads x head_dim]``.  Differentiable.

    Where whole head groups fill 128-lane blocks (head_dim divides 128,
    ``n_heads x head_dim`` is a multiple of 128) and the sequence is
    within the resident limit, the resident kernels read q, k and v
    straight out of ``qkv`` and write ``o`` in place: no transpose, no
    copy.  Any other shape is split into ``[batch, heads, seq,
    head_dim]`` and goes through :func:`flash_attention`, where
    ``block_q``/``block_k`` drive the streaming kernels."""
    batch, seq_len, width = qkv.shape
    head_dim = width // 3 // n_heads
    if sm_scale is None:
        sm_scale = head_dim ** -0.5
    if _resident_ok(n_heads, head_dim, seq_len, seq_len, 0):
        return _flash_qkv(qkv, int(n_heads), float(sm_scale), bool(causal),
                          None if interpret is None else bool(interpret))
    o = flash_attention(*_split_qkv(qkv, n_heads), causal=causal,
                        sm_scale=sm_scale, block_q=block_q,
                        block_k=block_k, interpret=interpret)
    return _to_rows(o)


def mha_reference(q, k, v, *, causal: bool = False,
                  sm_scale: Optional[float] = None,
                  q_block_offset: int = 0):
    """O(seq²) reference attention (tests compare the kernel against it).
    One implementation with :func:`_dense_forward` so the production
    fallback and the test reference cannot diverge."""
    if sm_scale is None:
        sm_scale = q.shape[-1] ** -0.5
    return _dense_forward(q, k, v, sm_scale, causal, q_block_offset)[0]
