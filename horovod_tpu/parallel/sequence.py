"""Sequence/context parallelism: ring attention and Ulysses all-to-all.

Beyond-parity long-context support (the reference has none — SURVEY.md §5
"Long-context / sequence parallelism: absent").  Two standard schemes, both
expressed over a named mesh axis (:data:`..core.topology.SEQ_AXIS`) inside
``shard_map``:

* **Ring attention** (:func:`ring_attention`) — q/k/v arrive sharded along
  the sequence axis; K/V chunks rotate around the ring with
  ``lax.ppermute`` while every device runs the Pallas flash-attention
  kernel on its resident q shard, merging partial results with the online
  log-sum-exp rule.  Peak memory is one sequence shard per device and the
  per-hop transfer overlaps with the chunk compute, so context length
  scales linearly with the ring size.  The backward pass rotates gradient
  accumulators with their chunks (one full ring pass) using the saved
  global LSE — the standard blockwise-parallel formulation.
* **Ulysses** (:func:`ulysses_attention`) — ``all_to_all`` re-shards from
  sequence-parallel to head-parallel, runs dense local flash attention on
  the full sequence for a head subset, and re-shards back.  Cheaper at
  moderate context (two all-to-alls total), but requires
  ``heads % axis_size == 0``.

Causal masking never wastes a full ring step: chunks entirely in the
future are skipped via ``lax.switch`` (only the selected branch executes),
the diagonal chunk runs the causal kernel, past chunks run unmasked.
"""

from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp

from ..core.topology import SEQ_AXIS
from ..ops.flash_attention import (_flash_backward, flash_attention,
                                   flash_attention_with_lse)


def _rot_perm(n: int):
    return [(i, (i + 1) % n) for i in range(n)]


def _merge_partial(o_acc, lse_acc, o_p, lse_p):
    """Online-softmax merge of two partial attentions over the same rows.

    ``o`` accumulates in float32; ``lse`` values of -inf (no visible keys)
    contribute zero weight without producing NaNs.
    """
    lse_new = jnp.logaddexp(lse_acc, lse_p)
    safe = jnp.where(jnp.isneginf(lse_new), 0.0, lse_new)
    w_acc = jnp.where(jnp.isneginf(lse_acc), 0.0, jnp.exp(lse_acc - safe))
    w_p = jnp.where(jnp.isneginf(lse_p), 0.0, jnp.exp(lse_p - safe))
    o_new = o_acc * w_acc[..., None] + o_p * w_p[..., None]
    return o_new, lse_new


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5, 6, 7, 8))
def _ring(q, k, v, axis_name, causal, sm_scale, block_q, block_k,
          interpret):
    o, _ = _ring_fwd_impl(q, k, v, axis_name, causal, sm_scale, block_q,
                          block_k, interpret)
    return o


def _attend_chunk(q, k_c, v_c, src, my, causal, sm_scale, block_q, block_k,
                  interpret):
    """Partial attention of the local q shard against one K/V chunk.

    ``src`` is the traced global index of the chunk currently resident;
    relative to the local shard index ``my`` it selects diagonal (causal
    mask), past (dense), or future (skip) handling.
    """
    kw = dict(sm_scale=sm_scale, block_q=block_q, block_k=block_k,
              interpret=interpret)
    if not causal:
        return flash_attention_with_lse(q, k_c, v_c, causal=False, **kw)

    def diag(_):
        return flash_attention_with_lse(q, k_c, v_c, causal=True, **kw)

    def full(_):
        return flash_attention_with_lse(q, k_c, v_c, causal=False, **kw)

    def skip(_):
        b, h, s, _d = q.shape
        return (jnp.zeros(q.shape, q.dtype),
                jnp.full((b, h, s), -jnp.inf, jnp.float32))

    branch = jnp.where(src == my, 0, jnp.where(src < my, 1, 2))
    return jax.lax.switch(branch, [diag, full, skip], None)


def _ring_fwd_impl(q, k, v, axis_name, causal, sm_scale, block_q, block_k,
                   interpret):
    n = jax.lax.axis_size(axis_name)
    my = jax.lax.axis_index(axis_name)
    perm = _rot_perm(n)

    b, h, s, d = q.shape
    o = jnp.zeros((b, h, s, d), jnp.float32)
    lse = jnp.full((b, h, s), -jnp.inf, jnp.float32)
    k_c, v_c = k, v
    for t in range(n):
        src = (my - t) % n
        o_p, lse_p = _attend_chunk(q, k_c, v_c, src, my, causal, sm_scale,
                                   block_q, block_k, interpret)
        o, lse = _merge_partial(o, lse, o_p.astype(jnp.float32), lse_p)
        if t != n - 1:
            k_c = jax.lax.ppermute(k_c, axis_name, perm)
            v_c = jax.lax.ppermute(v_c, axis_name, perm)
    return o.astype(q.dtype), lse


def _ring_fwd(q, k, v, axis_name, causal, sm_scale, block_q, block_k,
              interpret):
    o, lse = _ring_fwd_impl(q, k, v, axis_name, causal, sm_scale, block_q,
                            block_k, interpret)
    return o, (q, k, v, o, lse)


def _chunk_grads(q, k_c, v_c, o, lse, g, src, my, causal, sm_scale,
                 block_q, block_k, interpret):
    """(dq_partial, dk_chunk, dv_chunk) for one resident chunk.

    Uses the *global* LSE and final output, under which every chunk's
    softmax probabilities are exact — partial gradients then sum to the
    true gradient without any per-chunk renormalization.
    """
    def run(causal_flag):
        return _flash_backward((q, k_c, v_c, o, lse), g, sm_scale=sm_scale,
                               causal=causal_flag, block_q=block_q,
                               block_k=block_k, q_block_offset=0,
                               interpret=interpret)

    if not causal:
        return run(False)

    def diag(_):
        return run(True)

    def full(_):
        return run(False)

    def skip(_):
        return (jnp.zeros_like(q), jnp.zeros_like(k_c),
                jnp.zeros_like(v_c))

    branch = jnp.where(src == my, 0, jnp.where(src < my, 1, 2))
    return jax.lax.switch(branch, [diag, full, skip], None)


def _ring_bwd(axis_name, causal, sm_scale, block_q, block_k, interpret,
              res, g):
    q, k, v, o, lse = res
    n = jax.lax.axis_size(axis_name)
    my = jax.lax.axis_index(axis_name)
    perm = _rot_perm(n)

    dq = jnp.zeros(q.shape, jnp.float32)
    k_c, v_c = k, v
    dk_c = jnp.zeros(k.shape, jnp.float32)
    dv_c = jnp.zeros(v.shape, jnp.float32)
    for t in range(n):
        src = (my - t) % n
        dq_p, dk_p, dv_p = _chunk_grads(q, k_c, v_c, o, lse, g, src, my,
                                        causal, sm_scale, block_q, block_k,
                                        interpret)
        dq = dq + dq_p.astype(jnp.float32)
        dk_c = dk_c + dk_p.astype(jnp.float32)
        dv_c = dv_c + dv_p.astype(jnp.float32)
        # Gradient accumulators travel with their chunk; after the final
        # rotation each chunk's dK/dV lands back on its home device.
        k_c = jax.lax.ppermute(k_c, axis_name, perm)
        v_c = jax.lax.ppermute(v_c, axis_name, perm)
        dk_c = jax.lax.ppermute(dk_c, axis_name, perm)
        dv_c = jax.lax.ppermute(dv_c, axis_name, perm)
    return (dq.astype(q.dtype), dk_c.astype(k.dtype),
            dv_c.astype(v.dtype))


_ring.defvjp(_ring_fwd, _ring_bwd)


def ring_attention(q, k, v, *, axis_name: str = SEQ_AXIS,
                   causal: bool = False, sm_scale: Optional[float] = None,
                   block_q: int = 128, block_k: int = 128,
                   interpret: Optional[bool] = None):
    """Sequence-parallel attention over a ring of devices.

    Call inside ``shard_map`` with ``q, k, v : [batch, heads, seq_local,
    head_dim]`` sharded along ``axis_name``; sequence position is shard
    -major (shard i holds rows ``[i*seq_local, (i+1)*seq_local)``).
    Differentiable; numerically matches dense attention over the gathered
    sequence.
    """
    if sm_scale is None:
        sm_scale = q.shape[-1] ** -0.5
    return _ring(q, k, v, axis_name, bool(causal), float(sm_scale),
                 int(block_q), int(block_k), interpret)


def ulysses_attention(q, k, v, *, axis_name: str = SEQ_AXIS,
                      causal: bool = False,
                      sm_scale: Optional[float] = None,
                      block_q: int = 128, block_k: int = 128,
                      interpret: Optional[bool] = None):
    """All-to-all sequence parallelism (DeepSpeed-Ulysses scheme).

    Re-shards seq-parallel q/k/v to head-parallel over ``axis_name`` (one
    ``all_to_all``), runs local flash attention on the full sequence for
    ``heads / axis_size`` heads, and re-shards back.  Differentiable
    through the native transpose of ``all_to_all``.  Requires the head
    count to divide evenly.
    """
    n = jax.lax.axis_size(axis_name)
    h = q.shape[1]
    if h % n != 0:
        raise ValueError(f"ulysses_attention needs heads ({h}) divisible "
                         f"by the '{axis_name}' axis size ({n})")

    def to_heads(x):  # [B, H, S/n, D] -> [B, H/n, S, D]
        return jax.lax.all_to_all(x, axis_name, split_axis=1,
                                  concat_axis=2, tiled=True)

    def to_seq(x):  # [B, H/n, S, D] -> [B, H, S/n, D]
        return jax.lax.all_to_all(x, axis_name, split_axis=2,
                                  concat_axis=1, tiled=True)

    o = flash_attention(to_heads(q), to_heads(k), to_heads(v),
                        causal=causal, sm_scale=sm_scale, block_q=block_q,
                        block_k=block_k, interpret=interpret)
    return to_seq(o)
