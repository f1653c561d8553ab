"""Latent-attention mixture-of-experts decoder, for serving.

The DeepSeek-V3 family's block as ``skt/A.X-K1`` configures it
(docs/inference.md "Latent-attention mixture of experts"): pre-norm
residual blocks with RMSNorm, multi-head LATENT attention (queries and
keys/values go through low-rank bottlenecks; what a token leaves in the
cache is ONE ``kv_lora_rank + qk_rope_head_dim`` wide entry a layer, the
normed latent beside the rotated shared key), rotary positions with YaRN
on the rope part of a head only, ``first_k_dense_replace`` leading dense
SwiGLU layers and then layers of one shared and ``n_routed_experts``
routed SwiGLU experts, ``num_experts_per_tok`` a token by sigmoid score.

A member of an expert-parallel group holds ``experts_held`` of the routed
experts (``expert_offset`` on) and computes their part of each layer
(:func:`..parallel.expert.moe_layer_held`); attention, router, shared
expert and dense layers are whole on every member.  Nothing here stands
in for the absent members.

Two attention forms, which must agree: prefill REBUILDS keys and values
from the latent (``[k_nope | v] = c W_ukv``) and attends block by block;
decode ABSORBS ``W_uk`` into the query and ``W_uv`` into the output and
attends over the cached entries themselves, one 576-wide "head" shared
by all query heads.

:class:`LatentMoEServing` is what ``serving.InferenceEngine`` asks for
the cache entry, the paged decode step (:func:`decode_attend`: on the TPU
a kernel that walks the page table, ``ops/latent_paged_attention.py``;
elsewhere its twin, :func:`gathered_attend`, every slot's table row
gathered whole and attended under the lengths), the prefill step and the
fingerprint (serving/models.py has the protocol).
"""

from __future__ import annotations

import math
from dataclasses import dataclass
from functools import partial

import jax
import jax.numpy as jnp
import numpy as np
from jax.profiler import TraceAnnotation

from .. import telemetry as _telemetry
from ..ops import latent_paged_attention as _paged
from ..ops import sparse_latent_attention as _sparse
from ..parallel.expert import (moe_layer_held,
                               route_sigmoid_bias_group_top_k, swiglu)

_M_MOE_ASSIGN = _telemetry.counter(
    "serving.moe_assignments", "token-expert pairs the decode iterations "
    "computed on held experts, summed over the expert layers")
_M_MOE_LOAD_MAX = _telemetry.counter(
    "serving.moe_expert_load_max", "the fullest held expert's pairs, "
    "summed over decode iterations and expert layers (over "
    "serving.moe_assignments / experts held it is max over mean)")
_M_MOE_TOUCHED = _telemetry.counter(
    "serving.moe_experts_touched", "held experts with at least one "
    "token, summed over decode iterations and expert layers")
_M_DSA_SCORED = _telemetry.counter(
    "serving.dsa_scored_tokens", "cached positions the indexer scored for "
    "the decode iterations' queries, summed over live slots and layers")
_M_DSA_SELECTED = _telemetry.counter(
    "serving.dsa_selected_tokens", "positions the decode iterations' "
    "queries attended after the indexer's selection, summed over live "
    "slots and layers")
_M_DSA_SCORED_TRACED = _telemetry.counter(
    "serving.dsa_scored_tokens_traced", "serving.dsa_scored_tokens of the "
    "iterations retired WHILE a profiler session recorded: what a device "
    "trace's kernel seconds are held against (a trace of a few seconds "
    "between prompt passes of one to three is no fixed share of a window)")
_M_ENTRY_BYTES = _telemetry.gauge(
    "serving.cache_entry_bytes", "bytes one token leaves in the paged "
    "store in one layer (all of the model's stores)")


# A cached entry is padded with zeros to a multiple of this many values:
# the TPU tiles the minor dimension by 128 lanes, and a store whose rows
# are 576 wide it lays out PAGES-minor (less padding), then copies all of
# it into row order around every gather.
CACHE_LANE = 128
# Queries of one block of the prefill's attention.
PREFILL_Q_BLOCK = 256
# With an indexer (prompts of many thousand tokens in one program): query
# blocks whose keys end inside the same stretch of this many run as ONE
# mapped body against the keys up to the stretch's end; attention heads
# and indexer heads go through in groups; and the per-token matmuls of a
# dense layer in blocks of tokens.  Memory only.
PREFILL_KEY_CHUNK = 2048
PREFILL_HEAD_GROUP = 8
INDEX_HEAD_GROUP = 8
FFN_TOKEN_BLOCK = 2048
# Rows the head is applied to for a prompt's ONE wanted row (lm_head).
HEAD_ROWS = 8
# The indexer's key norm is a LayerNorm with this epsilon.
INDEX_NORM_EPS = 1e-6
# ``ops/latent_paged_attention.py``'s ``interpret``: None is the rule (the
# kernel on the TPU, the gathered rows elsewhere); a test sets True to run
# the kernel in the interpreter through the model.
PAGED_INTERPRET = None


@dataclass(frozen=True)
class LatentMoEConfig:
    """The published keys under their published names and, beside them,
    the share held here.  ``vocab_size`` and ``num_hidden_layers`` are
    what is RUN (the rows of the vocabulary held, the layers kept);
    ``n_routed_experts`` stays the router's width."""
    vocab_size: int = 163840
    hidden_size: int = 7168
    intermediate_size: int = 18432
    moe_intermediate_size: int = 2048
    num_hidden_layers: int = 61
    first_k_dense_replace: int = 1
    num_attention_heads: int = 64
    q_lora_rank: int = 1536
    kv_lora_rank: int = 512
    qk_nope_head_dim: int = 128
    qk_rope_head_dim: int = 64
    v_head_dim: int = 128
    n_routed_experts: int = 192
    n_shared_experts: int = 1
    num_experts_per_tok: int = 8
    routed_scaling_factor: float = 2.5
    norm_topk_prob: bool = True
    rms_norm_eps: float = 1e-6
    rope_theta: float = 10000.0
    rope_factor: float = 32.0
    beta_fast: float = 32.0
    beta_slow: float = 1.0
    mscale: float = 1.0
    mscale_all_dim: float = 1.0
    original_max_position_embeddings: int = 4096
    max_position_embeddings: int = 131072
    # Queries and latent rescaled after their norms by sqrt(hidden /
    # rank) (published by the configs that do it; this family's do not).
    mla_scale_q_lora: bool = False
    mla_scale_kv_lora: bool = False
    dtype: object = jnp.bfloat16
    # The lightning indexer (0 heads: none): ``index_topk`` cached tokens
    # a query, chosen by ``index_n_heads`` heads of ``index_head_dim``.
    index_n_heads: int = 0
    index_head_dim: int = 0
    index_topk: int = 0
    # Group-limited expert choice by biased scores ("noaux_tc"): the
    # ``topk_group`` best of ``n_group`` groups, then the experts among
    # them.  Off: plain top-k over all sigmoid scores.
    group_limited: bool = False
    n_group: int = 1
    topk_group: int = 1
    # The share held here.
    experts_held: int = 192
    expert_offset: int = 0

    @property
    def max_seq_len(self) -> int:
        return self.max_position_embeddings

    @property
    def n_moe_layers(self) -> int:
        return self.num_hidden_layers - self.first_k_dense_replace

    @property
    def cache_layers(self) -> int:
        """Layers of the paged store: one latent attention a layer."""
        return self.num_hidden_layers

    @property
    def entry_width(self) -> int:
        """Values a token leaves in the cache in one layer: the latent,
        the rotated key, and zeros up to the lane multiple."""
        w = self.kv_lora_rank + self.qk_rope_head_dim
        return -(-w // CACHE_LANE) * CACHE_LANE

    @property
    def indexed(self) -> bool:
        return self.index_n_heads > 0

    @property
    def entry_widths(self) -> tuple:
        """The minor width of each store: the latent entry and, with an
        indexer, its key beside it on the same page table."""
        return (self.entry_width,) + ((self.index_head_dim,)
                                      if self.indexed else ())

    def serving_model(self) -> "LatentMoEServing":
        return LatentMoEServing(self)


# -- rotary positions with YaRN ----------------------------------------------

def yarn_mscale(factor: float, mscale: float) -> float:
    return 1.0 if factor <= 1 else 0.1 * mscale * math.log(factor) + 1.0


def yarn_inv_freq(cfg: LatentMoEConfig) -> np.ndarray:
    """Inverse frequencies ``[qk_rope_head_dim / 2]`` as the DeepSeek
    family computes them: extrapolation (the plain ``theta^(-2i/d)``)
    blended into interpolation (that over ``factor``) by the linear ramp
    between the dims at which ``beta_fast`` and ``beta_slow`` rotations
    fit into the original context."""
    dim, base = cfg.qk_rope_head_dim, cfg.rope_theta
    extra = 1.0 / base ** (np.arange(0, dim, 2, dtype=np.float64) / dim)
    if cfg.rope_factor <= 1:
        return extra.astype(np.float32)

    def correction_dim(rotations):
        return (dim * math.log(cfg.original_max_position_embeddings
                               / (rotations * 2 * math.pi))
                / (2 * math.log(base)))

    low = max(math.floor(correction_dim(cfg.beta_fast)), 0)
    high = min(math.ceil(correction_dim(cfg.beta_slow)), dim - 1)
    if low == high:
        high += 0.001
    ramp = np.clip((np.arange(dim // 2, dtype=np.float64) - low)
                   / (high - low), 0.0, 1.0)
    return (extra / cfg.rope_factor * ramp
            + extra * (1.0 - ramp)).astype(np.float32)


def softmax_scale(cfg: LatentMoEConfig) -> float:
    """``(nope + rope)^-0.5 * m^2``, ``m`` YaRN's ``mscale_all_dim``
    correction; cos and sin carry ``mscale / mscale_all_dim``."""
    m = yarn_mscale(cfg.rope_factor, cfg.mscale_all_dim)
    return (cfg.qk_nope_head_dim + cfg.qk_rope_head_dim) ** -0.5 * m * m


def rope(x, pos, cfg: LatentMoEConfig):
    """Rotate ``x [..., s, (heads,) rope_dim]`` at positions ``pos
    [..., s]``; pairs are (i, i + rope_dim/2), the "rotate half" layout."""
    ang = (jnp.clip(pos, 0, None).astype(jnp.float32)[..., None]
           * jnp.asarray(yarn_inv_freq(cfg)))
    m = (yarn_mscale(cfg.rope_factor, cfg.mscale)
         / yarn_mscale(cfg.rope_factor, cfg.mscale_all_dim))
    cos, sin = jnp.cos(ang) * m, jnp.sin(ang) * m
    if x.ndim == pos.ndim + 2:          # a heads axis before rope_dim
        cos, sin = cos[..., None, :], sin[..., None, :]
    x1, x2 = jnp.split(x.astype(jnp.float32), 2, axis=-1)
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin],
                           axis=-1).astype(x.dtype)


def rmsnorm(x, w, eps: float, dtype, scale=None):
    """Computed in float32 whatever comes in, handed on as ``dtype``;
    ``scale`` multiplies the normed value before it is rounded."""
    xf = x.astype(jnp.float32)
    y = xf * jax.lax.rsqrt(jnp.mean(xf * xf, axis=-1, keepdims=True) + eps)
    y = y * w.astype(jnp.float32)
    return (y if scale is None else y * scale).astype(dtype)


def lora_scales(cfg) -> tuple:
    """``(queries, latent)``: ``sqrt(hidden / rank)`` where the config
    says the bottleneck's output is rescaled after its norm, else
    ``None``."""
    return ((cfg.hidden_size / cfg.q_lora_rank) ** 0.5
            if cfg.mla_scale_q_lora else None,
            (cfg.hidden_size / cfg.kv_lora_rank) ** 0.5
            if cfg.mla_scale_kv_lora else None)


# -- parameters ---------------------------------------------------------------

def init_latent_moe(key, cfg: LatentMoEConfig) -> dict:
    """Parameter pytree: ``layers`` is a LIST, one dict a layer, every
    weight a leaf of its own.  (Stacked on a leading axis and walked by
    ``lax.scan``, a layer's 1.3 GB of weights are first copied out of
    the stack, in every decode iteration: three times the traffic of a
    program whose whole cost is reading them once.)  Normal init,
    residual projections scaled by depth, router rows such that ``h
    W_r`` spreads the sigmoid scores."""
    d, f, fm = (cfg.hidden_size, cfg.intermediate_size,
                cfg.moe_intermediate_size)
    h_n, rq, rkv = (cfg.num_attention_heads, cfg.q_lora_rank,
                    cfg.kv_lora_rank)
    nope, rp, vd = (cfg.qk_nope_head_dim, cfg.qk_rope_head_dim,
                    cfg.v_head_dim)
    e, v, dt = cfg.experts_held, cfg.vocab_size, cfg.dtype
    std, res = 0.02, 0.02 / (2 * cfg.num_hidden_layers) ** 0.5
    per_layer = 24 if cfg.indexed or cfg.group_limited else 16
    keys = iter(jax.random.split(key, 4 + per_layer * cfg.num_hidden_layers))

    def w(shape, scale):
        return (jax.random.normal(next(keys), shape, jnp.float32)
                * scale).astype(dt)

    def attn():
        ap = {"norm": jnp.ones((d,), dt),
              "w_dq": w((d, rq), std), "q_norm": jnp.ones((rq,), dt),
              "w_uq": w((rq, h_n * (nope + rp)), std),
              "w_dkv": w((d, rkv + rp), std),
              "kv_norm": jnp.ones((rkv,), dt),
              "w_ukv": w((rkv, h_n * (nope + vd)), std),
              "w_o": w((h_n * vd, d), res)}
        if cfg.indexed:
            ih, idim = cfg.index_n_heads, cfg.index_head_dim
            ap.update(w_qi=w((rq, ih * idim), std), w_ki=w((d, idim), std),
                      ki_norm=jnp.ones((idim,), dt),
                      ki_bias=w((idim,), std), w_w=w((d, ih), std))
        return ap

    def ffn(width):
        return {"w_gate": w((d, width), std), "w_up": w((d, width), std),
                "w_down": w((width, d), res)}

    def layer(i):
        if i < cfg.first_k_dense_replace:
            return {"attn": attn(), "ffn_norm": jnp.ones((d,), dt),
                    "ffn": ffn(f)}
        lp = {"attn": attn(), "ffn_norm": jnp.ones((d,), dt),
              "router": w((d, cfg.n_routed_experts), 1.5 / d ** 0.5),
              "shared": ffn(fm * cfg.n_shared_experts),
              "w_gate": w((e, d, fm), std), "w_up": w((e, d, fm), std),
              "w_down": w((e, fm, d), res)}
        if cfg.group_limited:
            # The choice's bias stays float32 (it is added to float32
            # scores and never multiplied).
            lp["router_bias"] = jax.random.normal(
                next(keys), (cfg.n_routed_experts,), jnp.float32) * 0.05
        return lp

    return {
        "embed": w((v, d), std),
        "layers": [layer(i) for i in range(cfg.num_hidden_layers)],
        "norm_f": jnp.ones((d,), dt),
        "unembed": w((d, v), std),
    }


# -- latent attention ----------------------------------------------------------

def mla_project(h, ap, cfg: LatentMoEConfig, pos):
    """Queries and the cache entry of a block.  ``h [b, s, d]`` (normed),
    ``pos [b, s]``.  Returns ``q_nope [b, s, heads, nope]``, ``q_rope
    [b, s, heads, rope]`` (rotated) and ``entry [b, s, entry_width]``:
    the normed latent beside the rotated key all heads share, then the
    zeros that fill the last lane row."""
    b, s, _ = h.shape
    h_n, nope, rp = (cfg.num_attention_heads, cfg.qk_nope_head_dim,
                     cfg.qk_rope_head_dim)
    dt = h.dtype
    q_scale, kv_scale = lora_scales(cfg)
    # The bottlenecks' outputs reach their norms and the rotation in
    # float32; only matmul operands and the cache entry are rounded.
    c_q = rmsnorm(jnp.dot(h, ap["w_dq"],
                          preferred_element_type=jnp.float32),
                  ap["q_norm"], cfg.rms_norm_eps, dt)
    q = jnp.dot(c_q, ap["w_uq"], preferred_element_type=jnp.float32)
    if q_scale is not None:
        q = q * q_scale
    q = q.reshape(b, s, h_n, nope + rp)
    ckv = jnp.dot(h, ap["w_dkv"], preferred_element_type=jnp.float32)
    c = rmsnorm(ckv[..., :cfg.kv_lora_rank], ap["kv_norm"],
                cfg.rms_norm_eps, dt, kv_scale)
    k_rope = rope(ckv[..., cfg.kv_lora_rank:], pos, cfg).astype(dt)
    fill = jnp.zeros((b, s, cfg.entry_width - cfg.kv_lora_rank - rp), dt)
    return (q[..., :nope].astype(dt),
            rope(q[..., nope:], pos, cfg).astype(dt),
            jnp.concatenate([c, k_rope, fill], axis=-1))


def mla_latents(h, ap, cfg: LatentMoEConfig, pos):
    """:func:`mla_project` in two steps, for a caller that needs what
    lies between them (the indexer's queries start from the normed query
    latent; a long prompt takes its heads in groups).  This one: ``c_q
    [b, s, q_lora_rank]`` and the cache ``entry [b, s, entry_width]``."""
    b, s, _ = h.shape
    rp = cfg.qk_rope_head_dim
    dt = h.dtype
    _, kv_scale = lora_scales(cfg)
    # The bottlenecks' outputs reach their norms and the rotation in
    # float32; only matmul operands and the cache entry are rounded.
    c_q = rmsnorm(jnp.dot(h, ap["w_dq"],
                          preferred_element_type=jnp.float32),
                  ap["q_norm"], cfg.rms_norm_eps, dt)
    ckv = jnp.dot(h, ap["w_dkv"], preferred_element_type=jnp.float32)
    c = rmsnorm(ckv[..., :cfg.kv_lora_rank], ap["kv_norm"],
                cfg.rms_norm_eps, dt, kv_scale)
    k_rope = rope(ckv[..., cfg.kv_lora_rank:], pos, cfg).astype(dt)
    fill = jnp.zeros((b, s, cfg.entry_width - cfg.kv_lora_rank - rp), dt)
    return c_q, jnp.concatenate([c, k_rope, fill], axis=-1)


def mla_queries(c_q, w_uq, cfg: LatentMoEConfig, pos):
    """``(q_nope, q_rope)`` of the heads whose columns ``w_uq [q_lora_rank,
    heads * (nope + rope)]`` holds (all of them, or a group's), as
    :func:`mla_project` makes them."""
    b, s, _ = c_q.shape
    nope = cfg.qk_nope_head_dim
    dt = c_q.dtype
    q_scale, _ = lora_scales(cfg)
    q = jnp.dot(c_q, w_uq, preferred_element_type=jnp.float32)
    if q_scale is not None:
        q = q * q_scale
    q = q.reshape(b, s, -1, nope + cfg.qk_rope_head_dim)
    return (q[..., :nope].astype(dt),
            rope(q[..., nope:], pos, cfg).astype(dt))


def _masked_softmax(scores, mask):
    """float32 softmax over the last axis; rows with an empty mask (idle
    slots) come out all-zero, not NaN."""
    scores = jnp.where(mask, scores, -jnp.inf)
    m = jnp.max(scores, axis=-1, keepdims=True)
    p = jnp.exp(scores - jnp.where(jnp.isfinite(m), m, 0.0))
    denom = jnp.sum(p, axis=-1, keepdims=True)
    return p / jnp.where(denom == 0.0, 1.0, denom)


def _w_ukv(ap, cfg: LatentMoEConfig):
    w = ap["w_ukv"].reshape(cfg.kv_lora_rank, cfg.num_attention_heads,
                            cfg.qk_nope_head_dim + cfg.v_head_dim)
    return w[..., :cfg.qk_nope_head_dim], w[..., cfg.qk_nope_head_dim:]


def mla_rebuilt_attention(q_nope, q_rope, entry, ap, cfg: LatentMoEConfig):
    """Causal attention of a block over ITSELF with keys and values
    rebuilt from the latent, ``PREFILL_Q_BLOCK`` queries at a time
    against the keys up to their block's end, so the scores never exceed
    ``[heads, q_block, block]``.  Returns ``[b, s, heads * v_dim]``."""
    b, s, h_n, _ = q_nope.shape
    dt = q_nope.dtype
    c = entry[..., :cfg.kv_lora_rank]
    k_rope = entry[..., cfg.kv_lora_rank:
                   cfg.kv_lora_rank + cfg.qk_rope_head_dim]
    kv = jnp.dot(c, ap["w_ukv"], preferred_element_type=jnp.float32
                 ).astype(dt).reshape(b, s, h_n, -1)
    k = jnp.concatenate(
        [kv[..., :cfg.qk_nope_head_dim],
         jnp.broadcast_to(k_rope[:, :, None, :],
                          (b, s, h_n, cfg.qk_rope_head_dim))], axis=-1)
    v = kv[..., cfg.qk_nope_head_dim:]
    q = jnp.concatenate([q_nope, q_rope], axis=-1)
    scale = softmax_scale(cfg)
    qb = min(PREFILL_Q_BLOCK, s)
    outs = []
    for lo in range(0, s, qb):
        hi = min(lo + qb, s)
        scores = jnp.einsum("bqhd,bkhd->bhqk", q[:, lo:hi], k[:, :hi],
                            preferred_element_type=jnp.float32) * scale
        mask = (jnp.arange(hi)[None, :] <= jnp.arange(lo, hi)[:, None])
        p = _masked_softmax(scores, mask[None, None])
        outs.append(jnp.einsum("bhqk,bkhd->bqhd", p.astype(dt), v[:, :hi],
                               preferred_element_type=jnp.float32))
    return jnp.concatenate(outs, axis=1).astype(dt).reshape(b, s, -1)


def _absorbed_query(q_nope, q_rope, width: int, ap, cfg: LatentMoEConfig):
    """``W_uk`` absorbed into the query (a head's ``nope -> kv_rank``),
    the rotated part beside it, zeros up to an entry's ``width``: ``[b,
    s, heads, width]``, so the scores contract whole rows."""
    b, s, h_n, _ = q_nope.shape
    dt = q_nope.dtype
    w_uk, _ = _w_ukv(ap, cfg)
    q_lat = jnp.einsum("bshd,chd->bshc", q_nope, w_uk,
                       preferred_element_type=jnp.float32).astype(dt)
    fill = jnp.zeros((b, s, h_n, width - q_lat.shape[-1]
                      - q_rope.shape[-1]), dt)
    return jnp.concatenate([q_lat, q_rope, fill], axis=-1)


def _absorbed_output(o_lat, ap, cfg: LatentMoEConfig):
    """``W_uv`` applied to the attended latent ``[b, s, heads, kv_rank]``:
    ``[b, s, heads * v_dim]``."""
    _, w_uv = _w_ukv(ap, cfg)
    b, s = o_lat.shape[:2]
    return jnp.einsum("bshc,chv->bshv", o_lat, w_uv,
                      preferred_element_type=jnp.float32
                      ).astype(o_lat.dtype).reshape(b, s, -1)


def mla_absorbed_attention(q_nope, q_rope, view, q_pos, ap,
                           cfg: LatentMoEConfig, allowed=None):
    """Attention over cached entries themselves: ``W_uk`` absorbed into
    the query, ``W_uv`` into the output.  ``view [b, n, entry_width]``
    holds position ``j`` at row ``j``; row ``j`` takes part in query ``(b,
    i)`` iff ``j <= q_pos[b, i]`` (and ``allowed[b, i, j]``, an indexer's
    selection, where given).  The query is padded like the entry,
    so the scores contract whole rows (the zeros add nothing).  Returns
    ``[b, s, heads * v_dim]``."""
    dt = q_nope.dtype
    q = _absorbed_query(q_nope, q_rope, view.shape[-1], ap, cfg)
    scores = jnp.einsum("bshc,bnc->bhsn", q, view,
                        preferred_element_type=jnp.float32
                        ) * softmax_scale(cfg)
    mask = (jnp.arange(view.shape[1], dtype=jnp.int32)[None, None, None, :]
            <= q_pos[:, None, :, None])
    if allowed is not None:
        mask = mask & allowed[:, None]
    p = _masked_softmax(scores, mask)
    o_lat = jnp.einsum("bhsn,bnc->bshc", p.astype(dt),
                       view[..., :cfg.kv_lora_rank],
                       preferred_element_type=jnp.float32).astype(dt)
    return _absorbed_output(o_lat, ap, cfg)


# -- the lightning indexer and its selection ------------------------------------
# (docs/inference.md "Learned sparse attention over the latent store".)

def _rotate_head(x, pos, cfg: LatentMoEConfig):
    """An indexer head's first ``qk_rope_head_dim`` dims rotated."""
    rp = cfg.qk_rope_head_dim
    return jnp.concatenate([rope(x[..., :rp], pos, cfg), x[..., rp:]],
                           axis=-1)


def index_queries(c_q, ap, cfg: LatentMoEConfig, pos):
    """The indexer's queries ``[b, s, index heads, index dim]`` from the
    normed query latent ``c_q [b, s, q_lora_rank]``, rotated.  The source
    multiplies queries and keys by an orthonormal Hadamard matrix before
    it quantises them to 8 bits; nothing is quantised here and the
    products are the same without it, so it is left out."""
    b, s, _ = c_q.shape
    q = jnp.dot(c_q, ap["w_qi"], preferred_element_type=jnp.float32)
    q = q.reshape(b, s, cfg.index_n_heads, cfg.index_head_dim)
    return _rotate_head(q, pos, cfg).astype(c_q.dtype)


def index_keys(h, ap, cfg: LatentMoEConfig, pos):
    """What the indexer keeps of a token and how it weighs its heads for
    one: ONE key ``[b, s, index dim]`` (LayerNorm with weight and bias,
    rotated; cached beside the latent entry) and the heads' weights ``[b,
    s, index heads]`` float32 with both ``^-0.5`` factors in them."""
    k = jnp.dot(h, ap["w_ki"], preferred_element_type=jnp.float32)
    mu = jnp.mean(k, axis=-1, keepdims=True)
    var = jnp.mean(jnp.square(k - mu), axis=-1, keepdims=True)
    k = ((k - mu) * jax.lax.rsqrt(var + INDEX_NORM_EPS)
         * ap["ki_norm"].astype(jnp.float32)
         + ap["ki_bias"].astype(jnp.float32))
    w = jnp.dot(h, ap["w_w"], preferred_element_type=jnp.float32)
    return (_rotate_head(k, pos, cfg).astype(h.dtype),
            w * (cfg.index_n_heads ** -0.5 * cfg.index_head_dim ** -0.5))


def index_project(h, c_q, ap, cfg: LatentMoEConfig, pos):
    """``(queries, key, weights)``: :func:`index_queries` and
    :func:`index_keys` of a block."""
    return (index_queries(c_q, ap, cfg, pos),
            *index_keys(h, ap, cfg, pos))


def index_scores(q, k, w):
    """``I [n, m] = sum_j w[n, j] relu(q[n, j] . k[m])`` float32, of
    queries ``q [n, heads, dim]``, ``w [n, heads]`` against keys ``k [m,
    dim]``; the heads in groups of ``INDEX_HEAD_GROUP``, so the per-head
    scores alive are ``[group, n, m]``."""
    g = min(INDEX_HEAD_GROUP, q.shape[1])
    total = jnp.zeros((q.shape[0], k.shape[0]), jnp.float32)
    for lo in range(0, q.shape[1], g):
        part = jnp.einsum("nhd,md->hnm", q[:, lo:lo + g], k,
                          preferred_element_type=jnp.float32)
        total = total + jnp.einsum("hnm,nh->nm", jax.nn.relu(part),
                                   w[:, lo:lo + g])
    return total


def _q_block(s: int) -> int:
    return PREFILL_Q_BLOCK if s % PREFILL_Q_BLOCK == 0 else s


def _stretch_len(s: int) -> int:
    """Positions in one stretch of a sequence of ``s``: ``PREFILL_KEY_CHUNK``
    in whole query blocks."""
    qb = _q_block(s)
    return max(qb, PREFILL_KEY_CHUNK // qb * qb)


def _stretches(s: int):
    """``(lo, hi)`` of the stretches a sequence of ``s`` is cut into."""
    step = _stretch_len(s)
    return [(lo, min(lo + step, s)) for lo in range(0, s, step)]


def _stretch_selection(c_q, w_i, k_i, ap, cfg: LatentMoEConfig, pos, lo: int):
    """What the indexer selects for the queries of ONE stretch, rows ``[lo,
    lo + n)`` of a sequence (``c_q [n, q_lora_rank]``, ``w_i [n, index
    heads]``, ``pos [n]``), among the keys ``k_i [hi, index dim]`` up to
    the stretch's end: ``[n, hi]`` bool, or ``None`` where ``hi <=
    index_topk`` (every position is selected).  A block of queries at a
    time, made from ``c_q`` there and then: the per-row threshold of the
    ``index_topk``-th largest, never all heads' scores at once."""
    n, hi, top = c_q.shape[0], k_i.shape[0], cfg.index_topk
    if hi <= top:
        return None
    qb = _q_block(n)

    def one(at):
        cut = partial(jax.lax.dynamic_slice_in_dim, start_index=at,
                      slice_size=qb)
        with jax.named_scope("dsa_index"):
            q_i = index_queries(cut(c_q)[None], ap, cfg, cut(pos)[None])[0]
            scores = index_scores(q_i, k_i, cut(w_i))
        valid = (jnp.arange(hi)[None, :]
                 <= lo + at + jnp.arange(qb)[:, None])
        with jax.named_scope("dsa_select"):
            return _sparse.topk_mask(scores, valid, top)

    return jax.lax.map(one, jnp.arange(0, n, qb)).reshape(n, hi)


def prefill_selection(c_q, k_i, w_i, ap, cfg: LatentMoEConfig, pos):
    """What the indexer selects for every query of ONE sequence attending
    itself: ``[s, s]`` bool, row ``t`` the ``min(index_topk, t + 1)``
    positions ``<= t`` of largest index score (``None`` where ``s <=
    index_topk``: every position is selected); :func:`_stretch_selection`
    of ``c_q [s, q_lora_rank]`` a stretch at a time, against the keys ``k_i
    [s, index dim]`` up to its end."""
    s = c_q.shape[0]
    if s <= cfg.index_topk:
        return None
    rows = []
    for lo, hi in _stretches(s):
        block = _stretch_selection(c_q[lo:hi], w_i[lo:hi], k_i[:hi], ap,
                                   cfg, pos[lo:hi], lo)
        if block is None:
            block = jnp.tril(jnp.ones((hi - lo, hi), bool), lo)
        rows.append(jnp.pad(block, ((0, 0), (0, s - hi))))
    return jnp.concatenate(rows)


def _stretch_attention(c_q, entry, allowed, ap, cfg: LatentMoEConfig, pos,
                       lo: int):
    """:func:`mla_rebuilt_attention` of ONE stretch of a long sequence
    under a selection: the queries of rows ``[lo, lo + n)`` (``c_q [n,
    q_lora_rank]``, ``pos [n]``) against the entries up to the stretch's
    end (``entry [hi, entry_width]``), ``allowed [n, hi]`` bool or
    ``None``; causal on top of it.  Heads go through in groups of
    ``PREFILL_HEAD_GROUP`` (queries, keys and values of one group alive at
    a time), a group's query blocks as one mapped body, and its rows of
    ``w_o`` applied at once (all heads' outputs never stand side by side).
    Returns the block's output ``[n, hidden]`` float32.  Roundings are
    :func:`mla_rebuilt_attention`'s; the float32 sum over heads is taken
    a group at a time."""
    n, hi = c_q.shape[0], entry.shape[0]
    dt = c_q.dtype
    h_n, nope, rp, vd = (cfg.num_attention_heads, cfg.qk_nope_head_dim,
                         cfg.qk_rope_head_dim, cfg.v_head_dim)
    g = min(PREFILL_HEAD_GROUP, h_n)
    c = entry[:, :cfg.kv_lora_rank]
    k_rope = entry[:, cfg.kv_lora_rank:cfg.kv_lora_rank + rp]
    scale = softmax_scale(cfg)
    qb = _q_block(n)

    def group(i, y):
        w_uq = jax.lax.dynamic_slice_in_dim(
            ap["w_uq"], i * g * (nope + rp), g * (nope + rp), axis=1)
        w_ukv = jax.lax.dynamic_slice_in_dim(
            ap["w_ukv"], i * g * (nope + vd), g * (nope + vd), axis=1)
        q_nope, q_rope = mla_queries(c_q[None], w_uq, cfg, pos[None])
        q = jnp.concatenate([q_nope[0], q_rope[0]], axis=-1)
        kv = jnp.dot(c, w_ukv, preferred_element_type=jnp.float32
                     ).astype(dt).reshape(hi, g, nope + vd)
        k = jnp.concatenate(
            [kv[..., :nope], jnp.broadcast_to(k_rope[:, None, :],
                                              (hi, g, rp))], axis=-1)
        v = kv[..., nope:]

        def one(at):
            scores = jnp.einsum(
                "qhd,khd->hqk", jax.lax.dynamic_slice_in_dim(q, at, qb), k,
                preferred_element_type=jnp.float32) * scale
            mask = (jnp.arange(hi)[None, :]
                    <= lo + at + jnp.arange(qb)[:, None])
            if allowed is not None:
                mask = mask & jax.lax.dynamic_slice_in_dim(allowed, at, qb)
            p = _masked_softmax(scores, mask[None])
            return jnp.einsum("hqk,khd->qhd", p.astype(dt), v,
                              preferred_element_type=jnp.float32).astype(dt)

        out = jax.lax.map(one, jnp.arange(0, n, qb)).reshape(n, g * vd)
        w_o = jax.lax.dynamic_slice_in_dim(ap["w_o"], i * g * vd, g * vd)
        return y + jnp.dot(out, w_o, preferred_element_type=jnp.float32)

    with jax.named_scope("dsa_attend"):
        return jax.lax.fori_loop(
            0, h_n // g, group,
            jnp.zeros((n, ap["w_o"].shape[1]), jnp.float32))


def selected_rebuilt_attention(c_q, entry, allowed, ap,
                               cfg: LatentMoEConfig, pos):
    """:func:`mla_rebuilt_attention` of ONE long sequence under a
    selection: ``c_q [s, q_lora_rank]``, ``entry [s, entry_width]``,
    ``allowed [s, s]`` bool or ``None``; :func:`_stretch_attention` a
    stretch at a time.  Returns ``[s, hidden]`` float32."""
    return jnp.concatenate([
        _stretch_attention(
            c_q[lo:hi], entry[:hi],
            None if allowed is None else allowed[lo:hi, :hi], ap, cfg,
            pos[lo:hi], lo)
        for lo, hi in _stretches(c_q.shape[0])])


# -- layers -------------------------------------------------------------------

def _attn_block(x, ap, cfg, pos, attend):
    """Pre-norm residual attention; ``attend(q_nope, q_rope, entry, ap)``
    is the one part that sees cached entries.  Returns ``(x, entry)``.
    With an indexer the projections depend on the form too (a long
    prompt takes its heads in groups): ``attend(h, ap)`` makes them and
    returns ``(y, (entry, index key))``: the block's output ``[b, s,
    hidden]`` float32 and what the layer's two stores get."""
    h = rmsnorm(x, ap["norm"], cfg.rms_norm_eps, cfg.dtype)
    if cfg.indexed:
        y, entry = attend(h, ap)
        return x + y, entry
    q_nope, q_rope, entry = mla_project(h, ap, cfg, pos)
    o = attend(q_nope, q_rope, entry, ap)
    return x + jnp.dot(o, ap["w_o"],
                       preferred_element_type=jnp.float32), entry


def _token_blocks(f, h):
    """``f`` over ``h [b, s, d]``, ``FFN_TOKEN_BLOCK`` tokens at a time
    where there are more (a 16384-token prompt's gate and up products in
    float32 are 2.4 GB at once)."""
    b, s, d = h.shape
    if b * s <= FFN_TOKEN_BLOCK or (b * s) % FFN_TOKEN_BLOCK:
        return f(h)
    blocks = jax.lax.map(f, h.reshape(-1, 1, FFN_TOKEN_BLOCK, d))
    return blocks.reshape(b, s, -1)


def _dense_layer(x, lp, cfg, pos, attend):
    x, entry = _attn_block(x, lp["attn"], cfg, pos, attend)
    h = rmsnorm(x, lp["ffn_norm"], cfg.rms_norm_eps, cfg.dtype)
    f = lp["ffn"]
    return x + _token_blocks(
        lambda t: swiglu(t, f["w_gate"], f["w_up"], f["w_down"]), h), entry


def _moe_layer(x, lp, cfg, pos, attend, token_mask):
    x, entry = _attn_block(x, lp["attn"], cfg, pos, attend)
    b, s, d = x.shape
    h = rmsnorm(x, lp["ffn_norm"], cfg.rms_norm_eps,
                cfg.dtype).reshape(b * s, d)
    routing = None
    if cfg.group_limited:
        routing = partial(
            route_sigmoid_bias_group_top_k, router=lp["router"],
            bias=lp["router_bias"], top_k=cfg.num_experts_per_tok,
            n_group=cfg.n_group, topk_group=cfg.topk_group,
            routed_scale=cfg.routed_scaling_factor,
            norm_topk=cfg.norm_topk_prob)
    mask = None if token_mask is None else token_mask.reshape(-1)

    def experts(h, mask):
        held = moe_layer_held(
            h, lp, num_experts=cfg.n_routed_experts,
            expert_offset=cfg.expert_offset, top_k=cfg.num_experts_per_tok,
            routed_scale=cfg.routed_scaling_factor,
            norm_topk=cfg.norm_topk_prob, routing=routing, token_mask=mask)
        return held.out, held.counts

    t = b * s
    if t <= FFN_TOKEN_BLOCK or t % FFN_TOKEN_BLOCK or mask is None:
        out, counts = experts(h, mask)
    else:
        # A long prompt, FFN_TOKEN_BLOCK tokens at a time: routing is a
        # token's own, so the blocks' counts add up.
        out, counts = jax.lax.map(
            lambda hm: experts(*hm),
            (h.reshape(-1, FFN_TOKEN_BLOCK, d),
             mask.reshape(-1, FFN_TOKEN_BLOCK)))
        out, counts = out.reshape(t, d), jnp.sum(counts, axis=0)
    return x + out.reshape(b, s, d), entry, counts


def lm_head(params, x, cfg, rows=None):
    """The final norm and the head over ``x [b, s, d]``, or for row
    ``rows[b]`` of each sequence alone (a prompt's last valid one: the
    other rows' logits are never read, 1.06 GB of them at 16384 tokens):
    ``[b, s, vocab]`` or ``[b, vocab]`` float32.  The one row goes through
    the head inside a slab of ``HEAD_ROWS`` rows that ends at it: a
    product of ONE row the TPU's compiler turns into a multiply-and-reduce
    down the head's columns, 5 ms at 7168 x 20480 where the slab's matmul
    is a fraction of one."""
    if rows is None:
        x = rmsnorm(x, params["norm_f"], cfg.rms_norm_eps, cfg.dtype)
        return jnp.dot(x, params["unembed"],
                       preferred_element_type=jnp.float32)
    m = min(HEAD_ROWS, x.shape[1])
    start = jnp.clip(rows - (m - 1), 0, x.shape[1] - m)
    slab = jax.vmap(partial(jax.lax.dynamic_slice_in_dim, slice_size=m))(
        x, start)
    logits = lm_head(params, slab, cfg)
    return logits[jnp.arange(x.shape[0]), rows - start]


def _trunk(params, tokens, pos, cfg: LatentMoEConfig, attend, token_mask):
    """The layers of :func:`_layers` without the head and unstacked: ``(x
    [b, s, hidden] float32, what ``attend`` returned for each layer's
    store(s), the expert layers' counts)``."""
    # The residual stream is float32 from the embedding to the final
    # norm; matmul operands, the cache entry and the attention's
    # probabilities are what is rounded to the served type.
    x = params["embed"][tokens].astype(jnp.float32)
    entries, counts = [], []
    for i, lp in enumerate(params["layers"]):
        if i < cfg.first_k_dense_replace:
            x, entry = _dense_layer(x, lp, cfg, pos, partial(attend, i))
        else:
            x, entry, n = _moe_layer(x, lp, cfg, pos, partial(attend, i),
                                     token_mask)
            counts.append(n)
        entries.append(entry)
    return x, entries, counts


def _layers(params, tokens, pos, cfg: LatentMoEConfig, attend, token_mask,
            rows=None):
    """The forward around its attention: ``attend(layer, q_nope, q_rope,
    entry, ap)`` with ``layer`` the index into the cache and ``ap`` the
    layer's attention parameters (with an indexer: ``attend(layer, h,
    ap)``, see :func:`_attn_block`).  Returns ``(logits [b, s, vocab]
    float32, or [b, vocab] of row ``rows[b]``; entries [layers, b, s,
    width], with an indexer a pair of such, the index keys second; counts
    [expert layers, held])``."""
    x, entries, counts = _trunk(params, tokens, pos, cfg, attend, token_mask)
    logits = lm_head(params, x, cfg, rows)
    if cfg.indexed:
        entries = tuple(jnp.stack(e) for e in zip(*entries))
    else:
        entries = jnp.stack(entries)
    return logits, entries, jnp.stack(counts)


def selected_attend(cfg: LatentMoEConfig, pos, absorbed: bool = False):
    """The ``attend(layer, h, ap)`` of whole sequences attending
    themselves under the indexer's selection (``pos [b, s]``): a
    sequence at a time, :func:`prefill_selection`, then
    :func:`selected_rebuilt_attention` (``absorbed``: the decode's form
    over the block's own entries, as a test compares them)."""

    def one(h, ap, pos):
        c_q, entry = mla_latents(h[None], ap, cfg, pos[None])
        k_i, w_i = index_keys(h[None], ap, cfg, pos[None])
        allowed = prefill_selection(c_q[0], k_i[0], w_i[0], ap, cfg, pos)
        if absorbed:
            q_nope, q_rope = mla_queries(c_q, ap["w_uq"], cfg, pos[None])
            o = mla_absorbed_attention(
                q_nope, q_rope, entry, pos[None], ap, cfg,
                None if allowed is None else allowed[None])[0]
            y = jnp.dot(o, ap["w_o"], preferred_element_type=jnp.float32)
        else:
            y = selected_rebuilt_attention(c_q[0], entry[0], allowed, ap,
                                           cfg, pos)
        return y, (entry[0], k_i[0])

    def attend(layer, h, ap):
        return jax.vmap(one, in_axes=(0, None, 0))(h, ap, pos)

    return attend


def forward_full(params, tokens, cfg: LatentMoEConfig,
                 absorbed: bool = False):
    """Whole sequences ``[b, s]`` from an empty cache; ``absorbed``
    attends over the block's own entries in the decode's form instead of
    rebuilding keys.  Returns ``(logits, entries, counts)``."""
    b, s = tokens.shape
    pos = jnp.broadcast_to(jnp.arange(s, dtype=jnp.int32), (b, s))

    def attend(layer, q_nope, q_rope, entry, ap):
        if absorbed:
            return mla_absorbed_attention(q_nope, q_rope, entry, pos, ap,
                                          cfg)
        return mla_rebuilt_attention(q_nope, q_rope, entry, ap, cfg)

    if cfg.indexed:
        attend = selected_attend(cfg, pos, absorbed)
    return _layers(params, tokens, pos, cfg, attend, None)


def walked_stretch(cfg: LatentMoEConfig, b: int, s: int) -> int:
    """The rows of one stretch where the prompt program of a ``[b, s]``
    block WALKS it (:func:`walked_prefill`), else 0.  The shape decides:
    one prompt, an indexer's stretches, more than one of them and whole
    ones."""
    step = _stretch_len(s)
    return (step if cfg.indexed and b == 1 and s > step and s % step == 0
            else 0)


def walked_stretches(n_valid, step: int, s: int):
    """How many of a bucket's ``s // step`` stretches hold a token of a
    prompt of ``n_valid``: the walk's trip count, host integers and
    traced ones alike."""
    clip = np.clip if isinstance(n_valid, (int, np.integer)) else jnp.clip
    return clip(-(-n_valid // step), 1, s // step)


def walked_prefill(params, tokens, n_valid, cfg: LatentMoEConfig, step: int):
    """:func:`prefill_step` of ONE long prompt ``[1, bucket]``,
    stretch-major: ``step`` rows at a time through the embedding and ALL
    layers before the next stretch begins, each layer's entries and index
    keys of the stretches so far carried in ``[bucket, width]`` buffers
    (zeros to start with).  The trip count is computed on the device
    (:func:`walked_stretches`): a stretch whose first row is at or past
    ``n_valid`` is not run, and its rows of the returned entries stay
    zero.  The per-row parts are traced once, at ``step`` rows; the
    selection and the attention of a stretch's queries against the
    positions ``[0, hi)`` are one ``lax.switch`` on the stretch (the key
    extent of each stays static, so threshold and softmax are one piece
    over it, as in the layer-major form).  The head takes the last valid
    row out of the last stretch that ran; the experts' counts add up."""
    _, s = tokens.shape
    layers = params["layers"]

    def attend_stretch(c: int, ap):
        lo, hi = c * step, (c + 1) * step
        pos = jnp.arange(lo, hi, dtype=jnp.int32)

        def attend(c_q, w_i, entry, k_i):
            allowed = _stretch_selection(c_q, w_i, k_i[:hi], ap, cfg, pos,
                                         lo)
            return _stretch_attention(c_q, entry[:hi], allowed, ap, cfg,
                                      pos, lo)

        return attend

    def stretch(c, carry):
        bufs, counts, _ = carry
        lo = c * step
        pos = (lo + jnp.arange(step, dtype=jnp.int32))[None]

        def attend(layer, h, ap):
            c_q, entry = mla_latents(h, ap, cfg, pos)
            k_i, w_i = index_keys(h, ap, cfg, pos)
            so_far = tuple(
                jax.lax.dynamic_update_slice_in_dim(buf, new[0], lo, 0)
                for buf, new in zip(bufs[layer], (entry, k_i)))
            y = jax.lax.switch(
                c, [attend_stretch(i, ap) for i in range(s // step)],
                c_q[0], w_i[0], *so_far)
            return y[None], so_far

        x, held, n = _trunk(
            params, jax.lax.dynamic_slice_in_dim(tokens, lo, step, axis=1),
            pos, cfg, attend, pos < n_valid[:, None])
        return held, counts + jnp.stack(n), x

    trips = walked_stretches(n_valid[0], step, s)
    held, counts, x = jax.lax.fori_loop(0, trips, stretch, (
        [tuple(jnp.zeros((s, w), cfg.dtype) for w in cfg.entry_widths)
         for _ in layers],
        jnp.zeros((cfg.n_moe_layers, cfg.experts_held), jnp.int32),
        jnp.zeros((1, step, cfg.hidden_size), jnp.float32)))
    last = lm_head(params, x, cfg, rows=n_valid - 1 - (trips - 1) * step)
    return (last, tuple(jnp.stack(e)[:, None] for e in zip(*held)), counts)


def prefill_step(params, tokens, n_valid, cfg: LatentMoEConfig):
    """A padded prompt ``[1, bucket]`` from an empty cache: positions
    ``>= n_valid`` are padding (they reach no expert; their entries are
    garbage the caller maps to trash or overwrites).  Returns ``(last
    [1, vocab]``, the last valid row's logits, ``entries [layers, 1,
    bucket, width]`` (with an indexer a pair, the index keys second),
    ``counts)``.  A bucket of several stretches is walked a stretch at a
    time as far as the prompt reaches (:func:`walked_stretch`,
    :func:`walked_prefill`); without an indexer every row of a long
    bucket is still computed (no cell of that family has a prompt past
    one stretch)."""
    b, s = tokens.shape
    step = walked_stretch(cfg, b, s)
    if step:
        return walked_prefill(params, tokens, n_valid, cfg, step)
    pos = jnp.broadcast_to(jnp.arange(s, dtype=jnp.int32), (b, s))

    def attend(layer, q_nope, q_rope, entry, ap):
        return mla_rebuilt_attention(q_nope, q_rope, entry, ap, cfg)

    if cfg.indexed:
        attend = selected_attend(cfg, pos)
    return _layers(params, tokens, pos, cfg, attend,
                   pos < n_valid[:, None], rows=n_valid - 1)


def paged_kernel_runs() -> bool:
    """Whether the decode program attends through the kernel that walks
    the page table: read off the backend the program is built for
    (``PAGED_INTERPRET`` is a test's), nothing a user sets."""
    return _paged.kernel_runs(PAGED_INTERPRET)


def paged_attend(lengths, store, table, cfg, interpret=None):
    """The decode step's ``attend`` through the kernel
    (``ops/latent_paged_attention.py``): the live slots' own pages, read
    where they lie; the absorptions stay the matmuls they are, outside
    it."""
    order, n_live = _paged.live_first(lengths)

    def attend(layer, q_nope, q_rope, entry, ap):
        q = _absorbed_query(q_nope, q_rope, store.shape[-1], ap, cfg)
        o_lat = _paged.latent_paged_attention(
            q[:, 0], entry[:, 0], store, table, lengths, layer,
            scale=softmax_scale(cfg), kv_rank=cfg.kv_lora_rank,
            order=order, n_live=n_live, interpret=interpret)
        return _absorbed_output(o_lat[:, None], ap, cfg)

    return attend


def gathered_attend(lengths, store, table, cfg):
    """The kernel's twin off the TPU, the plainest thing that is right:
    every slot's table row gathered whole, the new entry put at its
    position, one :func:`mla_absorbed_attention` over it under the
    lengths (an idle slot's mask is empty: zeros)."""
    b = lengths.shape[0]
    pos = jnp.clip(lengths, 0, None)

    def attend(layer, q_nope, q_rope, entry, ap):
        view = store[layer][table].reshape(b, -1, store.shape[-1])
        view = view.at[jnp.arange(b), pos].set(entry[:, 0], mode="drop")
        return mla_absorbed_attention(q_nope, q_rope, view,
                                      lengths[:, None], ap, cfg)

    return attend


def selected_decode_attend(lengths, stores, table, cfg):
    """The decode step's ``attend(layer, h, ap)`` with an indexer over
    the two stores ``(latent entries, index keys)``: score the slot's
    cached index keys, select ``index_topk`` of them and the new token,
    attend the selected entries in the absorbed form.  On the TPU
    ``ops/sparse_latent_attention.py``'s two kernels read both stores
    where they lie and the selection is a threshold found without a sort;
    elsewhere the plain twin: gather the row, score, ``top_k``, mask."""
    store, keys = stores
    b = lengths.shape[0]
    pos = jnp.clip(lengths, 0, None)
    cap = table.shape[1] * store.shape[2]
    col = jnp.arange(cap, dtype=jnp.int32)[None, :]
    top = cfg.index_topk
    kernel = paged_kernel_runs()
    if kernel:
        order, n_live = _paged.live_first(lengths)

    def attend(layer, h, ap):
        c_q, entry = mla_latents(h, ap, cfg, pos[:, None])
        q_nope, q_rope = mla_queries(c_q, ap["w_uq"], cfg, pos[:, None])
        q_i, k_i, w_i = index_project(h, c_q, ap, cfg, pos[:, None])
        if kernel:
            scores = _sparse.index_paged_scores(
                q_i[:, 0], w_i[:, 0], keys, table, lengths, layer,
                order=order, n_live=n_live, interpret=PAGED_INTERPRET)
            own = jnp.sum(jax.nn.relu(jnp.einsum(
                "bhd,bd->bh", q_i[:, 0], k_i[:, 0],
                preferred_element_type=jnp.float32)) * w_i[:, 0], axis=-1)
            scores = jnp.where(col == pos[:, None], own[:, None], scores)
            selected = _sparse.select_paged(scores, lengths, top,
                                            interpret=PAGED_INTERPRET)
            q = _absorbed_query(q_nope, q_rope, store.shape[-1], ap, cfg)
            o_lat = _sparse.sparse_paged_attention(
                q[:, 0], entry[:, 0], store, table, lengths, layer,
                selected, scale=softmax_scale(cfg),
                kv_rank=cfg.kv_lora_rank, order=order, n_live=n_live,
                interpret=PAGED_INTERPRET)
            o = _absorbed_output(o_lat[:, None], ap, cfg)
        else:
            rows = jnp.arange(b)
            key_view = keys[layer][table].reshape(b, cap, -1).at[
                rows, pos].set(k_i[:, 0], mode="drop")
            scores = jax.vmap(index_scores)(q_i, key_view, w_i)[:, 0]
            _, idx = jax.lax.top_k(
                jnp.where(col <= lengths[:, None], scores, -jnp.inf),
                min(top, cap))
            selected = jnp.zeros((b, cap), bool).at[
                rows[:, None], idx].set(True)
            view = store[layer][table].reshape(b, cap, -1).at[
                rows, pos].set(entry[:, 0], mode="drop")
            o = mla_absorbed_attention(q_nope, q_rope, view,
                                       lengths[:, None], ap, cfg,
                                       selected[:, None])
        return (jnp.dot(o, ap["w_o"], preferred_element_type=jnp.float32),
                (entry, k_i))

    return attend, pos[:, None]


def decode_attend(lengths, store, table, cfg):
    """The decode step's attention over the paged store: on the TPU
    :func:`paged_attend`, elsewhere :func:`gathered_attend`
    (:func:`paged_kernel_runs`, asked here and nowhere else).
    ``lengths [slots]`` (-1 idle: such a slot attends nothing); ``store
    [cache layers, pages, page, width]``.  Returns ``(attend, pos)``:
    ``attend(layer, q_nope, q_rope, entry, ap)`` with ``layer`` the index
    into the store, and the new tokens' positions ``[slots, 1]``."""
    attend = (paged_attend(lengths, store, table, cfg, PAGED_INTERPRET)
              if paged_kernel_runs()
              else gathered_attend(lengths, store, table, cfg))
    return attend, jnp.clip(lengths, 0, None)[:, None]


def decode_step(params, tokens, lengths, store, table,
                cfg: LatentMoEConfig):
    """One token a slot over the paged store through
    :func:`decode_attend` (with an indexer ``store`` is the pair of
    stores and the attention :func:`selected_decode_attend`'s).
    ``tokens [slots]``; ``lengths [slots]`` (-1 idle: such a slot reaches
    no expert).  Returns ``(logits [slots, vocab], entries [layers,
    slots, width] (a pair with an indexer), counts [expert layers,
    held])``."""
    if cfg.indexed:
        attend, pos = selected_decode_attend(lengths, store, table, cfg)
    else:
        attend, pos = decode_attend(lengths, store, table, cfg)
    logits, entries, counts = _layers(params, tokens[:, None], pos, cfg,
                                      attend, lengths[:, None] >= 0)
    if cfg.indexed:
        return logits[:, 0], tuple(e[:, :, 0] for e in entries), counts
    return logits[:, 0], entries[:, :, 0], counts


# -- what the serving engine asks ---------------------------------------------

class LatentMoEServing:
    """The serving protocol (serving/models.py) for this model: ONE
    store, ``[cache layers, pages, page, entry_width]``.  A sibling
    family on the same store (models/shortcut_moe.py) gives its own step
    functions, ``(logits, entries, *extras)`` each, and identity."""

    decode_step = staticmethod(decode_step)
    prefill_step = staticmethod(prefill_step)

    speculative = False        # no verify / propose programs
    tensor_parallel = False    # one latent "head": nothing to shard
    tensor_parallel_why = "caches one entry all heads share"
    slot_state = False         # no per-slot store beside the pages
    prefix_cache = False       # the prefill attends its own block only
    prefix_cache_why = ("the latent prefill attends its own block only: "
                        "a suffix prefill over cached latent pages is "
                        "not written yet (chunked prefill)")

    def __init__(self, cfg: LatentMoEConfig) -> None:
        self.cfg = cfg

    def identity(self) -> dict:
        c = self.cfg
        return {"family": "latent_moe", "vocab_size": c.vocab_size,
                "hidden_size": c.hidden_size,
                "layers": c.num_hidden_layers,
                "dense_layers": c.first_k_dense_replace,
                "heads": c.num_attention_heads,
                "q_lora_rank": c.q_lora_rank,
                "kv_lora_rank": c.kv_lora_rank,
                "qk_dims": [c.qk_nope_head_dim, c.qk_rope_head_dim,
                            c.v_head_dim],
                "widths": [c.intermediate_size, c.moe_intermediate_size],
                "experts": [c.n_routed_experts, c.experts_held,
                            c.expert_offset, c.num_experts_per_tok],
                **({"indexer": [c.index_n_heads, c.index_head_dim,
                                c.index_topk]} if c.indexed else {}),
                **({"expert_groups": [c.n_group, c.topk_group]}
                   if c.group_limited else {}),
                "max_seq_len": c.max_seq_len,
                "dtype": jnp.dtype(c.dtype).name}

    def decode_view(self, lengths, page_size, pages_per_slot) -> float:
        """Tokens of the store a slot the decode program attends in one
        cache layer at these (host) lengths: the live lengths rounded up
        to the page (what the kernel copies; its twin gathers the whole
        rows and masks the rest), an idle slot nothing."""
        return _paged.tokens_read(lengths, page_size) / len(lengths)

    def cache_entry(self) -> dict:
        """One store, with an indexer two on one page table (the latent
        entry and the index key); to the cache each is one key/value
        head as wide as the entry."""
        widths = self.cfg.entry_widths
        _M_ENTRY_BYTES.set(sum(widths)
                           * jnp.dtype(self.cfg.dtype).itemsize)
        return {"n_layers": self.cfg.cache_layers, "n_heads": 1,
                "head_dim": widths[0], "widths": widths}

    def decode(self, params, pages, table, lengths, tokens):
        ps = pages[0].shape[2]
        logits, entries, *extras = self.decode_step(
            params, tokens, lengths, pages if self.cfg.indexed else pages[0],
            table, self.cfg)
        if not self.cfg.indexed:
            entries = (entries,)
        # One row a slot, written where it lies (see DenseLM.decode).
        pos = jnp.clip(lengths, 0, None)
        b = tokens.shape[0]
        page, off = table[jnp.arange(b), pos // ps], pos % ps
        zero = jnp.zeros((), jnp.int32)
        pages = list(pages)
        for slot in range(b):
            for i, rows in enumerate(entries):
                pages[i] = jax.lax.dynamic_update_slice(
                    pages[i], rows[:, slot][:, None, None, :],
                    (zero, page[slot], off[slot], zero))
        return (logits, *extras), tuple(pages)

    def prefill(self, params, pages, table_row, start, n_valid, tokens):
        """``start`` is always 0 here (``prefix_cache`` is off)."""
        ps, bucket = pages[0].shape[2], tokens.shape[1]
        last, entries, *_ = self.prefill_step(params, tokens, n_valid,
                                              self.cfg)
        if not self.cfg.indexed:
            entries = (entries,)
        # A page at a time, written where it lies.  (A scatter over the
        # flattened store makes the TPU copy all of it into a layout of
        # the scatter's own, and back.)
        zero = jnp.zeros((), jnp.int32)
        if walked_stretch(self.cfg, *tokens.shape) and bucket % ps == 0:
            # A walked prompt: the pages that hold a token, and no other
            # (the stretches past them were not run).
            def write(j, pages):
                return tuple(jax.lax.dynamic_update_slice(
                    store, jax.lax.dynamic_slice_in_dim(new, j * ps, ps, 2),
                    (zero, table_row[0, j], zero, zero))
                    for store, new in zip(pages, entries))

            return (last[0],), jax.lax.fori_loop(
                0, -(-n_valid[0] // ps), write, tuple(pages))
        # Pages past the prompt are not mapped: their rows land in trash
        # page 0.
        rows = min(ps, bucket)
        pages = list(pages)
        for j in range(max(1, bucket // ps)):
            for i, new in enumerate(entries):
                pages[i] = jax.lax.dynamic_update_slice(
                    pages[i], new[:, :, j * ps:j * ps + rows],
                    (zero, table_row[0, j], zero, zero))
        return (last[0],), tuple(pages)

    def prefill_rows(self, bucket: int, n_valid: int) -> int:
        """Rows the prompt program of ``bucket`` computes for a prompt of
        ``n_valid`` tokens (``serving.prefill_rows``): the bucket, or the
        stretches a walk runs (:func:`walked_stretches`, which the
        program takes its trip count from)."""
        step = walked_stretch(self.cfg, 1, bucket)
        if not step:
            return bucket
        return int(walked_stretches(n_valid, step, bucket)) * step

    def observe_launch(self, lengths) -> None:
        """What the indexer scored and selected in one decode iteration
        launched at these host lengths: a live slot's cached positions
        and its new token, ``index_topk`` of them at most, in every
        layer."""
        if not self.cfg.indexed:
            return
        lengths = np.asarray(lengths)
        seen = lengths[lengths >= 0] + 1
        layers = self.cfg.cache_layers
        _M_DSA_SCORED.inc(int(seen.sum()) * layers)
        if TraceAnnotation.is_enabled():
            _M_DSA_SCORED_TRACED.inc(int(seen.sum()) * layers)
        _M_DSA_SELECTED.inc(
            int(np.minimum(seen, self.cfg.index_topk).sum()) * layers)

    def observe_decode(self, extras) -> None:
        """Feed the counters from what the decode program returned
        beside the logits (inside ``serve.sample``)."""
        counts = np.asarray(extras[0])
        _M_MOE_ASSIGN.inc(int(counts.sum()))
        _M_MOE_LOAD_MAX.inc(int(counts.max(axis=1).sum()))
        _M_MOE_TOUCHED.inc(int((counts > 0).sum()))
