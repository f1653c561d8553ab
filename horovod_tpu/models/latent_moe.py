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

from .. import telemetry as _telemetry
from ..ops import latent_paged_attention as _paged
from ..parallel.expert import moe_layer_held, swiglu

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
    keys = iter(jax.random.split(key, 4 + 16 * cfg.num_hidden_layers))

    def w(shape, scale):
        return (jax.random.normal(next(keys), shape, jnp.float32)
                * scale).astype(dt)

    def attn():
        return {"norm": jnp.ones((d,), dt),
                "w_dq": w((d, rq), std), "q_norm": jnp.ones((rq,), dt),
                "w_uq": w((rq, h_n * (nope + rp)), std),
                "w_dkv": w((d, rkv + rp), std),
                "kv_norm": jnp.ones((rkv,), dt),
                "w_ukv": w((rkv, h_n * (nope + vd)), std),
                "w_o": w((h_n * vd, d), res)}

    def ffn(width):
        return {"w_gate": w((d, width), std), "w_up": w((d, width), std),
                "w_down": w((width, d), res)}

    def layer(i):
        if i < cfg.first_k_dense_replace:
            return {"attn": attn(), "ffn_norm": jnp.ones((d,), dt),
                    "ffn": ffn(f)}
        return {"attn": attn(), "ffn_norm": jnp.ones((d,), dt),
                "router": w((d, cfg.n_routed_experts), 1.5 / d ** 0.5),
                "shared": ffn(fm * cfg.n_shared_experts),
                "w_gate": w((e, d, fm), std), "w_up": w((e, d, fm), std),
                "w_down": w((e, fm, d), res)}

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
                           cfg: LatentMoEConfig):
    """Attention over cached entries themselves: ``W_uk`` absorbed into
    the query, ``W_uv`` into the output.  ``view [b, n, entry_width]``
    holds position ``j`` at row ``j``; row ``j`` takes part in query ``(b,
    i)`` iff ``j <= q_pos[b, i]``.  The query is padded like the entry,
    so the scores contract whole rows (the zeros add nothing).  Returns
    ``[b, s, heads * v_dim]``."""
    dt = q_nope.dtype
    q = _absorbed_query(q_nope, q_rope, view.shape[-1], ap, cfg)
    scores = jnp.einsum("bshc,bnc->bhsn", q, view,
                        preferred_element_type=jnp.float32
                        ) * softmax_scale(cfg)
    mask = (jnp.arange(view.shape[1], dtype=jnp.int32)[None, None, None, :]
            <= q_pos[:, None, :, None])
    p = _masked_softmax(scores, mask)
    o_lat = jnp.einsum("bhsn,bnc->bshc", p.astype(dt),
                       view[..., :cfg.kv_lora_rank],
                       preferred_element_type=jnp.float32).astype(dt)
    return _absorbed_output(o_lat, ap, cfg)


# -- layers -------------------------------------------------------------------

def _attn_block(x, ap, cfg, pos, attend):
    """Pre-norm residual attention; ``attend(q_nope, q_rope, entry, ap)``
    is the one part that sees cached entries.  Returns ``(x, entry)``."""
    h = rmsnorm(x, ap["norm"], cfg.rms_norm_eps, cfg.dtype)
    q_nope, q_rope, entry = mla_project(h, ap, cfg, pos)
    o = attend(q_nope, q_rope, entry, ap)
    return x + jnp.dot(o, ap["w_o"],
                       preferred_element_type=jnp.float32), entry


def _dense_layer(x, lp, cfg, pos, attend):
    x, entry = _attn_block(x, lp["attn"], cfg, pos, attend)
    h = rmsnorm(x, lp["ffn_norm"], cfg.rms_norm_eps, cfg.dtype)
    f = lp["ffn"]
    return x + swiglu(h, f["w_gate"], f["w_up"], f["w_down"]), entry


def _moe_layer(x, lp, cfg, pos, attend, token_mask):
    x, entry = _attn_block(x, lp["attn"], cfg, pos, attend)
    b, s, d = x.shape
    h = rmsnorm(x, lp["ffn_norm"], cfg.rms_norm_eps,
                cfg.dtype).reshape(b * s, d)
    held = moe_layer_held(
        h, lp, num_experts=cfg.n_routed_experts,
        expert_offset=cfg.expert_offset, top_k=cfg.num_experts_per_tok,
        routed_scale=cfg.routed_scaling_factor,
        norm_topk=cfg.norm_topk_prob,
        token_mask=None if token_mask is None else token_mask.reshape(-1))
    return x + held.out.reshape(b, s, d), entry, held.counts


def _layers(params, tokens, pos, cfg: LatentMoEConfig, attend, token_mask):
    """The forward around its attention: ``attend(layer, q_nope, q_rope,
    entry, ap)`` with ``layer`` the index into the cache and ``ap`` the
    layer's attention parameters.  Returns ``(logits [b, s, vocab]
    float32, entries [layers, b, s, width], counts [expert layers,
    held])``."""
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
    x = rmsnorm(x, params["norm_f"], cfg.rms_norm_eps, cfg.dtype)
    logits = jnp.dot(x, params["unembed"],
                     preferred_element_type=jnp.float32)
    return logits, jnp.stack(entries), jnp.stack(counts)


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

    return _layers(params, tokens, pos, cfg, attend, None)


def prefill_step(params, tokens, n_valid, cfg: LatentMoEConfig):
    """A padded prompt ``[1, bucket]`` from an empty cache: positions
    ``>= n_valid`` are padding (they reach no expert; their entries are
    garbage the caller maps to trash or overwrites).  Returns ``(logits
    [1, bucket, vocab], entries [layers, 1, bucket, width], counts)``."""
    b, s = tokens.shape
    pos = jnp.broadcast_to(jnp.arange(s, dtype=jnp.int32), (b, s))

    def attend(layer, q_nope, q_rope, entry, ap):
        return mla_rebuilt_attention(q_nope, q_rope, entry, ap, cfg)

    return _layers(params, tokens, pos, cfg, attend,
                   pos < n_valid[:, None])


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
    :func:`decode_attend`.  ``tokens [slots]``; ``lengths [slots]`` (-1
    idle: such a slot reaches no expert).  Returns ``(logits [slots,
    vocab], entries [layers, slots, width], counts [expert layers,
    held])``."""
    attend, pos = decode_attend(lengths, store, table, cfg)
    logits, entries, counts = _layers(params, tokens[:, None], pos, cfg,
                                      attend, lengths[:, None] >= 0)
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
                "max_seq_len": c.max_seq_len,
                "dtype": jnp.dtype(c.dtype).name}

    def decode_view(self, lengths, page_size, pages_per_slot) -> float:
        """Tokens of the store a slot the decode program attends in one
        cache layer at these (host) lengths: the live lengths rounded up
        to the page (what the kernel copies; its twin gathers the whole
        rows and masks the rest), an idle slot nothing."""
        return _paged.tokens_read(lengths, page_size) / len(lengths)

    def cache_entry(self) -> dict:
        """One store; to the cache it is one key/value head as wide as
        the entry."""
        w = self.cfg.entry_width
        _M_ENTRY_BYTES.set(w * jnp.dtype(self.cfg.dtype).itemsize)
        return {"n_layers": self.cfg.cache_layers, "n_heads": 1,
                "head_dim": w, "widths": (w,)}

    def decode(self, params, pages, table, lengths, tokens):
        (store,) = pages
        ps = store.shape[2]
        logits, entries, *extras = self.decode_step(
            params, tokens, lengths, store, table, self.cfg)
        # One row a slot, written where it lies (see DenseLM.decode).
        pos = jnp.clip(lengths, 0, None)
        b = tokens.shape[0]
        page, off = table[jnp.arange(b), pos // ps], pos % ps
        zero = jnp.zeros((), jnp.int32)
        for slot in range(b):
            store = jax.lax.dynamic_update_slice(
                store, entries[:, slot][:, None, None, :],
                (zero, page[slot], off[slot], zero))
        return (logits, *extras), (store,)

    def prefill(self, params, pages, table_row, start, n_valid, tokens):
        """``start`` is always 0 here (``prefix_cache`` is off)."""
        (store,) = pages
        ps, bucket = store.shape[2], tokens.shape[1]
        logits, entries, *_ = self.prefill_step(params, tokens, n_valid,
                                                self.cfg)
        # A page at a time, written where it lies.  (A scatter over the
        # flattened store makes the TPU copy all of it into a layout of
        # the scatter's own, and back.)  Pages past the prompt are not
        # mapped: their rows land in trash page 0.
        rows = min(ps, bucket)
        zero = jnp.zeros((), jnp.int32)
        for j in range(max(1, bucket // ps)):
            store = jax.lax.dynamic_update_slice(
                store, entries[:, :, j * ps:j * ps + rows],
                (zero, table_row[0, j], zero, zero))
        return (logits[0, n_valid[0] - 1],), (store,)

    def observe_decode(self, extras) -> None:
        """Feed the counters from what the decode program returned
        beside the logits (inside ``serve.sample``)."""
        counts = np.asarray(extras[0])
        _M_MOE_ASSIGN.inc(int(counts.sum()))
        _M_MOE_LOAD_MAX.inc(int(counts.max(axis=1).sum()))
        _M_MOE_TOUCHED.inc(int((counts > 0).sum()))
