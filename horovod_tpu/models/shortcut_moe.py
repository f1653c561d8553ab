"""Shortcut-connected mixture-of-experts decoder, for serving.

The LongCat-Flash family's decoder layer as ``meituan-longcat/
LongCat-Flash-Omni`` configures it (docs/inference.md "Shortcut-connected
mixture of experts"): ONE layer holds TWO latent attentions, TWO dense
SwiGLU feed-forwards and ONE expert layer on a shortcut.  For residual
stream ``x``::

    for j in (0, 1):
        x = x + attention_j(norm_in_j(x))           # cache layer 2i + j
        g = norm_post_j(x)
        if j == 0:
            m = experts(g)                          # read here ...
        x = x + dense_ffn_j(g)
    x = x + m                                       # ... joined here

so that in a deployment the experts' exchange runs under the first dense
feed-forward, the second attention and the second dense feed-forward.
The router is a softmax over ``n_routed_experts + zero_expert_num``
outputs; the ``moe_topk`` largest of ``scores + bias`` are chosen and
weighted by the unbiased scores ``* routed_scaling_factor``, not
renormalised; a chosen zero-compute expert (the router's last
``zero_expert_num`` outputs) returns its input, so it adds ``w g`` and
costs no matmul.  The latent attention is ``models/latent_moe.py``'s
(one ``kv_lora_rank + qk_rope_head_dim`` wide entry a token an
attention), with the queries and the latent rescaled after their norms
(``mla_scale_q_lora``, ``mla_scale_kv_lora``) and plain rotary positions
(the config publishes no ``rope_scaling``).  No leading dense layer, no
shared expert.

This module holds what the family does NOT share with
``models/latent_moe.py``: the layer's structure, which no published key
expresses (it is the family's), its router and its parameters.  The
attention functions, the decode step's attention over the store and the
serving protocol's store handling are imported from there.

A member of an expert-parallel group holds ``experts_held`` of the real
experts (``expert_offset`` on) and computes their part of each layer and
the zero-compute term (:func:`..parallel.expert.moe_layer_held`); both
attentions, both dense feed-forwards and the router are whole on every
member.  Nothing here stands in for the absent members.
"""

from __future__ import annotations

from dataclasses import dataclass
from functools import cached_property, partial

import jax
import jax.numpy as jnp

from .. import telemetry as _telemetry
from ..parallel.expert import moe_layer_held, route_softmax_top_k, swiglu
from . import latent_moe as _latent
from .latent_moe import LatentMoEConfig, LatentMoEServing, rmsnorm

_M_MOE_ZERO = _telemetry.counter(
    "serving.moe_zero_assignments", "token-expert pairs the decode "
    "iterations sent to zero-compute experts, summed over the expert "
    "layers")
_M_MOE_ROUTED = _telemetry.counter(
    "serving.moe_routed_pairs", "all token-expert pairs of live tokens "
    "(live tokens x experts a token), summed over decode iterations and "
    "expert layers")


@dataclass(frozen=True)
class ShortcutMoEConfig:
    """The published keys under their published names and, beside them,
    the share held here.  ``vocab_size`` and ``num_layers`` are what is
    RUN (the rows of the vocabulary held, the layers kept);
    ``n_routed_experts`` stays the count of real experts the router
    scores, before its ``zero_expert_num`` zero-compute outputs."""
    vocab_size: int = 131072
    hidden_size: int = 6144
    ffn_hidden_size: int = 12288
    expert_ffn_hidden_size: int = 2048
    num_layers: int = 28
    num_attention_heads: int = 64
    q_lora_rank: int = 1536
    kv_lora_rank: int = 512
    qk_nope_head_dim: int = 128
    qk_rope_head_dim: int = 64
    v_head_dim: int = 128
    mla_scale_q_lora: bool = True
    mla_scale_kv_lora: bool = True
    n_routed_experts: int = 512
    zero_expert_num: int = 256
    moe_topk: int = 12
    routed_scaling_factor: float = 6.0
    rms_norm_eps: float = 1e-5
    rope_theta: float = 1e7
    max_position_embeddings: int = 131072
    dtype: object = jnp.bfloat16
    # The share held here.
    experts_held: int = 512
    expert_offset: int = 0

    @property
    def max_seq_len(self) -> int:
        return self.max_position_embeddings

    @property
    def router_outputs(self) -> int:
        return self.n_routed_experts + self.zero_expert_num

    @property
    def cache_layers(self) -> int:
        """Layers of the paged store: two latent attentions a layer."""
        return 2 * self.num_layers

    @cached_property
    def mla(self) -> LatentMoEConfig:
        """What the shared attention functions read: the latent sizes,
        the rescaling, and no YaRN (factor 1: plain rotary frequencies,
        a softmax scale of ``(nope + rope) ** -0.5``)."""
        return LatentMoEConfig(
            hidden_size=self.hidden_size,
            num_attention_heads=self.num_attention_heads,
            q_lora_rank=self.q_lora_rank, kv_lora_rank=self.kv_lora_rank,
            qk_nope_head_dim=self.qk_nope_head_dim,
            qk_rope_head_dim=self.qk_rope_head_dim,
            v_head_dim=self.v_head_dim, rms_norm_eps=self.rms_norm_eps,
            rope_theta=self.rope_theta, rope_factor=1.0,
            mla_scale_q_lora=self.mla_scale_q_lora,
            mla_scale_kv_lora=self.mla_scale_kv_lora, dtype=self.dtype)

    @property
    def entry_width(self) -> int:
        return self.mla.entry_width

    # What ``LatentMoEServing`` asks beside it: no indexer, one store.
    indexed = False

    @property
    def entry_widths(self) -> tuple:
        return self.mla.entry_widths

    def serving_model(self) -> "ShortcutMoEServing":
        return ShortcutMoEServing(self)


# -- parameters ---------------------------------------------------------------

def init_shortcut_moe(key, cfg: ShortcutMoEConfig) -> dict:
    """Parameter pytree: ``layers`` is a LIST, one dict a layer, every
    weight a leaf of its own (see ``init_latent_moe``); a layer's two
    sublayers are lists of two under ``attn``, ``ffn_norm`` and ``ffn``.
    Normal init, residual projections scaled by the number of sublayers,
    router rows such that the softmax's chosen few carry a real share,
    the score-correction bias small and non-zero."""
    d, f, fm = (cfg.hidden_size, cfg.ffn_hidden_size,
                cfg.expert_ffn_hidden_size)
    h_n, rq, rkv = (cfg.num_attention_heads, cfg.q_lora_rank,
                    cfg.kv_lora_rank)
    nope, rp, vd = (cfg.qk_nope_head_dim, cfg.qk_rope_head_dim,
                    cfg.v_head_dim)
    e, v, dt = cfg.experts_held, cfg.vocab_size, cfg.dtype
    std, res = 0.02, 0.02 / (2 * cfg.cache_layers) ** 0.5
    keys = iter(jax.random.split(key, 4 + 32 * cfg.num_layers))

    def w(shape, scale, dtype=dt):
        return (jax.random.normal(next(keys), shape, jnp.float32)
                * scale).astype(dtype)

    def attn():
        return {"norm": jnp.ones((d,), dt),
                "w_dq": w((d, rq), std), "q_norm": jnp.ones((rq,), dt),
                "w_uq": w((rq, h_n * (nope + rp)), std),
                "w_dkv": w((d, rkv + rp), std),
                "kv_norm": jnp.ones((rkv,), dt),
                "w_ukv": w((rkv, h_n * (nope + vd)), std),
                "w_o": w((h_n * vd, d), res)}

    def ffn():
        return {"w_gate": w((d, f), std), "w_up": w((d, f), std),
                "w_down": w((f, d), res)}

    def layer():
        return {"attn": [attn(), attn()],
                "ffn_norm": [jnp.ones((d,), dt), jnp.ones((d,), dt)],
                "ffn": [ffn(), ffn()],
                "router": w((d, cfg.router_outputs), 1.5 / d ** 0.5),
                "router_bias": w((cfg.router_outputs,), 5e-4, jnp.float32),
                "w_gate": w((e, d, fm), std), "w_up": w((e, d, fm), std),
                "w_down": w((e, fm, d), res)}

    return {
        "embed": w((v, d), std),
        "layers": [layer() for _ in range(cfg.num_layers)],
        "norm_f": jnp.ones((d,), dt),
        "unembed": w((d, v), std),
    }


# -- layers -------------------------------------------------------------------

def _shortcut_layer(x, lp, cfg: ShortcutMoEConfig, pos, attend, token_mask,
                    cache_layer: int):
    """One decoder layer; sublayer ``j`` attends cached entries through
    ``attend(cache_layer + j, q_nope, q_rope, entry, ap)``.  Returns
    ``(x, [entry_0, entry_1], held)``, ``held`` the expert layer's
    :class:`~horovod_tpu.parallel.expert.HeldExpertsOutput`."""
    b, s, d = x.shape
    entries, held = [], None
    for j in (0, 1):
        x, entry = _latent._attn_block(x, lp["attn"][j], cfg.mla, pos,
                                       partial(attend, cache_layer + j))
        entries.append(entry)
        g = rmsnorm(x, lp["ffn_norm"][j], cfg.rms_norm_eps, cfg.dtype)
        if j == 0:
            held = moe_layer_held(
                g.reshape(b * s, d), lp, num_experts=cfg.router_outputs,
                expert_offset=cfg.expert_offset, top_k=cfg.moe_topk,
                routing=partial(route_softmax_top_k, router=lp["router"],
                                bias=lp["router_bias"], top_k=cfg.moe_topk,
                                routed_scale=cfg.routed_scaling_factor),
                zero_experts=cfg.zero_expert_num,
                token_mask=(None if token_mask is None
                            else token_mask.reshape(-1)))
        f = lp["ffn"][j]
        x = x + swiglu(g, f["w_gate"], f["w_up"], f["w_down"])
    with jax.named_scope("moe_shortcut_add"):
        x = x + held.out.reshape(b, s, d)
    return x, entries, held


def _layers(params, tokens, pos, cfg: ShortcutMoEConfig, attend, token_mask,
            rows=None):
    """The forward around its attention: ``attend(layer, q_nope, q_rope,
    entry, ap)`` with ``layer`` the index into the CACHE (``2i + j`` for
    sublayer ``j`` of decoder layer ``i``).  Returns ``(logits [b, s,
    vocab] float32 (``[b, vocab]`` of row ``rows[b]`` alone where given:
    ``latent_moe.lm_head``), entries [cache layers, b, s, width], counts
    [layers, held], zero_pairs [layers], routed_pairs [layers])``."""
    x = params["embed"][tokens].astype(jnp.float32)
    entries, held = [], []
    for i, lp in enumerate(params["layers"]):
        x, pair, h = _shortcut_layer(x, lp, cfg, pos, attend, token_mask,
                                     cache_layer=2 * i)
        entries += pair
        held.append(h)
    logits = _latent.lm_head(params, x, cfg, rows)
    return (logits, jnp.stack(entries),
            jnp.stack([h.counts for h in held]),
            jnp.stack([h.zero_pairs for h in held]),
            jnp.stack([h.routed_pairs for h in held]))


def _positions(tokens):
    b, s = tokens.shape
    return jnp.broadcast_to(jnp.arange(s, dtype=jnp.int32), (b, s))


def forward_full(params, tokens, cfg: ShortcutMoEConfig,
                 absorbed: bool = False):
    """Whole sequences ``[b, s]`` from an empty cache; ``absorbed``
    attends over the block's own entries in the decode's form instead of
    rebuilding keys.  Returns what :func:`_layers` does."""
    pos = _positions(tokens)

    def attend(layer, q_nope, q_rope, entry, ap):
        if absorbed:
            return _latent.mla_absorbed_attention(q_nope, q_rope, entry,
                                                  pos, ap, cfg.mla)
        return _latent.mla_rebuilt_attention(q_nope, q_rope, entry, ap,
                                             cfg.mla)

    return _layers(params, tokens, pos, cfg, attend, None)


def prefill_step(params, tokens, n_valid, cfg: ShortcutMoEConfig):
    """A padded prompt ``[1, bucket]`` from an empty cache (see
    ``latent_moe.prefill_step``, the last valid row's logits alone):
    padding reaches no expert, real or zero-compute."""
    pos = _positions(tokens)

    def attend(layer, q_nope, q_rope, entry, ap):
        return _latent.mla_rebuilt_attention(q_nope, q_rope, entry, ap,
                                             cfg.mla)

    return _layers(params, tokens, pos, cfg, attend,
                   pos < n_valid[:, None], rows=n_valid - 1)


def decode_step(params, tokens, lengths, store, table,
                cfg: ShortcutMoEConfig):
    """One token a slot over the paged store through the latent
    family's attention (``latent_moe.decode_attend``: the paged kernel on
    the TPU, the gathered rows elsewhere), two cache layers a decoder
    layer.  Returns ``(logits [slots, vocab], entries [cache layers,
    slots, width], counts [layers, held], zero_pairs [layers],
    routed_pairs [layers])``."""
    attend, pos = _latent.decode_attend(lengths, store, table, cfg.mla)
    logits, entries, *counted = _layers(params, tokens[:, None], pos, cfg,
                                        attend, lengths[:, None] >= 0)
    return (logits[:, 0], entries[:, :, 0], *counted)


# -- what the serving engine asks ---------------------------------------------

class ShortcutMoEServing(LatentMoEServing):
    """The latent family's one store with two layers of it a decoder
    layer; the engine, the scheduler and the cache manager see no
    difference."""

    decode_step = staticmethod(decode_step)
    prefill_step = staticmethod(prefill_step)

    def identity(self) -> dict:
        c = self.cfg
        return {"family": "shortcut_moe", "vocab_size": c.vocab_size,
                "hidden_size": c.hidden_size, "layers": c.num_layers,
                "cache_layers": c.cache_layers,
                "heads": c.num_attention_heads,
                "q_lora_rank": c.q_lora_rank,
                "kv_lora_rank": c.kv_lora_rank,
                "qk_dims": [c.qk_nope_head_dim, c.qk_rope_head_dim,
                            c.v_head_dim],
                "mla_scale": [c.mla_scale_q_lora, c.mla_scale_kv_lora],
                "widths": [c.ffn_hidden_size, c.expert_ffn_hidden_size],
                "experts": [c.n_routed_experts, c.zero_expert_num,
                            c.experts_held, c.expert_offset, c.moe_topk],
                "routed_scaling_factor": c.routed_scaling_factor,
                "rope_theta": c.rope_theta,
                "max_seq_len": c.max_seq_len,
                "dtype": jnp.dtype(c.dtype).name}

    def observe_decode(self, extras) -> None:
        """The held experts' counters, then the pairs that cost nothing
        and all pairs routed."""
        counts, zero, routed = jax.device_get(tuple(extras))  # one wait
        super().observe_decode((counts,))
        _M_MOE_ZERO.inc(int(zero.sum()))
        _M_MOE_ROUTED.inc(int(routed.sum()))
