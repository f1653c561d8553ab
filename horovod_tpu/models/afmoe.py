"""The ``afmoe`` decoder (``arcee-ai/Trinity-Large-Preview``), for serving.

Gated grouped-query attention under a published LIST ``layer_types`` of
``sliding_attention`` and ``full_attention`` layers, four RMS norms a layer
(a sandwich: one before and one after each branch), leading dense layers
and then expert layers with one shared expert (docs/inference.md "Window
and full layers in pages").  With ``x`` the float32 residual stream:

* ``x_0 = sqrt(hidden_size) E[token]`` (``mup_enabled``).
* ``h = norm_in(x)``; ``[q | k | v | g] = h W_in`` (``heads``, ``kv_heads``,
  ``kv_heads`` and ``heads`` of ``head_dim``); ``q`` and ``k`` RMS-normed
  over each head with ONE learned weight each, THEN rotated (rotate-half,
  the whole head, ``rope_theta``) on a sliding layer and NOT on a full
  one; causal softmax of ``q . k / sqrt(head_dim)``, on a sliding layer
  over the keys ``j`` with ``i - j < sliding_window``; ``a = (softmax v) *
  sigmoid(g)``; ``x += norm_post_attn(a W_o)``.
* ``h = norm_pre_mlp(x)``; a dense layer (the first ``num_dense_layers``)
  a SwiGLU of ``intermediate_size``; an expert layer the shared expert
  plus the chosen experts of ``moe_intermediate_size``, chosen by
  :func:`~horovod_tpu.parallel.expert.route_sigmoid_bias_top_k`;
  ``x += norm_post_mlp(m)``.
* a last norm and an untied head.

One chip's share of an expert-parallel group: ``experts_held`` of the
router's ``num_experts`` outputs from ``expert_offset``
(:func:`~horovod_tpu.parallel.expert.moe_layer_held`: what the absent
experts would add is left out), attention, shared expert, router and dense
layers whole.

This is a SIBLING of ``models/mamba2_hybrid.py`` and of ``models/
latent_moe.py``, chosen by the published keys (``model_type``,
``layer_types``).  Shared with the first: the RMS norm and the plain
grouped-query ``attend_view``; with the second: the held expert layer and
its counters.

**The cache**: two layer GROUPS of the paged store (serving/kv_cache.py):
``full`` (the full layers: every position) and ``window`` (the sliding
layers: a ring of ``ceil(window / page) + 1`` pages a slot).  On the TPU
the decode program attends a group's pages where they lie, through a
kernel that walks the group's page table for the slots alive
(``ops/gqa_paged_attention.py``: the ring is its mask's).  Its twin
elsewhere is the plain thing (:func:`gathered_attend`): a slot's table row
of the group gathered whole and attended under the kernel's own mask;
:func:`paged_kernel_runs` says which, from the backend.  The store is the
same four arrays on every backend.
"""

from __future__ import annotations

from dataclasses import dataclass
from functools import partial

import jax
import jax.numpy as jnp
import numpy as np

from ..memory.planner import ring_entries
from ..ops import gqa_paged_attention as _paged
from ..ops.flash_attention import gqa_window_attention, kernel_runs
from ..parallel.expert import (moe_layer_held, route_sigmoid_bias_top_k,
                               swiglu)
from .hybrid_ssm import (_M_SHARED_KV, _M_WINDOW, PREFILL_Q_BLOCK, _dot,
                         _masked_exp)
from .latent_moe import LatentMoEServing
from .mamba2_hybrid import attend_view, rms_norm

SLIDING, FULL = "sliding_attention", "full_attention"
# A test's: run the prompt's flash kernel in the Pallas interpreter.
FLASH_INTERPRET = False
# ``ops/gqa_paged_attention.py``'s ``interpret``: None is the rule (the
# kernel on the TPU, the gathered rows elsewhere); a test sets True to run
# the kernel in the Pallas interpreter, before the engine builds its programs.
PAGED_INTERPRET = None


@dataclass(frozen=True)
class AfmoeConfig:
    """The published keys under their published names; ``experts_held``
    and ``expert_offset`` say which of the router's ``num_experts`` outputs
    live here."""
    vocab_size: int = 200192
    hidden_size: int = 3072
    intermediate_size: int = 12288
    moe_intermediate_size: int = 3072
    num_hidden_layers: int = 60
    num_dense_layers: int = 6
    layer_types: tuple = tuple(
        FULL if l % 4 == 3 else SLIDING for l in range(60))
    num_attention_heads: int = 48
    num_key_value_heads: int = 8
    head_dim: int = 128
    sliding_window: int = 4096
    rope_theta: float = 10000.0
    rms_norm_eps: float = 1e-5
    num_experts: int = 256
    num_experts_per_tok: int = 4
    num_shared_experts: int = 1
    route_norm: bool = True
    route_scale: float = 2.448
    mup_enabled: bool = True
    max_position_embeddings: int = 262144
    experts_held: int = 256
    expert_offset: int = 0
    dtype: object = jnp.bfloat16
    # Read by nothing since the decode's twin gathers whole table rows (it
    # was a chunk of the gathered view); taken because the benchmark's
    # builder hands it on from a fixture that still names it.
    decode_chunk_tokens: int = 256

    def __post_init__(self):
        object.__setattr__(self, "layer_types", tuple(self.layer_types))
        if (len(self.layer_types) != self.num_hidden_layers
                or set(self.layer_types) - {SLIDING, FULL}):
            raise ValueError(f"layer_types names {SLIDING!r} or {FULL!r} "
                             f"for each of num_hidden_layers layers")
        if FULL not in self.layer_types:
            raise ValueError("the paged store's first group is the full "
                             "layers': the model has none")
        if self.num_attention_heads % self.num_key_value_heads:
            raise ValueError("query heads share key/value heads in whole "
                             "groups")
        if self.num_shared_experts != 1:
            raise ValueError("the expert layer is written for one shared "
                             "expert")
        if not 0 < self.num_dense_layers < self.num_hidden_layers:
            raise ValueError("leading dense layers, then expert layers")

    @property
    def max_seq_len(self) -> int:
        return self.max_position_embeddings

    @property
    def q_width(self) -> int:
        return self.num_attention_heads * self.head_dim

    @property
    def kv_width(self) -> int:
        return self.num_key_value_heads * self.head_dim

    @property
    def attention_multiplier(self) -> float:
        """The scores' scale, under the name the sibling families' configs
        publish it by."""
        return self.head_dim ** -0.5

    @property
    def embedding_multiplier(self) -> float:
        return self.hidden_size ** 0.5 if self.mup_enabled else 1.0

    def serving_model(self) -> "AfmoeServing":
        return AfmoeServing(self)


# -- parameters ---------------------------------------------------------------

def init_afmoe(key, cfg: AfmoeConfig) -> dict:
    """Parameter pytree, ``layers`` a LIST (one dict a layer).  Projections
    and the embedding normal 0.02; the norms before a branch 1, the norms
    AFTER a branch ``(2 layers) ** -0.5`` (the sandwich scaled by depth:
    every branch joins the stream at that size); the query and key norms'
    weights uniform in 0.5..1.5 and the router's bias normal 0.005 (a
    token in four has a choice changed by it; ten times that unbalances the
    experts' load), so that neither is a value nothing can see."""
    d, hd = cfg.hidden_size, cfg.head_dim
    qw, kvw = cfg.q_width, cfg.kv_width
    f, fe, held = (cfg.intermediate_size, cfg.moe_intermediate_size,
                   cfg.experts_held)
    dt = cfg.dtype
    std = 0.02
    post = (2.0 * cfg.num_hidden_layers) ** -0.5
    keys = iter(jax.random.split(key, 3 + 12 * cfg.num_hidden_layers))

    def w(shape, scale=std, dtype=dt):
        return (jax.random.normal(next(keys), shape, jnp.float32)
                * scale).astype(dtype)

    def head_norm():
        return (0.5 + jax.random.uniform(next(keys), (hd,), jnp.float32)
                ).astype(dt)

    def ffn(width, *lead):
        return {"w_gate": w((*lead, d, width)), "w_up": w((*lead, d, width)),
                "w_down": w((*lead, width, d))}

    def layer(i):
        lp = {"norm_in": jnp.ones((d,), dt),
              "attn": {"w_in": w((d, 2 * qw + 2 * kvw)), "w_o": w((qw, d)),
                       "q_norm": head_norm(), "k_norm": head_norm()},
              "norm_post_attn": jnp.full((d,), post, dt),
              "norm_pre_mlp": jnp.ones((d,), dt),
              "norm_post_mlp": jnp.full((d,), post, dt)}
        if i < cfg.num_dense_layers:
            lp["mlp"] = ffn(f)
        else:
            lp["moe"] = {"router": w((d, cfg.num_experts)),
                         "bias": w((cfg.num_experts,), 0.005, jnp.float32),
                         **ffn(fe, held), "shared": ffn(fe)}
        return lp

    return {"embed": w((cfg.vocab_size, d)),
            "layers": [layer(i) for i in range(cfg.num_hidden_layers)],
            "norm_f": jnp.ones((d,), dt),
            "unembed": w((d, cfg.vocab_size))}


# -- pieces -------------------------------------------------------------------

def rope(x, pos, cfg: AfmoeConfig):
    """Rotate-half over the whole head: ``x [.., n, heads, hd]`` float32 at
    positions ``pos [.., n]``; pairs are ``(i, i + hd / 2)``."""
    half = cfg.head_dim // 2
    inv = cfg.rope_theta ** (-np.arange(half, dtype=np.float64) / half)
    ang = pos.astype(jnp.float32)[..., None, None] * jnp.asarray(
        inv, jnp.float32)
    cos, sin = jnp.cos(ang), jnp.sin(ang)
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin],
                           axis=-1)


def project(h, ap, pos, sliding: bool, cfg: AfmoeConfig):
    """``h [n, d]`` (normed) at positions ``pos [n]``: ``(q [n, q_width],
    k, v [n, kv_width], gate [n, q_width] float32)``, queries and keys
    normed a head and, on a sliding layer, rotated."""
    n = h.shape[0]
    qw, kvw = cfg.q_width, cfg.kv_width
    both = _dot(h, ap["w_in"])
    q = both[:, :qw].reshape(n, -1, cfg.head_dim)
    k = both[:, qw:qw + kvw].reshape(n, -1, cfg.head_dim)
    q = rms_norm(q, ap["q_norm"], cfg.rms_norm_eps, jnp.float32)
    k = rms_norm(k, ap["k_norm"], cfg.rms_norm_eps, jnp.float32)
    if sliding:
        q, k = rope(q, pos, cfg), rope(k, pos, cfg)
    return (q.astype(cfg.dtype).reshape(n, qw),
            k.astype(cfg.dtype).reshape(n, kvw),
            both[:, qw + kvw:qw + 2 * kvw].astype(cfg.dtype),
            both[:, qw + 2 * kvw:])


def gate_out(a, gate, ap, cfg: AfmoeConfig):
    """``(a * sigmoid(gate)) W_o``: the gate on the attention's output,
    before the output projection."""
    return _dot((a.astype(jnp.float32) * jax.nn.sigmoid(gate)
                 ).astype(cfg.dtype), ap["w_o"])


def flash_runs(interpret=None) -> bool:
    """Whether a prompt's attention goes through the streaming flash
    kernel: on the TPU, or where a test asks for the interpreter (the
    kernels' one rule; ``interpret``: a sibling family's own switch, else
    this module's); elsewhere :func:`attend_block`, its twin."""
    return kernel_runs((FLASH_INTERPRET if interpret is None
                        else interpret) or None)


def attend_prompt(q, k, v, cfg: AfmoeConfig, window: int = 0,
                  interpret=None):
    """Grouped-query attention of one sequence over ITSELF, causal (and
    within ``window`` keys, itself included, where given).  ``q [t, heads
    * hd]``, ``k``/``v`` ``[t, kv_heads * hd]``; query head ``i`` reads
    key/value head ``i // (heads / kv_heads)``.  On the TPU the scores
    never leave the chip's fast memory
    (``ops/flash_attention.gqa_window_attention``: blocks of keys wholly
    outside the window or ahead of the queries are skipped); in blocks of
    plain ``jnp`` they cross HBM several times, 48 heads x t x t float32
    a pass (517 ms of a 4096-token prompt: PERF.md section 6, PR 41)."""
    if interpret is None:
        interpret = FLASH_INTERPRET
    if not flash_runs(interpret):
        return attend_block(q, k, v, cfg, window)
    t, hd = q.shape[0], cfg.head_dim
    heads = lambda x: x.reshape(t, -1, hd).transpose(1, 0, 2)
    o = gqa_window_attention(heads(q), heads(k), heads(v), window=window,
                             sm_scale=cfg.attention_multiplier,
                             interpret=bool(interpret))
    return o.transpose(1, 0, 2).reshape(t, -1)


def attend_block(q, k, v, cfg: AfmoeConfig, window: int = 0):
    """:func:`attend_prompt`'s twin off the TPU: ``PREFILL_Q_BLOCK``
    queries at a time against the keys they can see."""
    t = q.shape[0]
    hd, dt = cfg.head_dim, q.dtype
    g = cfg.num_key_value_heads
    q4 = q.reshape(t, g, cfg.num_attention_heads // g, hd)
    k3, v3 = k.reshape(t, g, hd), v.reshape(t, g, hd)
    qb = min(PREFILL_Q_BLOCK, t)
    outs = []
    for lo in range(0, t, qb):
        hi = min(lo + qb, t)
        klo = max(0, lo - window + 1) if window else 0
        scores = jnp.einsum(
            "qgrd,kgd->grqk", q4[lo:hi], k3[klo:hi],
            preferred_element_type=jnp.float32) * cfg.attention_multiplier
        q_pos = jnp.arange(lo, hi)[:, None]
        k_pos = jnp.arange(klo, hi)[None, :]
        mask = k_pos <= q_pos
        if window:
            mask = mask & (k_pos > q_pos - window)
        m = jnp.max(jnp.where(mask, scores, -jnp.inf), axis=-1,
                    keepdims=True)
        p = _masked_exp(scores, mask, m)
        p = p / jnp.sum(p, axis=-1, keepdims=True)
        outs.append(jnp.einsum("grqk,kgd->qgrd", p.astype(dt), v3[klo:hi],
                               preferred_element_type=jnp.float32))
    return jnp.concatenate(outs, axis=0).astype(dt).reshape(t, -1)


def feed_forward(x, lp, token_mask, cfg: AfmoeConfig):
    """``x + norm_post_mlp(m)``; returns ``(x, counts [held] or None)``."""
    f32 = jnp.float32
    h = rms_norm(x, lp["norm_pre_mlp"], cfg.rms_norm_eps, cfg.dtype)
    counts = None
    if "mlp" in lp:
        with jax.named_scope("swiglu"):
            m = swiglu(h, lp["mlp"]["w_gate"], lp["mlp"]["w_up"],
                       lp["mlp"]["w_down"])
    else:
        e = lp["moe"]
        held = moe_layer_held(
            h, e, num_experts=cfg.num_experts,
            expert_offset=cfg.expert_offset, top_k=cfg.num_experts_per_tok,
            token_mask=token_mask,
            routing=partial(route_sigmoid_bias_top_k, router=e["router"],
                            bias=e["bias"], top_k=cfg.num_experts_per_tok,
                            routed_scale=cfg.route_scale,
                            norm_topk=cfg.route_norm))
        m, counts = held.out, held.counts
    return x + rms_norm(m, lp["norm_post_mlp"], cfg.rms_norm_eps, f32), counts


def head(x, params, cfg: AfmoeConfig):
    return _dot(rms_norm(x, params["norm_f"], cfg.rms_norm_eps, cfg.dtype),
                params["unembed"])


def group_layers(cfg: AfmoeConfig) -> tuple:
    """``(full layers, sliding layers)``: each kind's indices into
    ``layer_types``, in order: a layer's place in its cache group."""
    kinds = cfg.layer_types
    return (tuple(i for i, k in enumerate(kinds) if k == FULL),
            tuple(i for i, k in enumerate(kinds) if k == SLIDING))


# -- whole sequences ----------------------------------------------------------

def prefill_step(params, tokens, n_valid, cfg: AfmoeConfig,
                 last_only: bool = True):
    """A padded prompt ``tokens [bucket]`` from an empty cache; positions
    ``>= n_valid`` are padding, which reaches no expert.  With
    ``last_only`` the head runs for token ``n_valid - 1`` alone.

    Returns ``(logits [vocab] of the last real token (or [bucket, vocab]),
    k [layers, bucket, kv_width], v, counts [expert layers, held])``."""
    t = tokens.shape[0]
    f32 = jnp.float32
    pos = jnp.arange(t, dtype=jnp.int32)
    live = pos < n_valid
    x = params["embed"][tokens].astype(f32) * cfg.embedding_multiplier
    ks, vs, counts = [], [], []
    for kind, lp in zip(cfg.layer_types, params["layers"]):
        with jax.named_scope("gqa_attention"):
            h = rms_norm(x, lp["norm_in"], cfg.rms_norm_eps, cfg.dtype)
            q, k, v, gate = project(h, lp["attn"], pos, kind == SLIDING, cfg)
            a = attend_prompt(q, k, v, cfg,
                              cfg.sliding_window if kind == SLIDING else 0)
            x = x + rms_norm(gate_out(a, gate, lp["attn"], cfg),
                             lp["norm_post_attn"], cfg.rms_norm_eps, f32)
        ks.append(k)
        vs.append(v)
        x, n = feed_forward(x, lp, live, cfg)
        if n is not None:
            counts.append(n)
    if last_only:
        x = jax.lax.dynamic_slice_in_dim(x, n_valid - 1, 1, axis=0)
    logits = head(x, params, cfg)
    return ((logits[0] if last_only else logits), jnp.stack(ks),
            jnp.stack(vs), jnp.stack(counts))


def forward_full(params, tokens, cfg: AfmoeConfig):
    """Every position of one sequence ``tokens [t]``: ``logits [t,
    vocab]``."""
    return prefill_step(params, tokens, jnp.int32(tokens.shape[0]), cfg,
                        last_only=False)[0]


# -- the decode's attention over the paged groups -----------------------------

def paged_kernel_runs() -> bool:
    """Whether the decode program attends through the kernel that walks
    the groups' page tables: read off the backend the program is built for
    (``PAGED_INTERPRET`` is a test's), nothing a user sets."""
    return _paged.kernel_runs(PAGED_INTERPRET)


def paged_attend(lengths, groups, cfg: AfmoeConfig, interpret=None):
    """The decode step's ``attend`` through the kernel
    (``ops/gqa_paged_attention.py``): the live slots' own pages of a
    layer's group, read where they lie, the ring in the kernel's mask.
    ``groups``: ``{kind: (table, window, k_pages, v_pages)}``."""
    order, n_live = _paged.live_first(lengths)

    def attend(kind, layer, q, k_self, v_self):
        table, window, k_pages, v_pages = groups[kind]
        return _paged.gqa_paged_attention(
            q, k_self, v_self, k_pages, v_pages, table, lengths, layer,
            heads=cfg.num_attention_heads, scale=cfg.attention_multiplier,
            window=window, order=order, n_live=n_live, interpret=interpret)

    return attend


def gathered_attend(lengths, groups, cfg: AfmoeConfig):
    """The kernel's twin off the TPU, the plainest thing that is right: a
    slot's table row gathered in table order, every entry of it, and
    :func:`attend_view` over that under the kernel's mask
    (``gqa_paged_attention.gathered_rows``)."""
    cached = jnp.clip(lengths, 0, None)

    def attend(kind, layer, q, k_self, v_self):
        table, window, k_pages, v_pages = groups[kind]
        return attend_view(q, k_self, v_self, *_paged.gathered_rows(
            cached, table, k_pages, v_pages, layer, window), cfg)

    return attend


def decode_step(params, tokens, lengths, stores, table, cfg: AfmoeConfig):
    """One token a slot.  ``tokens [slots]``; ``lengths [slots]``: the
    position of the new token, the count of cached ones (-1: an idle
    slot); ``stores = (full_k, full_v [full layers, pages, page,
    kv_width], win_k, win_v [sliding layers, ...])``; ``table [slots,
    pages a slot + ring entries]``, the two groups' tables side by side.

    Each layer attends its own group's pages of its own paged layer: on
    the TPU where they lie (:func:`paged_attend`), elsewhere over a
    gathered table row (:func:`gathered_attend`);
    :func:`paged_kernel_runs` says which, asked here and nowhere else.
    The new token's own key and value are not in the store; the caller
    writes them to both groups at the end.

    Returns ``(logits [slots, vocab], k [layers, slots, kv_width], v,
    counts [expert layers, held])``."""
    full_k, full_v, win_k, win_v = stores
    f32 = jnp.float32
    ring = ring_entries(cfg.sliding_window, full_k.shape[2])
    pps = table.shape[1] - ring
    cached = jnp.clip(lengths, 0, None)
    groups = {FULL: (table[:, :pps], 0, full_k, full_v),
              SLIDING: (table[:, pps:], cfg.sliding_window, win_k, win_v)}
    attend = (paged_attend(lengths, groups, cfg, PAGED_INTERPRET)
              if paged_kernel_runs() else
              gathered_attend(lengths, groups, cfg))

    x = params["embed"][tokens].astype(f32) * cfg.embedding_multiplier
    new_k, new_v, counts = [], [], []
    seen = {FULL: 0, SLIDING: 0}
    for kind, lp in zip(cfg.layer_types, params["layers"]):
        with jax.named_scope("gqa_attention"):
            h = rms_norm(x, lp["norm_in"], cfg.rms_norm_eps, cfg.dtype)
            q, k, v, gate = project(h, lp["attn"], cached, kind == SLIDING,
                                    cfg)
            a = attend(kind, seen[kind], q, k, v)
            x = x + rms_norm(gate_out(a, gate, lp["attn"], cfg),
                             lp["norm_post_attn"], cfg.rms_norm_eps, f32)
        seen[kind] += 1
        new_k.append(k)
        new_v.append(v)
        x, n = feed_forward(x, lp, lengths >= 0, cfg)
        if n is not None:
            counts.append(n)
    return (head(x, params, cfg), jnp.stack(new_k), jnp.stack(new_v),
            jnp.stack(counts))


# -- what the serving engine asks ---------------------------------------------

class AfmoeServing:
    """The serving protocol (serving/models.py) for this model: two paged
    layer groups, the same four arrays on every backend."""

    speculative = False        # no verify / propose programs
    tensor_parallel = False
    tensor_parallel_why = ("its window group is not written for a "
                           "sharded model axis")
    prefix_cache = False
    prefix_cache_why = ("a window group's pages are a ring written over in "
                        "place: a cached prefix page of the full group has "
                        "no window-group page beside it (the shared-prefix "
                        "index is not written for layer groups)")
    slot_state = False         # the prefill need not know its slot

    def __init__(self, cfg: AfmoeConfig) -> None:
        self.cfg = cfg
        self.full, self.sliding = group_layers(cfg)

    def identity(self) -> dict:
        c = self.cfg
        return {"family": "afmoe", "vocab_size": c.vocab_size,
                "hidden_size": c.hidden_size,
                "widths": [c.intermediate_size, c.moe_intermediate_size],
                "layer_types": list(c.layer_types),
                "dense_layers": c.num_dense_layers,
                "heads": [c.num_attention_heads, c.num_key_value_heads,
                          c.head_dim],
                "sliding_window": c.sliding_window,
                "rope_theta": c.rope_theta,
                "experts": [c.num_experts, c.experts_held, c.expert_offset,
                            c.num_experts_per_tok],
                "route": [c.route_norm, c.route_scale],
                "mup_enabled": c.mup_enabled,
                "max_seq_len": c.max_seq_len,
                "dtype": jnp.dtype(c.dtype).name}

    def cache_entry(self) -> dict:
        """Keys and values, in two layer groups (the window group's table
        row a ring of ``ring_entries`` pages a slot).  The decode reads
        the pages in place and asks for no room to gather into."""
        c = self.cfg
        return {"n_layers": len(self.full),
                "n_heads": c.num_key_value_heads, "head_dim": c.head_dim,
                "widths": (c.kv_width,) * 2,
                "groups": ({"name": "full", "n_layers": len(self.full)},
                           {"name": "window", "n_layers": len(self.sliding),
                            "window": c.sliding_window})}

    def decode_view(self, lengths, page_size, pages_per_slot) -> float:
        """Positions a slot a layer the decode program reads of the paged
        stores at these (host) lengths: each group's entries in use of the
        live slots, whole pages (what the kernel copies; its twin gathers
        the whole rows and masks the rest), weighted by the groups'
        layers, over the slots."""
        full = _paged.tokens_read(lengths, pages_per_slot, page_size)
        window = _paged.tokens_read(
            lengths, ring_entries(self.cfg.sliding_window, page_size),
            page_size)
        n_f, n_w = len(self.full), len(self.sliding)
        return ((n_f * full + n_w * window) / (n_f + n_w) / len(lengths))

    def observe_launch(self, lengths) -> None:
        """Count what a decode iteration attends, from the host's lengths
        of its launch: every position up to the new token's own in a full
        layer, at most the window in a sliding one."""
        seen = lengths[lengths >= 0].astype(np.int64) + 1
        _M_SHARED_KV.inc(int(seen.sum()))
        _M_WINDOW.inc(int(np.minimum(seen, self.cfg.sliding_window).sum()))

    # The held experts' counters, fed as the latent family feeds them.
    observe_decode = LatentMoEServing.observe_decode

    def _by_group(self, rows):
        """``rows [layers, ...]`` as ``(full layers', sliding layers')``."""
        return (rows[jnp.asarray(self.full)],
                rows[jnp.asarray(self.sliding)])

    def decode(self, params, pages, table, lengths, tokens):
        full_k, full_v, win_k, win_v = pages
        logits, k, v, counts = decode_step(
            params, tokens, lengths, pages, table, self.cfg)
        # One row a slot in every layer of both groups, written where it
        # lies (see DenseLM.decode): the full group at the position's own
        # page, the window group at that page's ring entry; an idle slot's
        # rows land in the trash pages.
        ps = full_k.shape[2]
        ring = ring_entries(self.cfg.sliding_window, ps)
        pps = table.shape[1] - ring
        pos = jnp.clip(lengths, 0, None)
        b = tokens.shape[0]
        at_page, off = pos // ps, pos % ps
        page_f = table[jnp.arange(b), at_page]
        page_w = table[jnp.arange(b), pps + at_page % ring]
        (k_f, k_w), (v_f, v_w) = self._by_group(k), self._by_group(v)
        zero = jnp.zeros((), jnp.int32)
        for slot in range(b):
            at = (zero, page_f[slot], off[slot], zero)
            full_k = jax.lax.dynamic_update_slice(
                full_k, k_f[:, slot][:, None, None, :], at)
            full_v = jax.lax.dynamic_update_slice(
                full_v, v_f[:, slot][:, None, None, :], at)
            at = (zero, page_w[slot], off[slot], zero)
            win_k = jax.lax.dynamic_update_slice(
                win_k, k_w[:, slot][:, None, None, :], at)
            win_v = jax.lax.dynamic_update_slice(
                win_v, v_w[:, slot][:, None, None, :], at)
        return (logits, counts), (full_k, full_v, win_k, win_v)

    def prefill(self, params, pages, table_row, start, n_valid, tokens):
        """``start`` is always 0 here (``prefix_cache`` is off).  The full
        group takes every page of the prompt; the window group the last
        ``ring`` pages' worth, each into its ring entry."""
        full_k, full_v, win_k, win_v = pages
        ps, bucket = full_k.shape[2], tokens.shape[1]
        ring = ring_entries(self.cfg.sliding_window, ps)
        pps = table_row.shape[1] - ring
        logits, k, v, _ = prefill_step(params, tokens[0], n_valid[0],
                                       self.cfg)
        (k_f, k_w), (v_f, v_w) = self._by_group(k), self._by_group(v)
        # A page at a time, written where it lies; pages past the prompt
        # are not mapped: their rows land in a trash page.
        rows = min(ps, bucket)
        n_pages = max(1, bucket // ps)
        zero = jnp.zeros((), jnp.int32)
        top = (n_valid[0] - 1) // ps

        def page_rows(x, j):
            return jax.lax.dynamic_slice_in_dim(x, j * ps, rows,
                                                axis=1)[:, None]

        def write_full(j, kv):
            at = (zero, table_row[0, j], zero, zero)
            return tuple(jax.lax.dynamic_update_slice(s, page_rows(x, j), at)
                         for s, x in zip(kv, (k_f, v_f)))

        def write_ring(e, kv):
            # The newest logical page congruent to the entry; an entry
            # the prompt does not reach is unmapped.
            j = jnp.clip(top - (top - e) % ring, 0, n_pages - 1)
            at = (zero, table_row[0, pps + e], zero, zero)
            return tuple(jax.lax.dynamic_update_slice(s, page_rows(x, j), at)
                         for s, x in zip(kv, (k_w, v_w)))

        full_k, full_v = jax.lax.fori_loop(0, n_pages, write_full,
                                           (full_k, full_v))
        win_k, win_v = jax.lax.fori_loop(0, min(ring, n_pages), write_ring,
                                         (win_k, win_v))
        return (logits,), (full_k, full_v, win_k, win_v)
