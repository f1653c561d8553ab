"""Decoder-hybrid-decoder, for serving.

The block stack of ``microsoft/Phi-4-mini-flash-reasoning`` (Ren et al.,
arXiv:2507.06607; docs/inference.md "State-space and windowed layers"):
pre-norm residual blocks with float LayerNorm, a SwiGLU MLP in every
layer, no positional encoding of any kind, a tied head, and FIVE kinds of
mixer in one stack (:func:`layer_kinds`):

* ``ssm``: a Mamba-1 selective state-space layer: a depthwise causal
  convolution, then ``S_t = exp(dt_t A) S_{t-1} + (dt_t x_t) B_t^T``,
  ``y_t = S_t C_t + D x_t``, gated by ``silu(z_t)``.  What it keeps of a
  sequence is CONSTANT: the ``[d_state, d_inner]`` float32 state and the
  last ``d_conv - 1`` inputs of the convolution.
* ``window``: differential attention over the last ``sliding_window``
  positions; its keys and values are a layer of the cache's WINDOW group,
  whose table row is a ring of pages a slot.
* ``full``: differential attention over every position: its keys and
  values are the cache's FULL group, one paged per-token store of one
  layer.
* ``gmu``: a gated memory unit, ``W_out (m_t * silu(W_in u_t))``, ``m`` the
  last first-half state-space layer's output before its gate.  No state.
* ``cross``: differential attention with queries of its own onto the
  ``full`` layer's keys and values.  No cache of its own.

The first half alternates ``ssm`` and ``window``; the second half begins
with one more ``ssm`` (which hands on ``m``), then ``full``, then ``gmu``
and ``cross`` alternate.  So a prefill runs the layers up to ``full``
over the whole prompt and everything after it for the LAST token only:
nothing after ``full`` leaves anything behind for later tokens.

Differential attention: query heads pair up as ``(q1, q2)``, key heads as
``(k1, k2)``, value heads as one double-width ``V``; a pair's output is
``P(q1 k1) V - lambda P(q2 k2) V``, RMS-normed over the double width and
scaled by ``1 - lambda_init`` (:func:`attend_block`, :func:`attend_view`).

:class:`HybridSSMServing` is the serving protocol (serving/models.py):
two paged layer groups of the engine's cache manager (the one ``full``
layer; the window layers, a ring of pages a slot) and, beside them, the
per-slot stores it owns (``slot_stores``): the state and the tails.  The
decode attends both groups where they lie, through
``ops/gqa_paged_attention.py`` with the differential head map
(:func:`paged_attend`), on the TPU; its twin elsewhere gathers a slot's
table row and attends that (:func:`gathered_attend`).
"""

from __future__ import annotations

import math
from dataclasses import dataclass

import jax
import jax.numpy as jnp
import numpy as np

from .. import telemetry as _telemetry
from ..memory.planner import ring_entries
from ..ops import gqa_paged_attention as _paged
from ..ops.ssm_scan import ssm_scan, ssm_step

_M_SHARED_KV = _telemetry.counter(
    "serving.shared_kv_tokens", "cached positions of the paged store the "
    "decode iterations attended, summed over slots and iterations (by "
    "the host's lengths; every layer that reads the store attends them "
    "once)")
_M_WINDOW = _telemetry.counter(
    "serving.window_tokens", "positions of the window stores the decode "
    "iterations attended, summed over slots and iterations (at most the "
    "window a slot)")
_M_STATE_BYTES = _telemetry.gauge(
    "serving.state_bytes", "bytes of per-slot recurrent state (the "
    "state-space state and the convolution's tail, all layers and slots)")

# Queries of one block of the prefill's attention.
PREFILL_Q_BLOCK = 256
# A test's: run the decode's paged kernel in the Pallas interpreter.
PAGED_INTERPRET = None


@dataclass(frozen=True)
class HybridSSMConfig:
    """The published keys under their published names; ``d_state`` ..
    ``dt_rank`` are the state-space layer's sizes, which the published
    config leaves to the family's defaults."""
    vocab_size: int = 200064
    hidden_size: int = 2560
    intermediate_size: int = 10240
    num_hidden_layers: int = 32
    num_attention_heads: int = 40
    num_key_value_heads: int = 20
    sliding_window: int = 512
    layer_norm_eps: float = 1e-5
    mb_per_layer: int = 2
    d_state: int = 16
    d_conv: int = 4
    expand: int = 2
    dt_rank: int = 160
    max_position_embeddings: int = 262144
    dtype: object = jnp.bfloat16
    # Read by nothing since the decode attends its pages where they lie
    # (it was a chunk of the gathered view); taken because the benchmark's
    # builder hands it on from a fixture that still names it.
    decode_chunk_tokens: int = 256

    def __post_init__(self):
        if self.mb_per_layer != 2 or self.num_hidden_layers % 4:
            raise ValueError("the layer layout is written for "
                             "mb_per_layer == 2 and a depth that is a "
                             "multiple of 4")
        if (self.num_attention_heads % self.num_key_value_heads
                or self.num_key_value_heads % 2
                or self.num_attention_heads % 2):
            raise ValueError("differential attention pairs query heads "
                             "and key/value heads")

    @property
    def max_seq_len(self) -> int:
        return self.max_position_embeddings

    @property
    def head_dim(self) -> int:
        return self.hidden_size // self.num_attention_heads

    @property
    def d_inner(self) -> int:
        return self.expand * self.hidden_size

    @property
    def kv_width(self) -> int:
        return self.num_key_value_heads * self.head_dim

    def serving_model(self) -> "HybridSSMServing":
        return HybridSSMServing(self)


def layer_kinds(n_layers: int) -> tuple:
    """The mixer of each layer.  With ``h = n_layers / 2``: even ``l <=
    h`` state-space, odd ``l < h`` window, ``l = h + 1`` full, even ``l >
    h`` gated memory, odd ``l > h + 1`` cross."""
    h = n_layers // 2
    kinds = []
    for l in range(n_layers):
        if l % 2 == 0:
            kinds.append("ssm" if l <= h else "gmu")
        else:
            kinds.append("window" if l < h else
                         "full" if l == h + 1 else "cross")
    return tuple(kinds)


def lambda_init(layer: int) -> float:
    return 0.8 - 0.6 * math.exp(-0.3 * layer)


# -- parameters ---------------------------------------------------------------

def init_hybrid_ssm(key, cfg: HybridSSMConfig) -> dict:
    """Parameter pytree: ``layers`` is a LIST, one dict a layer (see
    ``latent_moe.init_latent_moe`` for why not a stack).  ``A_log =
    log(1..d_state)``, ``D = 1``, the ``dt`` bias such that its softplus
    lies in 1e-3..1e-1, convolution weights normal ``d_conv^-0.5``, the
    four lambda vectors normal 0.1, the rest normal 0.02 with the
    residual projections scaled by depth."""
    d, f, di = cfg.hidden_size, cfg.intermediate_size, cfg.d_inner
    n, k, r = cfg.d_state, cfg.d_conv, cfg.dt_rank
    hd = cfg.head_dim
    qw, kvw = cfg.num_attention_heads * hd, cfg.kv_width
    dt = cfg.dtype
    std, res = 0.02, 0.02 / (2 * cfg.num_hidden_layers) ** 0.5
    keys = iter(jax.random.split(key, 2 + 16 * cfg.num_hidden_layers))

    def w(shape, scale):
        return (jax.random.normal(next(keys), shape, jnp.float32)
                * scale).astype(dt)

    def norm(width=d):
        return {"scale": jnp.ones((width,), dt),
                "bias": jnp.zeros((width,), dt)}

    def dt_bias():
        u = jax.random.uniform(next(keys), (di,), jnp.float32)
        step = jnp.exp(u * (math.log(0.1) - math.log(0.001))
                       + math.log(0.001))
        return (step + jnp.log(-jnp.expm1(-step))).astype(dt)

    def lambdas():
        return {name: w((hd,), 0.1) for name in
                ("lambda_q1", "lambda_k1", "lambda_q2", "lambda_k2")}

    def mixer(kind):
        if kind == "ssm":
            return {"w_in": w((d, 2 * di), std),
                    "conv_w": w((k, di), k ** -0.5),
                    "conv_b": w((di,), std),
                    "w_x": w((di, r + 2 * n), std),
                    "w_dt": w((r, di), std), "b_dt": dt_bias(),
                    "A_log": jnp.broadcast_to(
                        jnp.log(jnp.arange(1, n + 1, dtype=jnp.float32)
                                )[:, None], (n, di)).astype(dt),
                    "D": jnp.ones((di,), dt),
                    "w_out": w((di, d), res)}
        if kind == "gmu":
            return {"w_in": w((d, di), std), "w_out": w((di, d), res)}
        if kind == "cross":
            return {"w_q": w((d, qw), std), "b_q": w((qw,), std),
                    "w_o": w((qw, d), res), "b_o": w((d,), std),
                    **lambdas(), "subln": jnp.ones((2 * hd,), dt)}
        return {"w_qkv": w((d, qw + 2 * kvw), std),
                "b_qkv": w((qw + 2 * kvw,), std),
                "w_o": w((qw, d), res), "b_o": w((d,), std),
                **lambdas(), "subln": jnp.ones((2 * hd,), dt)}

    def layer(kind):
        return {"norm1": norm(), "mixer": mixer(kind), "norm2": norm(),
                "mlp": {"w1": w((d, 2 * f), std), "w2": w((f, d), res)}}

    return {"embed": w((cfg.vocab_size, d), std),
            "layers": [layer(kind)
                       for kind in layer_kinds(cfg.num_hidden_layers)],
            "norm_f": norm()}


# -- pieces -------------------------------------------------------------------

def _dot(a, b):
    return jnp.dot(a, b, preferred_element_type=jnp.float32)


def layer_norm(x, p, eps: float, dtype):
    """Computed in float32 whatever comes in, handed on as ``dtype``."""
    xf = x.astype(jnp.float32)
    mu = jnp.mean(xf, axis=-1, keepdims=True)
    var = jnp.mean(jnp.square(xf - mu), axis=-1, keepdims=True)
    y = (xf - mu) * jax.lax.rsqrt(var + eps)
    return (y * p["scale"].astype(jnp.float32)
            + p["bias"].astype(jnp.float32)).astype(dtype)


def mlp(x, lp, cfg: HybridSSMConfig):
    """``x + W2 (silu(g) * u)``, ``[g | u] = W1 LN(x)``."""
    h = layer_norm(x, lp["norm2"], cfg.layer_norm_eps, cfg.dtype)
    g, u = jnp.split(_dot(h, lp["mlp"]["w1"]), 2, axis=-1)
    return x + _dot((jax.nn.silu(g) * u).astype(cfg.dtype), lp["mlp"]["w2"])


def diff_lambda(ap, layer: int):
    f = jnp.float32
    return (jnp.exp(jnp.sum(ap["lambda_q1"].astype(f)
                            * ap["lambda_k1"].astype(f)))
            - jnp.exp(jnp.sum(ap["lambda_q2"].astype(f)
                              * ap["lambda_k2"].astype(f)))
            + lambda_init(layer))


def _combine(o, ap, layer: int, cfg: HybridSSMConfig):
    """``o [..., 2, 2 * head_dim]`` float32, the two softmaxes' outputs of
    a pair: their difference, RMS-normed with the learned scale, times
    ``1 - lambda_init``."""
    a = o[..., 0, :] - diff_lambda(ap, layer) * o[..., 1, :]
    a = a * jax.lax.rsqrt(jnp.mean(a * a, axis=-1, keepdims=True)
                          + cfg.layer_norm_eps)
    return a * ap["subln"].astype(jnp.float32) * (1.0 - lambda_init(layer))


def _masked_exp(scores, mask, m):
    return jnp.where(mask, jnp.exp(scores - m), 0.0)


def attend_block(q, k, v, ap, layer: int, cfg: HybridSSMConfig,
                 window: int = 0):
    """Differential attention of one sequence over ITSELF, causal (and
    within ``window`` keys, itself included, where given), ``PREFILL_Q_
    BLOCK`` queries at a time against the keys they can see.  ``q [t,
    heads * hd]``, ``k``/``v`` ``[t, kv_heads * hd]``.  Returns ``[t,
    heads * hd]``."""
    t = q.shape[0]
    hd, dt = cfg.head_dim, q.dtype
    j = cfg.num_key_value_heads // 2            # key/value pairs
    p_n = cfg.num_attention_heads // 2 // j      # query pairs a kv pair
    q5 = q.reshape(t, j, p_n, 2, hd)
    k4 = k.reshape(t, j, 2, hd)
    v3 = v.reshape(t, j, 2 * hd)
    qb = min(PREFILL_Q_BLOCK, t)
    outs = []
    for lo in range(0, t, qb):
        hi = min(lo + qb, t)
        klo = max(0, lo - window + 1) if window else 0
        scores = jnp.einsum("qjpcd,kjcd->jpcqk", q5[lo:hi], k4[klo:hi],
                            preferred_element_type=jnp.float32) * hd ** -0.5
        q_pos = jnp.arange(lo, hi)[:, None]
        k_pos = jnp.arange(klo, hi)[None, :]
        mask = k_pos <= q_pos
        if window:
            mask = mask & (k_pos > q_pos - window)
        m = jnp.max(jnp.where(mask, scores, -jnp.inf), axis=-1,
                    keepdims=True)
        p = _masked_exp(scores, mask, m)
        p = p / jnp.sum(p, axis=-1, keepdims=True)
        o = jnp.einsum("jpcqk,kjv->qjpcv", p.astype(dt), v3[klo:hi],
                       preferred_element_type=jnp.float32)
        outs.append(_combine(o, ap, layer, cfg))
    return jnp.concatenate(outs, axis=0).astype(dt).reshape(t, -1)


def key_head_of(cfg: HybridSSMConfig) -> np.ndarray:
    """The key head each query head reads: query heads pair up as ``(q1,
    q2)`` within a key/value PAIR's group, ``q1`` onto ``k1``, ``q2`` onto
    ``k2``."""
    heads = np.arange(cfg.num_attention_heads)
    per = cfg.num_attention_heads // (cfg.num_key_value_heads // 2)
    return 2 * (heads // per) + heads % 2


def _lay_queries(q, cfg: HybridSSMConfig):
    """``q [b, heads * hd]`` as ``[b, kv_width, heads]``: a query head laid
    into a column that is zero outside its key head, so that a view is
    contracted in the layout it is stored in (the zeros add nothing)."""
    b = q.shape[0]
    h_n, kv_n = cfg.num_attention_heads, cfg.num_key_value_heads
    lay = jnp.asarray(key_head_of(cfg)[:, None] == np.arange(kv_n)[None, :],
                      q.dtype)
    return jnp.einsum("bhd,hg->bgdh", q.reshape(b, h_n, cfg.head_dim), lay
                      ).reshape(b, kv_n * cfg.head_dim, h_n)


def _own_values(o, cfg: HybridSSMConfig):
    """``o [.., heads, kv_width]``, a head's probabilities times EVERY
    value pair: head ``h`` keeps the block of its own pair."""
    h_n, j = cfg.num_attention_heads, cfg.num_key_value_heads // 2
    own = jnp.asarray((np.arange(h_n) // (h_n // j))[:, None]
                      == np.arange(j)[None, :], jnp.float32)
    return jnp.einsum("...hgv,hg->...hv",
                      o.reshape(*o.shape[:-1], j, 2 * cfg.head_dim), own)


def _finish(o, ap, layer: int, cfg: HybridSSMConfig, dtype):
    """``o [b, heads, 2 hd]`` float32, each head's softmax over its pair's
    values: the pairs combined, ``[b, heads * hd]`` in ``dtype``."""
    b, h_n = o.shape[:2]
    return _combine(o.reshape(b, h_n // 2, 2, 2 * cfg.head_dim), ap, layer,
                    cfg).astype(dtype).reshape(b, -1)


def attend_view(q, k_self, v_self, k_view, v_view, mask, ap, layer: int,
                cfg: HybridSSMConfig):
    """Differential attention of ONE query a row over a view of cached
    keys and values plus the row's own new key and value, which are not
    in the view.  ``q [b, heads * hd]``; ``k_self``/``v_self`` ``[b,
    kv_width]``; ``k_view``/``v_view`` ``[b, n, kv_width]``; ``mask [b,
    n]``: which view rows a row attends (none: it attends itself only).
    Returns ``[b, heads * hd]``."""
    dt = q.dtype
    qbd = _lay_queries(q, cfg)
    scale = cfg.head_dim ** -0.5
    scores = jnp.einsum("bnk,bkh->bhn", k_view, qbd,
                        preferred_element_type=jnp.float32) * scale
    s_self = jnp.einsum("bk,bkh->bh", k_self, qbd,
                        preferred_element_type=jnp.float32) * scale
    mask = mask[:, None, :]
    m = jnp.maximum(jnp.max(jnp.where(mask, scores, -jnp.inf), axis=-1),
                    s_self)
    p = _masked_exp(scores, mask, m[..., None])
    p_self = jnp.exp(s_self - m)
    o = _own_values(jnp.einsum("bhn,bnk->bhk", p.astype(dt), v_view,
                               preferred_element_type=jnp.float32), cfg)
    o = o + p_self[..., None] * _own_values(
        v_self.astype(jnp.float32)[:, None, :], cfg)
    denom = jnp.sum(p, axis=-1) + p_self
    return _finish(o / denom[..., None], ap, layer, cfg, dt)


# -- the decode's attention over the paged groups -----------------------------

def paged_kernel_runs() -> bool:
    """Whether the decode program attends through the kernel that walks
    the groups' page tables: read off the backend the program is built for
    (``PAGED_INTERPRET`` is a test's), nothing a user sets."""
    return _paged.kernel_runs(PAGED_INTERPRET)


def paged_attend(lengths, groups, cfg: HybridSSMConfig, interpret=None):
    """The decode step's ``attend`` through the kernel
    (``ops/gqa_paged_attention.py``): the live slots' own pages of a
    layer's group, read where they lie, the ring in the kernel's mask, the
    heads paired by the kernel's head map; a head's softmax over its
    pair's values comes back float32 and the pairs are combined here.
    ``groups``: ``{kind: (table, window, k_pages, v_pages)}``."""
    order, n_live = _paged.live_first(lengths)
    key_head = key_head_of(cfg)

    def attend(kind, at, layer, ap, q, k_self, v_self):
        table, window, k_pages, v_pages = groups[kind]
        o = _paged.gqa_paged_attention(
            q, k_self, v_self, k_pages, v_pages, table, lengths, at,
            heads=cfg.num_attention_heads, scale=cfg.head_dim ** -0.5,
            window=window, key_head=key_head,
            value_heads=cfg.num_key_value_heads // 2, out_dtype=jnp.float32,
            order=order, n_live=n_live, interpret=interpret)
        return _finish(o.reshape(q.shape[0], cfg.num_attention_heads, -1),
                       ap, layer, cfg, q.dtype)

    return attend


def gathered_attend(lengths, groups, cfg: HybridSSMConfig):
    """The kernel's twin off the TPU, the plainest thing that is right: a
    slot's table row gathered in table order, every entry of it, and
    :func:`attend_view` over that under the kernel's mask
    (``gqa_paged_attention.gathered_rows``)."""
    cached = jnp.clip(lengths, 0, None)

    def attend(kind, at, layer, ap, q, k_self, v_self):
        table, window, k_pages, v_pages = groups[kind]
        return attend_view(q, k_self, v_self, *_paged.gathered_rows(
            cached, table, k_pages, v_pages, at, window), ap, layer, cfg)

    return attend


def _project(h, ap, name: str):
    return (_dot(h, ap["w_" + name])
            + ap["b_" + name].astype(jnp.float32)).astype(h.dtype)


def _qkv(h, ap, cfg: HybridSSMConfig):
    qkv = _project(h, ap, "qkv")
    qw, kvw = cfg.num_attention_heads * cfg.head_dim, cfg.kv_width
    return qkv[..., :qw], qkv[..., qw:qw + kvw], qkv[..., qw + kvw:]


def _ssm_inputs(xc, mp, cfg: HybridSSMConfig):
    """From the convolved, activated input ``xc [.., d_inner]`` float32:
    ``(dt [.., d_inner], B [.., n], C [.., n], A [n, d_inner])``."""
    r, n = cfg.dt_rank, cfg.d_state
    dbc = _dot(xc.astype(cfg.dtype), mp["w_x"])
    delta = jax.nn.softplus(_dot(dbc[..., :r].astype(cfg.dtype), mp["w_dt"])
                            + mp["b_dt"].astype(jnp.float32))
    return (delta, dbc[..., r:r + n], dbc[..., r + n:],
            -jnp.exp(mp["A_log"].astype(jnp.float32)))


def _conv(window, mp):
    """``window [.., d_conv, d_inner]``: a step's own input last.  A
    convolution without a bias (``models/olmo_hybrid.py``) has no
    ``conv_b``."""
    w = mp["conv_w"].astype(jnp.float32)
    y = jnp.sum(window.astype(jnp.float32) * w, axis=-2)
    if "conv_b" in mp:
        y = y + mp["conv_b"].astype(jnp.float32)
    return jax.nn.silu(y)


def ssm_prefill(h, mp, n_valid, cfg: HybridSSMConfig):
    """One sequence ``h [t, d]`` from an empty state.  Returns ``(out [t,
    d] float32, y [t, d_inner] before the gate, state [n, d_inner], tail
    [d_conv - 1, d_inner])``, state and tail as they stand after token
    ``n_valid - 1``."""
    t = h.shape[0]
    di, k = cfg.d_inner, cfg.d_conv
    xz = _dot(h, mp["w_in"])
    # The convolution's inputs are kept in the served type, as the tail
    # store holds them: prefill and decode then convolve the same values.
    x, z = xz[:, :di].astype(cfg.dtype), xz[:, di:]
    xp = jnp.concatenate([jnp.zeros((k - 1, di), x.dtype), x])
    xc = _conv(jnp.stack([xp[i:i + t] for i in range(k)], axis=1), mp)
    delta, b_m, c_m, a = _ssm_inputs(xc, mp, cfg)
    y, state = ssm_scan(xc, delta, b_m, c_m, a,
                        jnp.zeros((cfg.d_state, di), jnp.float32), n_valid)
    y = y + mp["D"].astype(jnp.float32) * xc
    out = _dot((y * jax.nn.silu(z)).astype(cfg.dtype), mp["w_out"])
    tail = jax.lax.dynamic_slice(xp, (n_valid, 0), (k - 1, di))
    return out, y, state, tail


def ssm_decode(h, mp, state, tail, cfg: HybridSSMConfig):
    """One token a slot: ``h [b, d]``, ``state [b, n, d_inner]``, ``tail
    [b, d_conv - 1, d_inner]``.  Returns ``(out, y, state, tail)``."""
    di = cfg.d_inner
    xz = _dot(h, mp["w_in"])
    x, z = xz[:, :di].astype(cfg.dtype), xz[:, di:]
    window = jnp.concatenate([tail, x[:, None]], axis=1)
    xc = _conv(window, mp)
    delta, b_m, c_m, a = _ssm_inputs(xc, mp, cfg)
    y, state = ssm_step(state, xc, delta, b_m, c_m, a)
    y = y + mp["D"].astype(jnp.float32) * xc
    out = _dot((y * jax.nn.silu(z)).astype(cfg.dtype), mp["w_out"])
    return out, y, state, window[:, 1:]


def gmu(h, mem, mp, cfg: HybridSSMConfig):
    gate = jax.nn.silu(_dot(h, mp["w_in"]))
    return _dot((mem * gate).astype(cfg.dtype), mp["w_out"])


def head(x, params, cfg: HybridSSMConfig):
    h = layer_norm(x, params["norm_f"], cfg.layer_norm_eps, cfg.dtype)
    return jax.lax.dot_general(h, params["embed"], (((1,), (1,)), ((), ())),
                               preferred_element_type=jnp.float32)


def _attn_out(a, ap):
    return _dot(a, ap["w_o"]) + ap["b_o"].astype(jnp.float32)


# -- whole sequences ----------------------------------------------------------

def prefill_step(params, tokens, n_valid, cfg: HybridSSMConfig,
                 last_only: bool = True):
    """A padded prompt ``tokens [bucket]`` from empty state; positions
    ``>= n_valid`` are padding, which advances neither state nor tail.
    The layers up to ``full`` run over the block; with ``last_only`` the
    rest run for token ``n_valid - 1`` alone, which is exact for its
    logits (``last_only=False`` runs every layer over the block: the tests
    hold the two equal).

    Returns ``(logits [vocab] of the last real token (or [bucket, vocab]),
    left)``: ``left["k"]``/``["v"] [bucket, kv_width]`` of the ``full``
    layer, ``left["window_k"]``/``["window_v"] [window layers, bucket,
    kv_width]`` of the window layers (the caller keeps the last ring's
    worth), ``left["state"] [ssm layers, n, d_inner]``, ``left["tail"]
    [ssm layers, d_conv - 1, d_inner]``."""
    t = tokens.shape[0]
    kinds = layer_kinds(cfg.num_hidden_layers)
    full_at = kinds.index("full")
    win = cfg.sliding_window
    eps, dt = cfg.layer_norm_eps, cfg.dtype
    last = n_valid - 1
    x = params["embed"][tokens].astype(jnp.float32)
    win_k, win_v, states, tails = [], [], [], []
    mem = k_full = v_full = None

    def at_last(v):
        return jax.lax.dynamic_slice_in_dim(v, last, 1, axis=0)

    for l, (kind, lp) in enumerate(zip(kinds, params["layers"])):
        mp = lp["mixer"]
        if l == full_at + 1 and last_only:
            x, mem = at_last(x), at_last(mem)
        h = layer_norm(x, lp["norm1"], eps, dt)
        if kind == "ssm":
            mix, mem, state, tail = ssm_prefill(h, mp, n_valid, cfg)
            states.append(state)
            tails.append(tail)
        elif kind in ("window", "full"):
            q, k, v = _qkv(h, mp, cfg)
            mix = _attn_out(attend_block(
                q, k, v, mp, l, cfg, win if kind == "window" else 0), mp)
            if kind == "window":
                win_k.append(k)
                win_v.append(v)
            else:
                k_full, v_full = k, v
        elif kind == "gmu":
            mix = gmu(h, mem, mp, cfg)
        elif last_only:     # cross, one query over the block's keys
            mix = _attn_out(attend_view(
                _project(h, mp, "q"), at_last(k_full), at_last(v_full),
                k_full[None], v_full[None],
                (jnp.arange(t) < last)[None], mp, l, cfg), mp)
        else:
            mix = _attn_out(attend_block(_project(h, mp, "q"), k_full,
                                         v_full, mp, l, cfg), mp)
        x = mlp(x + mix, lp, cfg)
    logits = head(x, params, cfg)
    return (logits[0] if last_only else logits), {
        "k": k_full, "v": v_full,
        "window_k": jnp.stack(win_k), "window_v": jnp.stack(win_v),
        "state": jnp.stack(states), "tail": jnp.stack(tails)}


def forward_full(params, tokens, cfg: HybridSSMConfig):
    """Every layer over every position of one sequence ``tokens [t]``:
    ``logits [t, vocab]``."""
    return prefill_step(params, tokens, jnp.int32(tokens.shape[0]), cfg,
                        last_only=False)[0]


def decode_step(params, tokens, lengths, stores, table,
                cfg: HybridSSMConfig):
    """One token a slot.  ``tokens [slots]``; ``lengths [slots]``: the
    position of the new token, the count of cached ones (-1: an idle slot,
    whose state stays as it is); ``stores = (k_pages, v_pages [1, pages,
    page, kv_width], win_k, win_v [window layers, window pages, page,
    kv_width], state [ssm layers, slots, n, d_inner], tail [ssm layers,
    slots, d_conv - 1, d_inner])``; ``table [slots, pages a slot + ring
    entries]``, the two groups' tables side by side.

    A window layer attends its own paged layer of the window group, the
    full layer and every cross layer the full group's one layer (the cross
    layers with queries of their own): on the TPU where the pages lie
    (:func:`paged_attend`), elsewhere over a gathered table row
    (:func:`gathered_attend`); :func:`paged_kernel_runs` says which.  The
    new token's own key and value are not in any store (the attention takes
    them beside it), so no paged store is written here: the caller writes
    both groups at the end.

    Returns ``(logits [slots, vocab], new)``: ``new["k"]``/``["v"]
    [slots, kv_width]`` of the ``full`` layer, ``new["window_k"]``/
    ``["window_v"] [window layers, slots, kv_width]``, ``new["state"]``
    and ``new["tail"]`` whole."""
    k_pages, v_pages, win_k, win_v, state, tail = stores
    kinds = layer_kinds(cfg.num_hidden_layers)
    win = cfg.sliding_window
    eps, dt = cfg.layer_norm_eps, cfg.dtype
    alive = lengths >= 0
    pps = table.shape[1] - ring_entries(win, k_pages.shape[2])
    groups = {"full": (table[:, :pps], 0, k_pages, v_pages),
              "window": (table[:, pps:], win, win_k, win_v)}
    attend = (paged_attend(lengths, groups, cfg, PAGED_INTERPRET)
              if paged_kernel_runs() else
              gathered_attend(lengths, groups, cfg))

    x = params["embed"][tokens].astype(jnp.float32)
    new_wk, new_wv = [], []
    mem = k_full = v_full = None
    n_ssm = 0
    for l, (kind, lp) in enumerate(zip(kinds, params["layers"])):
        mp = lp["mixer"]
        h = layer_norm(x, lp["norm1"], eps, dt)
        if kind == "ssm":
            i, n_ssm = n_ssm, n_ssm + 1
            mix, mem, s_new, t_new = ssm_decode(h, mp, state[i], tail[i],
                                                cfg)
            # A layer's rows written where they lie (stacked at the end,
            # all nine were copied once more: 0.55 ms an iteration).
            state = state.at[i].set(
                jnp.where(alive[:, None, None], s_new, state[i]))
            tail = tail.at[i].set(
                jnp.where(alive[:, None, None], t_new, tail[i]))
        elif kind == "window":
            q, k, v = _qkv(h, mp, cfg)
            with jax.named_scope("diff_attention"):
                mix = attend("window", len(new_wk), l, mp, q, k, v)
            mix = _attn_out(mix, mp)
            new_wk.append(k)
            new_wv.append(v)
        elif kind == "gmu":
            mix = gmu(h, mem, mp, cfg)
        else:               # full, and cross onto the full layer's new row
            if kind == "full":
                q, k_full, v_full = _qkv(h, mp, cfg)
            else:
                q = _project(h, mp, "q")
            with jax.named_scope("diff_attention"):
                mix = attend("full", 0, l, mp, q, k_full, v_full)
            mix = _attn_out(mix, mp)
        x = mlp(x + mix, lp, cfg)
    return head(x, params, cfg), {
        "k": k_full, "v": v_full,
        "window_k": jnp.stack(new_wk), "window_v": jnp.stack(new_wv),
        "state": state, "tail": tail}


# -- what the serving engine asks ---------------------------------------------

class HybridSSMServing:
    """The serving protocol (serving/models.py) for this model: two paged
    layer groups (the ONE full layer, which every layer after it reads;
    the window layers, a ring of pages a slot) and two per-slot stores."""

    speculative = False        # no verify / propose programs
    tensor_parallel = False
    tensor_parallel_why = ("its per-slot state stores are not written "
                           "for a sharded model axis")
    prefix_cache = False
    prefix_cache_why = ("state-space state and convolution tails are per "
                        "slot and not page-addressable, and a window "
                        "group's pages are a ring written over in place: a "
                        "cached prefix page carries none of them "
                        "(snapshots of recurrent state are not written "
                        "yet)")
    slot_state = True          # prefill is told which slot it fills

    def __init__(self, cfg: HybridSSMConfig) -> None:
        self.cfg = cfg
        self.kinds = layer_kinds(cfg.num_hidden_layers)

    def identity(self) -> dict:
        c = self.cfg
        return {"family": "hybrid_ssm", "vocab_size": c.vocab_size,
                "hidden_size": c.hidden_size,
                "intermediate_size": c.intermediate_size,
                "layers": c.num_hidden_layers,
                "heads": [c.num_attention_heads, c.num_key_value_heads],
                "sliding_window": c.sliding_window,
                "ssm": [c.d_state, c.d_conv, c.expand, c.dt_rank],
                "max_seq_len": c.max_seq_len,
                "dtype": jnp.dtype(c.dtype).name}

    def cache_entry(self) -> dict:
        """Keys and values in two layer groups (the window group's table
        row a ring of ``ring_entries`` pages a slot: bounded by the window,
        whatever the capacity) and the per-slot stores, each ``[layers,
        slots, *shape]`` in the cache manager.  The decode reads the pages
        in place and asks for no room to gather into."""
        c = self.cfg
        n_win, n_ssm = self.kinds.count("window"), self.kinds.count("ssm")
        return {"n_layers": 1, "n_heads": c.num_key_value_heads,
                "head_dim": c.head_dim, "widths": (c.kv_width,) * 2,
                "groups": ({"name": "full", "n_layers": 1},
                           {"name": "window", "n_layers": n_win,
                            "window": c.sliding_window}),
                "slot_stores": (
                    {"name": "ssm_state", "kind": "state",
                     "shape": (n_ssm, c.d_state, c.d_inner),
                     "dtype": jnp.float32},
                    {"name": "conv_tail", "kind": "state",
                     "shape": (n_ssm, c.d_conv - 1, c.d_inner),
                     "dtype": c.dtype})}

    def observe_stores(self, nbytes: dict) -> None:
        """Bytes of the per-slot stores by kind, once at build."""
        _M_STATE_BYTES.set(nbytes.get("state", 0))

    def decode_view(self, lengths, page_size, pages_per_slot) -> float:
        """Positions a slot a layer the decode program reads of the paged
        stores at these (host) lengths: each group's entries in use of the
        live slots, whole pages (what the kernel copies; its twin gathers
        the whole rows and masks the rest), the full group's once for each
        of its readers, the window group's once for each window layer,
        over those layers and the slots."""
        full = _paged.tokens_read(lengths, pages_per_slot, page_size)
        window = _paged.tokens_read(
            lengths, ring_entries(self.cfg.sliding_window, page_size),
            page_size)
        n_w = self.kinds.count("window")
        n_f = 1 + self.kinds.count("cross")
        return (n_f * full + n_w * window) / (n_f + n_w) / len(lengths)

    def observe_launch(self, lengths) -> None:
        """Count what a decode iteration attends, from the host's lengths
        of its launch: every position up to the new token's own."""
        seen = lengths[lengths >= 0].astype(np.int64) + 1
        _M_SHARED_KV.inc(int(seen.sum()))
        _M_WINDOW.inc(int(np.minimum(seen, self.cfg.sliding_window).sum()))

    def decode(self, params, pages, table, lengths, tokens):
        k_pages, v_pages, win_k, win_v = pages[:4]
        logits, new = decode_step(params, tokens, lengths, pages, table,
                                  self.cfg)
        # One row a slot in the one layer of the full group and in every
        # layer of the window group, written where it lies (see
        # DenseLM.decode): the full group at the position's own page, the
        # window group at that page's ring entry; an idle slot's rows land
        # in the trash pages.
        ps = k_pages.shape[2]
        ring = ring_entries(self.cfg.sliding_window, ps)
        pps = table.shape[1] - ring
        pos = jnp.clip(lengths, 0, None)
        b = tokens.shape[0]
        at_page, off = pos // ps, pos % ps
        page_f = table[jnp.arange(b), at_page]
        page_w = table[jnp.arange(b), pps + at_page % ring]
        zero = jnp.zeros((), jnp.int32)
        for slot in range(b):
            at = (zero, page_f[slot], off[slot], zero)
            k_pages = jax.lax.dynamic_update_slice(
                k_pages, new["k"][slot][None, None, None, :], at)
            v_pages = jax.lax.dynamic_update_slice(
                v_pages, new["v"][slot][None, None, None, :], at)
            at = (zero, page_w[slot], off[slot], zero)
            win_k = jax.lax.dynamic_update_slice(
                win_k, new["window_k"][:, slot][:, None, None, :], at)
            win_v = jax.lax.dynamic_update_slice(
                win_v, new["window_v"][:, slot][:, None, None, :], at)
        return (logits,), (k_pages, v_pages, win_k, win_v, new["state"],
                           new["tail"])

    def prefill(self, params, pages, table_row, start, n_valid, tokens,
                slot):
        """``start`` is always 0 here (``prefix_cache`` is off); ``slot
        [1]`` is the slot filled: its state and tails are REPLACED by what
        the prompt leaves.  The full group takes every page of the prompt,
        the window group the last ``ring`` pages' worth, each into its
        ring entry (what the slot's last owner left in a page is past the
        new length, or written over)."""
        k_pages, v_pages, win_k, win_v, state, tail = pages
        ps, bucket = k_pages.shape[2], tokens.shape[1]
        ring = ring_entries(self.cfg.sliding_window, ps)
        pps = table_row.shape[1] - ring
        logits, left = prefill_step(params, tokens[0], n_valid[0], self.cfg)
        # A page at a time, written where it lies; pages past the prompt
        # are not mapped: their rows land in a trash page.
        rows = min(ps, bucket)
        n_pages = max(1, bucket // ps)
        zero = jnp.zeros((), jnp.int32)
        for j in range(n_pages):
            at = (zero, table_row[0, j], zero, zero)
            k_pages = jax.lax.dynamic_update_slice(
                k_pages, left["k"][None, None, j * ps:j * ps + rows], at)
            v_pages = jax.lax.dynamic_update_slice(
                v_pages, left["v"][None, None, j * ps:j * ps + rows], at)
        top = (n_valid[0] - 1) // ps

        def write_ring(e, kv):
            # The newest logical page congruent to the entry; an entry
            # the prompt does not reach is unmapped.
            j = jnp.clip(top - (top - e) % ring, 0, n_pages - 1)
            at = (zero, table_row[0, pps + e], zero, zero)
            return tuple(jax.lax.dynamic_update_slice(
                store, jax.lax.dynamic_slice_in_dim(x, j * ps, rows,
                                                    axis=1)[:, None], at)
                for store, x in zip(kv, (left["window_k"],
                                         left["window_v"])))

        win_k, win_v = jax.lax.fori_loop(0, min(ring, n_pages), write_ring,
                                         (win_k, win_v))
        at = (zero, slot[0], zero, zero)
        state = jax.lax.dynamic_update_slice(state, left["state"][:, None],
                                             at)
        tail = jax.lax.dynamic_update_slice(tail, left["tail"][:, None], at)
        return (logits,), (k_pages, v_pages, win_k, win_v, state, tail)
