"""A Mamba-2 hybrid decoder, for serving.

The block stack of ``ibm-granite/granite-4.0-h-micro`` (``model_type``
``granitemoehybrid`` with no experts; docs/inference.md "Mamba-2 layers"):
pre-norm residual blocks with RMSNorm, a SwiGLU MLP in every layer, NO
positional encoding, a tied head, four scalar multipliers, and the mixer
of each layer named by the published LIST ``layer_types``:

* ``mamba``: a Mamba-2 state-space mixer.  ``[z | xBC | dt] = u W_in``; a
  depthwise causal convolution over ALL of ``xBC`` (the input ``x`` and
  the shared ``B`` and ``C`` together), then per head ``S_t = exp(dt_t A)
  S_{t-1} + dt_t x_t B_t^T``, ``y_t = S_t C_t + D x_t`` with ONE scalar
  ``A``, ``D`` and ``dt`` bias a head; the gate ``silu(z)`` is applied
  BEFORE one RMS norm over all of ``d_inner``.  What it keeps of a
  sequence is constant: a ``[d_head, d_state]`` float32 matrix a head
  (:mod:`horovod_tpu.ops.ssd` has its layout) and the last ``d_conv - 1``
  inputs of the convolution.
* ``attention``: plain grouped-query softmax attention over every
  position, scaled by ``attention_multiplier`` (not ``head_dim ** -0.5``);
  its keys and values are one layer of the paged store.

``x_0 = embedding_multiplier E[token]``; a branch joins the stream as ``x
+ residual_multiplier * branch``; ``logits = RMSNorm(x_L) E^T /
logits_scaling``.

This is a SIBLING of ``models/hybrid_ssm.py``, not a mode of it: that
module fixes its five-kind layout by arithmetic on the layer index and
every attention in it is differential; which module serves a config is
decided by the published keys (``model_type``, ``layer_types``).  Shared
with it: the convolution, the per-slot stores of the cache manager and the
counters.

A prompt runs the chunked form of the recurrence
(:func:`~horovod_tpu.ops.ssd.ssd_chunk_scan`), decode the one-step form
(:func:`~horovod_tpu.ops.ssd.ssd_step`), which updates the live slots'
rows of the state store in place; its attention layers read their pages
where they lie (``ops/gqa_paged_attention.py``; :func:`paged_attend`).
"""

from __future__ import annotations

from dataclasses import dataclass

import jax
import jax.numpy as jnp
import numpy as np

from .. import telemetry as _telemetry
from ..ops import gqa_paged_attention as _paged
from ..ops.ssd import head_pack, ssd_chunk_scan, ssd_step
from .hybrid_ssm import (_M_SHARED_KV, _M_STATE_BYTES, PREFILL_Q_BLOCK,
                         _conv, _dot, _masked_exp)

_M_STATE_MOVED = _telemetry.counter(
    "serving.state_bytes_moved", "bytes of recurrent state and convolution "
    "tails the decode iterations had to read and write: 2 x live slots x "
    "state-space layers x a slot's bytes in one layer, from the host's "
    "lengths (what an implementation moves beyond that is not in it)")

# ``ops/gqa_paged_attention.py``'s ``interpret``: None is the rule (the
# kernel on the TPU, the gathered rows elsewhere); a test sets True to run
# the kernel in the Pallas interpreter, before the engine builds its programs.
PAGED_INTERPRET = None

# granite-4.0-h-micro's published list: attention at 5, 15, 25, 35.
GRANITE_4_0_H_MICRO_LAYERS = tuple(
    "attention" if l % 10 == 5 else "mamba" for l in range(40))


@dataclass(frozen=True)
class Mamba2HybridConfig:
    """The published keys under their published names."""
    vocab_size: int = 100352
    hidden_size: int = 2048
    intermediate_size: int = 8192
    num_hidden_layers: int = 40
    layer_types: tuple = GRANITE_4_0_H_MICRO_LAYERS
    num_attention_heads: int = 32
    num_key_value_heads: int = 8
    attention_multiplier: float = 0.015625
    embedding_multiplier: float = 12.0
    residual_multiplier: float = 0.22
    logits_scaling: float = 8.0
    rms_norm_eps: float = 1e-5
    mamba_n_heads: int = 64
    mamba_d_head: int = 64
    mamba_d_state: int = 128
    mamba_d_conv: int = 4
    mamba_expand: int = 2
    mamba_n_groups: int = 1
    mamba_chunk_size: int = 256
    max_position_embeddings: int = 131072
    dtype: object = jnp.bfloat16
    # Read by nothing: the benchmark's builder hands it on from a fixture.
    decode_chunk_tokens: int = 256

    def __post_init__(self):
        object.__setattr__(self, "layer_types", tuple(self.layer_types))
        if (len(self.layer_types) != self.num_hidden_layers
                or set(self.layer_types) - {"mamba", "attention"}):
            raise ValueError("layer_types names 'mamba' or 'attention' for "
                             "each of num_hidden_layers layers")
        if self.mamba_n_groups != 1:
            raise ValueError("the state-space mixer is written for one "
                             "group of B and C shared by all heads")
        if self.mamba_n_heads * self.mamba_d_head != self.d_inner:
            raise ValueError("mamba_n_heads x mamba_d_head is not "
                             "mamba_expand x hidden_size")
        if self.num_attention_heads % self.num_key_value_heads:
            raise ValueError("query heads share key/value heads in whole "
                             "groups")

    @property
    def max_seq_len(self) -> int:
        return self.max_position_embeddings

    @property
    def head_dim(self) -> int:
        return self.hidden_size // self.num_attention_heads

    @property
    def d_inner(self) -> int:
        return self.mamba_expand * self.hidden_size

    @property
    def conv_width(self) -> int:
        """Channels the convolution covers: ``x``, ``B`` and ``C``."""
        return self.d_inner + 2 * self.mamba_d_state

    @property
    def kv_width(self) -> int:
        return self.num_key_value_heads * self.head_dim

    @property
    def state_shape(self) -> tuple:
        """A slot's state in one layer, as ``ops/ssd.py`` keeps it."""
        pack = head_pack(self.mamba_n_heads, self.mamba_d_head)
        return (self.mamba_n_heads // pack, self.mamba_d_state,
                pack * self.mamba_d_head)

    def serving_model(self) -> "Mamba2HybridServing":
        return Mamba2HybridServing(self)


# -- parameters ---------------------------------------------------------------

def init_mamba2_hybrid(key, cfg: Mamba2HybridConfig) -> dict:
    """Parameter pytree, ``layers`` a LIST (one dict a layer).  ``A_log =
    log(uniform(1, 16))``, ``D = 1``, the ``dt`` bias such that its
    softplus lies log-uniform in 1e-3..1e-1, convolution weights normal
    ``d_conv^-0.5``, norm weights 1, the embedding normal ``0.02 /
    embedding_multiplier``, the rest normal 0.02: the two multipliers
    stand where an initialisation would scale by width and by depth."""
    d, f, di = cfg.hidden_size, cfg.intermediate_size, cfg.d_inner
    h, k, cw = cfg.mamba_n_heads, cfg.mamba_d_conv, cfg.conv_width
    qw, kvw = cfg.num_attention_heads * cfg.head_dim, cfg.kv_width
    dt = cfg.dtype
    std = 0.02
    keys = iter(jax.random.split(key, 2 + 8 * cfg.num_hidden_layers))

    def w(shape, scale):
        return (jax.random.normal(next(keys), shape, jnp.float32)
                * scale).astype(dt)

    def uniform():
        return jax.random.uniform(next(keys), (h,), jnp.float32)

    def norm(width=d):
        return {"scale": jnp.ones((width,), dt)}

    def mixer(kind):
        if kind == "attention":
            return {"w_qkv": w((d, qw + 2 * kvw), std),
                    "w_o": w((qw, d), std)}
        step = jnp.exp(uniform() * np.log(100.0) + np.log(1e-3))
        return {"w_in": w((d, di + cw + h), std),
                "conv_w": w((k, cw), k ** -0.5), "conv_b": w((cw,), std),
                "dt_bias": (step + jnp.log(-jnp.expm1(-step))).astype(dt),
                "A_log": jnp.log(1.0 + 15.0 * uniform()).astype(dt),
                "D": jnp.ones((h,), dt), "norm": jnp.ones((di,), dt),
                "w_out": w((di, d), std)}

    def layer(kind):
        return {"norm1": norm(), "mixer": mixer(kind), "norm2": norm(),
                "mlp": {"w1": w((d, 2 * f), std), "w2": w((f, d), std)}}

    return {"embed": w((cfg.vocab_size, d), std / cfg.embedding_multiplier),
            "layers": [layer(kind) for kind in cfg.layer_types],
            "norm_f": norm()}


# -- pieces -------------------------------------------------------------------

def rms_norm(x, scale, eps: float, dtype):
    """Computed in float32 whatever comes in, handed on as ``dtype``."""
    xf = x.astype(jnp.float32)
    y = xf * jax.lax.rsqrt(jnp.mean(xf * xf, axis=-1, keepdims=True) + eps)
    return (y * scale.astype(jnp.float32)).astype(dtype)


def mlp(x, lp, cfg: Mamba2HybridConfig):
    """``x + r W2 (silu(g) * u)``, ``[g | u] = W1 RMSNorm(x)``."""
    with jax.named_scope("swiglu"):
        h = rms_norm(x, lp["norm2"]["scale"], cfg.rms_norm_eps, cfg.dtype)
        g, u = jnp.split(_dot(h, lp["mlp"]["w1"]), 2, axis=-1)
        return x + cfg.residual_multiplier * _dot(
            (jax.nn.silu(g) * u).astype(cfg.dtype), lp["mlp"]["w2"])


def head(x, params, cfg: Mamba2HybridConfig):
    h = rms_norm(x, params["norm_f"]["scale"], cfg.rms_norm_eps, cfg.dtype)
    return jax.lax.dot_general(
        h, params["embed"], (((1,), (1,)), ((), ())),
        preferred_element_type=jnp.float32) / cfg.logits_scaling


def _qkv(h, ap, cfg: Mamba2HybridConfig):
    qkv = _dot(h, ap["w_qkv"]).astype(h.dtype)
    qw, kvw = cfg.num_attention_heads * cfg.head_dim, cfg.kv_width
    return qkv[..., :qw], qkv[..., qw:qw + kvw], qkv[..., qw + kvw:]


def attend_block(q, k, v, cfg: Mamba2HybridConfig):
    """Grouped-query attention of one sequence over ITSELF, causal,
    ``PREFILL_Q_BLOCK`` queries at a time against the keys they can see.
    ``q [t, heads * hd]``, ``k``/``v`` ``[t, kv_heads * hd]``; query head
    ``i`` reads key/value head ``i // (heads / kv_heads)``."""
    t = q.shape[0]
    hd, dt = cfg.head_dim, q.dtype
    g = cfg.num_key_value_heads
    q4 = q.reshape(t, g, cfg.num_attention_heads // g, hd)
    k3, v3 = k.reshape(t, g, hd), v.reshape(t, g, hd)
    qb = min(PREFILL_Q_BLOCK, t)
    outs = []
    for lo in range(0, t, qb):
        hi = min(lo + qb, t)
        scores = jnp.einsum(
            "qgrd,kgd->grqk", q4[lo:hi], k3[:hi],
            preferred_element_type=jnp.float32) * cfg.attention_multiplier
        mask = jnp.arange(hi)[None, :] <= jnp.arange(lo, hi)[:, None]
        m = jnp.max(jnp.where(mask, scores, -jnp.inf), axis=-1,
                    keepdims=True)
        p = _masked_exp(scores, mask, m)
        p = p / jnp.sum(p, axis=-1, keepdims=True)
        outs.append(jnp.einsum("grqk,kgd->qgrd", p.astype(dt), v3[:hi],
                               preferred_element_type=jnp.float32))
    return jnp.concatenate(outs, axis=0).astype(dt).reshape(t, -1)


# -- the decode's attention over the paged layers -----------------------------

def attend_view(q, k_self, v_self, k_view, v_view, mask, cfg):
    """Grouped-query attention of ONE query a row over a view of cached
    keys and values plus the row's own new key and value, which are not in
    the view (the paged kernel's arithmetic in plain ``jnp``; ``cfg``: this
    family's config or a sibling's with the same keys).  ``q [b, heads *
    hd]``; ``k_self``/``v_self`` ``[b, kv_width]``; ``k_view``/``v_view``
    ``[b, n, kv_width]``; ``mask [b, n]``: which view rows a row attends
    (none: it attends itself only).  Returns ``[b, heads * hd]``."""
    b, n = mask.shape
    hd, dt, f32 = cfg.head_dim, q.dtype, jnp.float32
    g = cfg.num_key_value_heads
    q4 = q.reshape(b, g, -1, hd)
    scores = jnp.einsum("bgrd,bngd->bgrn", q4, k_view.reshape(b, n, g, hd),
                        preferred_element_type=f32
                        ) * cfg.attention_multiplier
    s_self = jnp.einsum("bgrd,bgd->bgr", q4, k_self.reshape(b, g, hd),
                        preferred_element_type=f32
                        ) * cfg.attention_multiplier
    mask = mask[:, None, None, :]
    m = jnp.maximum(jnp.max(jnp.where(mask, scores, -jnp.inf), axis=-1),
                    s_self)
    p = _masked_exp(scores, mask, m[..., None])
    p_self = jnp.exp(s_self - m)
    o = jnp.einsum("bgrn,bngd->bgrd", p.astype(dt),
                   v_view.reshape(b, n, g, hd), preferred_element_type=f32)
    o = o + p_self[..., None] * v_self.astype(f32).reshape(b, g, 1, hd)
    denom = jnp.sum(p, axis=-1) + p_self
    return (o / denom[..., None]).astype(dt).reshape(b, -1)


def paged_attend(lengths, table, k_pages, v_pages, cfg: Mamba2HybridConfig):
    """The decode step's ``attend(layer, q, k_self, v_self)``, built once a
    program.  On the TPU the kernel that walks the page table
    (``ops/gqa_paged_attention.py``): the live slots' own pages of paged
    layer ``layer``, read where they lie.  Elsewhere its plain twin: a
    slot's table row gathered whole and :func:`attend_view` over it under
    the kernel's mask.  Which of the two is read off the backend the
    program is built for (``PAGED_INTERPRET`` is a test's)."""
    if _paged.kernel_runs(PAGED_INTERPRET):
        order, n_live = _paged.live_first(lengths)

        def attend(layer, q, k_self, v_self):
            return _paged.gqa_paged_attention(
                q, k_self, v_self, k_pages, v_pages, table, lengths, layer,
                heads=cfg.num_attention_heads,
                scale=cfg.attention_multiplier, window=0, order=order,
                n_live=n_live, interpret=PAGED_INTERPRET)
    else:
        cached = jnp.clip(lengths, 0, None)

        def attend(layer, q, k_self, v_self):
            return attend_view(q, k_self, v_self, *_paged.gathered_rows(
                cached, table, k_pages, v_pages, layer), cfg)

    return attend


def _split_in(h, mp, cfg: Mamba2HybridConfig):
    """``[z | xBC | dt] = h W_in``: the gate (float32), the convolution's
    input in the served type (as the tail store holds it: prefill and
    decode convolve the same values) and ``dt`` before its bias."""
    di, cw = cfg.d_inner, cfg.conv_width
    zxd = _dot(h, mp["w_in"])
    return (zxd[..., :di], zxd[..., di:di + cw].astype(cfg.dtype),
            zxd[..., di + cw:])


def _ssd_inputs(conv, raw_dt, mp, cfg: Mamba2HybridConfig):
    """From the convolved, activated ``xBC`` (float32) and the raw ``dt``:
    ``(x [.., H, P], dt [.., H], A [H], B [.., N], C [.., N])``, the
    recurrence's operands in the served type."""
    di, n = cfg.d_inner, cfg.mamba_d_state
    conv = conv.astype(cfg.dtype)
    x = conv[..., :di].reshape(*conv.shape[:-1], cfg.mamba_n_heads,
                               cfg.mamba_d_head)
    delta = jax.nn.softplus(raw_dt + mp["dt_bias"].astype(jnp.float32))
    return (x, delta, -jnp.exp(mp["A_log"].astype(jnp.float32)),
            conv[..., di:di + n], conv[..., di + n:])


def _gate_out(y, z, mp, cfg: Mamba2HybridConfig):
    """``y [.., H, P]`` (``S C + D x``) gated by ``silu(z)`` FIRST, then
    ONE RMS norm over all of ``d_inner``, then ``W_out``."""
    g = y.reshape(*y.shape[:-2], cfg.d_inner) * jax.nn.silu(z)
    return _dot(rms_norm(g, mp["norm"], cfg.rms_norm_eps, cfg.dtype),
                mp["w_out"])


def ssd_prefill(h, mp, n_valid, cfg: Mamba2HybridConfig):
    """One sequence ``h [t, d]`` from an empty state.  Returns ``(out [t,
    d] float32, state, tail [d_conv - 1, conv_width])``, state and tail
    as they stand after token ``n_valid - 1``."""
    t = h.shape[0]
    k = cfg.mamba_d_conv
    z, xbc, raw_dt = _split_in(h, mp, cfg)
    xp = jnp.concatenate([jnp.zeros((k - 1, cfg.conv_width), xbc.dtype),
                          xbc])
    conv = _conv(jnp.stack([xp[i:i + t] for i in range(k)], axis=1), mp)
    x, delta, a, b_m, c_m = _ssd_inputs(conv, raw_dt, mp, cfg)
    y, state = ssd_chunk_scan(
        x, delta, a, b_m, c_m, jnp.zeros(cfg.state_shape, jnp.float32),
        n_valid, chunk=cfg.mamba_chunk_size)
    y = y + mp["D"].astype(jnp.float32)[:, None] * x.astype(jnp.float32)
    tail = jax.lax.dynamic_slice(xp, (n_valid, 0), (k - 1, cfg.conv_width))
    return _gate_out(y, z, mp, cfg), state, tail


def ssd_decode(h, mp, state, tail, layer: int, alive,
               cfg: Mamba2HybridConfig):
    """One token a slot: ``h [b, d]``, ``state`` the WHOLE store
    ``[layers, b, *state_shape]`` of which ``layer``'s live rows are
    advanced in place, ``tail [b, d_conv - 1, conv_width]``.  Returns
    ``(out, state, tail)``."""
    z, xbc, raw_dt = _split_in(h, mp, cfg)
    window = jnp.concatenate([tail, xbc[:, None]], axis=1)
    x, delta, a, b_m, c_m = _ssd_inputs(_conv(window, mp), raw_dt, mp, cfg)
    y, state = ssd_step(state, x, delta, a, b_m, c_m,
                        mp["D"].astype(jnp.float32), alive, layer=layer)
    return _gate_out(y, z, mp, cfg), state, window[:, 1:]


# -- whole sequences ----------------------------------------------------------

def prefill_step(params, tokens, n_valid, cfg: Mamba2HybridConfig,
                 last_only: bool = True):
    """A padded prompt ``tokens [bucket]`` from empty state; positions
    ``>= n_valid`` are padding, which advances neither state nor tail.
    Every layer leaves something behind, so every layer runs over the
    block; with ``last_only`` the head runs for token ``n_valid - 1``
    alone.

    Returns ``(logits [vocab] of the last real token (or [bucket, vocab]),
    left)``: ``left["k"]``/``["v"] [attention layers, bucket, kv_width]``,
    ``left["state"] [mamba layers, *state_shape]``, ``left["tail"] [mamba
    layers, d_conv - 1, conv_width]``."""
    eps, dt, r = cfg.rms_norm_eps, cfg.dtype, cfg.residual_multiplier
    x = params["embed"][tokens].astype(jnp.float32) * cfg.embedding_multiplier
    ks, vs, states, tails = [], [], [], []
    for kind, lp in zip(cfg.layer_types, params["layers"]):
        mp = lp["mixer"]
        h = rms_norm(x, lp["norm1"]["scale"], eps, dt)
        if kind == "mamba":
            with jax.named_scope("ssd_mixer"):
                mix, state, tail = ssd_prefill(h, mp, n_valid, cfg)
            states.append(state)
            tails.append(tail)
        else:
            with jax.named_scope("gqa_attention"):
                q, k, v = _qkv(h, mp, cfg)
                mix = _dot(attend_block(q, k, v, cfg), mp["w_o"])
            ks.append(k)
            vs.append(v)
        x = mlp(x + r * mix, lp, cfg)
    if last_only:
        x = jax.lax.dynamic_slice_in_dim(x, n_valid - 1, 1, axis=0)
    logits = head(x, params, cfg)
    return (logits[0] if last_only else logits), {
        "k": jnp.stack(ks), "v": jnp.stack(vs),
        "state": jnp.stack(states), "tail": jnp.stack(tails)}


def forward_full(params, tokens, cfg: Mamba2HybridConfig):
    """Every position of one sequence ``tokens [t]``: ``logits [t,
    vocab]``."""
    return prefill_step(params, tokens, jnp.int32(tokens.shape[0]), cfg,
                        last_only=False)[0]


def decode_step(params, tokens, lengths, stores, table,
                cfg: Mamba2HybridConfig):
    """One token a slot.  ``tokens [slots]``; ``lengths [slots]``: the
    position of the new token, the count of cached ones (-1: an idle slot,
    whose state stays bit for bit as it is); ``stores = (k_pages, v_pages
    [attention layers, pages, page, kv_width], state [mamba layers, slots,
    *state_shape], tail [mamba layers, slots, d_conv - 1, conv_width])``.

    Each attention layer attends its own paged layer through ONE ``attend``
    (:func:`paged_attend`).  The new token's own key and value are not in
    the store, so the paged store is written at the end only; the state
    store is advanced layer by layer in place (``ops/ssd.py``
    ``ssd_step``).

    Returns ``(logits [slots, vocab], new)``: ``new["k"]``/``["v"]
    [attention layers, slots, kv_width]``, ``new["state"]`` and
    ``new["tail"]`` whole."""
    k_pages, v_pages, state, tail = stores
    eps, dt, r = cfg.rms_norm_eps, cfg.dtype, cfg.residual_multiplier
    alive = lengths >= 0
    attend = paged_attend(lengths, table, k_pages, v_pages, cfg)

    x = params["embed"][tokens].astype(jnp.float32) * cfg.embedding_multiplier
    new_k, new_v = [], []
    n_mamba = 0
    for kind, lp in zip(cfg.layer_types, params["layers"]):
        mp = lp["mixer"]
        h = rms_norm(x, lp["norm1"]["scale"], eps, dt)
        if kind == "mamba":
            i, n_mamba = n_mamba, n_mamba + 1
            with jax.named_scope("ssd_mixer"):
                mix, state, t_new = ssd_decode(h, mp, state, tail[i], i,
                                               alive, cfg)
                tail = tail.at[i].set(
                    jnp.where(alive[:, None, None], t_new, tail[i]))
        else:
            with jax.named_scope("gqa_attention"):
                q, k, v = _qkv(h, mp, cfg)
                mix = _dot(attend(len(new_k), q, k, v), mp["w_o"])
            new_k.append(k)
            new_v.append(v)
        x = mlp(x + r * mix, lp, cfg)
    return head(x, params, cfg), {
        "k": jnp.stack(new_k), "v": jnp.stack(new_v), "state": state,
        "tail": tail}


# -- what the serving engine asks ---------------------------------------------

def write_token_rows(k_pages, v_pages, k, v, table, lengths):
    """A decode iteration's new keys and values ``k``/``v`` ``[layers,
    slots, kv_width]`` into the paged stores: one row a slot in every
    paged layer, written where it lies (see DenseLM.decode); an idle slot's
    row lands in the trash page."""
    ps = k_pages.shape[2]
    pos = jnp.clip(lengths, 0, None)
    b = lengths.shape[0]
    page, off = table[jnp.arange(b), pos // ps], pos % ps
    zero = jnp.zeros((), jnp.int32)
    for slot in range(b):
        at = (zero, page[slot], off[slot], zero)
        k_pages = jax.lax.dynamic_update_slice(
            k_pages, k[:, slot][:, None, None, :], at)
        v_pages = jax.lax.dynamic_update_slice(
            v_pages, v[:, slot][:, None, None, :], at)
    return k_pages, v_pages


def write_prompt_pages(k_pages, v_pages, k, v, table_row):
    """A prompt's keys and values ``k``/``v`` ``[layers, bucket,
    kv_width]`` into the paged stores, a page at a time, written where it
    lies; pages past the prompt are not mapped: their rows land in trash
    page 0."""
    ps, bucket = k_pages.shape[2], k.shape[1]
    rows = min(ps, bucket)
    zero = jnp.zeros((), jnp.int32)
    for j in range(max(1, bucket // ps)):
        at = (zero, table_row[0, j], zero, zero)
        k_pages = jax.lax.dynamic_update_slice(
            k_pages, k[:, None, j * ps:j * ps + rows], at)
        v_pages = jax.lax.dynamic_update_slice(
            v_pages, v[:, None, j * ps:j * ps + rows], at)
    return k_pages, v_pages


def replace_slot_rows(state, tail, new_state, new_tail, slot):
    """What a prompt leaves (``new_state [layers, *state_shape]``,
    ``new_tail [layers, d_conv - 1, conv_width]``) in the place of slot
    ``slot [1]``'s rows of the per-slot stores."""
    zero = jnp.zeros((), jnp.int32)
    state = jax.lax.dynamic_update_slice(
        state, new_state[:, None], (zero, slot[0], zero, zero, zero))
    tail = jax.lax.dynamic_update_slice(
        tail, new_tail[:, None], (zero, slot[0], zero, zero))
    return state, tail


class Mamba2HybridServing:
    """The serving protocol (serving/models.py) for this model: one paged
    layer an attention layer and two per-slot stores."""

    speculative = False        # no verify / propose programs
    tensor_parallel = False
    tensor_parallel_why = ("its per-slot state stores are not written "
                           "for a sharded model axis")
    prefix_cache = False
    prefix_cache_why = ("state-space state and convolution tails are per "
                        "slot and not page-addressable: a cached prefix "
                        "page carries none of them (snapshots of "
                        "recurrent state are not written yet)")
    slot_state = True          # prefill is told which slot it fills

    def __init__(self, cfg: Mamba2HybridConfig) -> None:
        self.cfg = cfg
        self.n_mamba = cfg.layer_types.count("mamba")
        self.n_attention = cfg.layer_types.count("attention")
        # A slot's recurrent bytes in ONE state-space layer: the float32
        # state and the tail in the served type.
        self.slot_layer_bytes = (
            4 * int(np.prod(cfg.state_shape))
            + (cfg.mamba_d_conv - 1) * cfg.conv_width
            * jnp.dtype(cfg.dtype).itemsize)

    def identity(self) -> dict:
        c = self.cfg
        return {"family": "mamba2_hybrid", "vocab_size": c.vocab_size,
                "hidden_size": c.hidden_size,
                "intermediate_size": c.intermediate_size,
                "layer_types": list(c.layer_types),
                "heads": [c.num_attention_heads, c.num_key_value_heads],
                "mamba": [c.mamba_n_heads, c.mamba_d_head, c.mamba_d_state,
                          c.mamba_d_conv, c.mamba_expand, c.mamba_n_groups,
                          c.mamba_chunk_size],
                "multipliers": [c.attention_multiplier,
                                c.embedding_multiplier,
                                c.residual_multiplier, c.logits_scaling],
                "max_seq_len": c.max_seq_len,
                "dtype": jnp.dtype(c.dtype).name}

    def cache_entry(self) -> dict:
        """A paged layer (keys, values) for each attention layer and the
        per-slot stores, each ``[layers, slots, *shape]`` in the cache
        manager.  The decode reads the pages in place and asks for no room
        to gather into."""
        c = self.cfg
        return {"n_layers": self.n_attention,
                "n_heads": c.num_key_value_heads, "head_dim": c.head_dim,
                "widths": (c.kv_width,) * 2,
                "slot_stores": (
                    {"name": "ssm_state", "kind": "state",
                     "shape": (self.n_mamba, *c.state_shape),
                     "dtype": jnp.float32},
                    {"name": "conv_tail", "kind": "state",
                     "shape": (self.n_mamba, c.mamba_d_conv - 1,
                               c.conv_width),
                     "dtype": c.dtype})}

    def observe_stores(self, nbytes: dict) -> None:
        """Bytes of the per-slot stores by kind, once at build."""
        _M_STATE_BYTES.set(nbytes.get("state", 0))

    def decode_view(self, lengths, page_size, pages_per_slot) -> float:
        """Positions a slot an attention layer the decode program reads of
        the paged store at these (host) lengths: the live slots' entries
        in use, whole pages (what the kernel copies; its twin gathers the
        whole rows and masks the rest), over the slots."""
        return (_paged.tokens_read(lengths, pages_per_slot, page_size)
                / len(lengths))

    def observe_launch(self, lengths) -> None:
        """Count what a decode iteration attends and what state it must
        move, from the host's lengths of its launch."""
        live = lengths[lengths >= 0].astype(np.int64)
        _M_SHARED_KV.inc(int((live + 1).sum()))
        _M_STATE_MOVED.inc(2 * len(live) * self.n_mamba
                           * self.slot_layer_bytes)

    def decode(self, params, pages, table, lengths, tokens):
        logits, new = decode_step(params, tokens, lengths, pages, table,
                                  self.cfg)
        k_pages, v_pages = write_token_rows(*pages[:2], new["k"], new["v"],
                                            table, lengths)
        return (logits,), (k_pages, v_pages, new["state"], new["tail"])

    def prefill(self, params, pages, table_row, start, n_valid, tokens,
                slot):
        """``start`` is always 0 here (``prefix_cache`` is off); ``slot
        [1]`` is the slot filled: its state and tails are REPLACED by what
        the prompt leaves."""
        k_pages, v_pages, state, tail = pages
        logits, left = prefill_step(params, tokens[0], n_valid[0], self.cfg)
        k_pages, v_pages = write_prompt_pages(k_pages, v_pages, left["k"],
                                              left["v"], table_row)
        state, tail = replace_slot_rows(state, tail, left["state"],
                                        left["tail"], slot)
        return (logits,), (k_pages, v_pages, state, tail)
