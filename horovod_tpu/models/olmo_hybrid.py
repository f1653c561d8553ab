"""The ``olmo_hybrid`` decoder (``allenai/Olmo-Hybrid-7B``), for serving.

Gated delta-rule linear attention three layers to one multi-head full
layer, under the published LIST ``layer_types`` (docs/inference.md "Gated
delta-rule layers").  The family's REORDERED norm: no norm before a branch,
one RMS norm (weight only) on its output.  With ``x`` the float32 residual
stream, ``x_0 = E[token]``:

    h = x + RMSNorm(Mixer(x))        y = h + RMSNorm(W_2 (silu(g) * u)),  [g | u] = W_1 h
    logits = W_head RMSNorm(x_L)     (an untied head)

* ``linear_attention``: ``[q~ | k~ | v~ | z | a | b] = x W_in`` (``H d_k``,
  ``H d_k``, ``H d_v``, ``H d_v``, ``H``, ``H``); a depthwise causal
  convolution of ``linear_conv_kernel_dim`` steps, no bias, then ``silu``,
  over ALL of ``[q~ | k~ | v~]``; per head ``q = q^ / |q^| / sqrt(d_k)``,
  ``k = k^ / |k^|``, ``beta = 2 sigmoid(b)`` (``linear_allow_neg_eigval``;
  ``sigmoid(b)`` without), ``g = -exp(A_log) softplus(a + dt_bias)``, the
  gated delta rule (:mod:`horovod_tpu.ops.gated_delta`: ``S' = exp(g) S``,
  ``S = S' + beta k (v - S'^T k)^T``, ``o = S^T q``), then ``RMSNorm_{d_v}
  (o) * silu(z)`` a head at a time with one weight ``[d_v]`` (the norm
  FIRST, the gate after it) and ``W_o``.  What it keeps of a sequence is
  constant: a ``[d_k, d_v]`` float32 matrix a head (``ops/gated_delta.py``
  has its layout) and the convolution's last ``d_conv - 1`` inputs.
* ``full_attention``: ``q = RMSNorm(x W_q)``, ``k = RMSNorm(x W_k)`` (ONE
  norm over the whole projection, not a head's), ``v = x W_v``; causal
  softmax of ``q_i . k_j / sqrt(head_dim)`` over every position, every
  query head on its own key/value head; NO positional encoding (the
  published ``rope_theta`` is ``null``); its keys and values are one layer
  of the paged store.

This is a SIBLING of ``models/mamba2_hybrid.py`` (also a matrix state a
head beside a few paged layers), chosen by the published keys
(``model_type`` ``olmo_hybrid``): that module's recurrence only scales its
state and adds to it, its norms stand before the branches and its head is
tied.  Shared with it: the RMS norm, the page writers and the counters;
with ``models/hybrid_ssm.py``: the convolution; with ``models/afmoe.py``:
the full layers' attention, a prompt's through the streaming flash forward
and the decode's through ``ops/gqa_paged_attention.py`` where the pages lie
(off the TPU: their plain twins there).

A prompt runs the chunked form of the recurrence
(:func:`~horovod_tpu.ops.gated_delta.gated_delta_chunk_scan`), decode the
one-step form (:func:`~horovod_tpu.ops.gated_delta.gated_delta_step`),
which updates the live slots' rows of the state store in place.
"""

from __future__ import annotations

from dataclasses import dataclass

import jax
import jax.numpy as jnp
import numpy as np

from ..ops import gqa_paged_attention as _paged
from ..ops.gated_delta import (gated_delta_chunk_scan, gated_delta_step,
                               head_pack)
from . import afmoe as _afmoe
from .hybrid_ssm import _M_SHARED_KV, _M_STATE_BYTES, _conv, _dot
from .mamba2_hybrid import (_M_STATE_MOVED, replace_slot_rows, rms_norm,
                            write_prompt_pages, write_token_rows)

LINEAR, FULL = "linear_attention", "full_attention"
L2_EPS = 1e-6
# A test's: the four kernels (the prompt's flash forward, the decode's paged
# attention, the two of the delta rule) in the Pallas interpreter; ``None``
# is the kernels' one rule (``ops/flash_attention.kernel_runs``).  Set
# before the engine builds its programs.
INTERPRET = None


@dataclass(frozen=True)
class OlmoHybridConfig:
    """The published keys under their published names;
    ``linear_chunk_size`` is the chunked scan's (the published config has
    no such key)."""
    vocab_size: int = 100352
    hidden_size: int = 3840
    intermediate_size: int = 11008
    num_hidden_layers: int = 32
    layer_types: tuple = tuple(
        FULL if l % 4 == 3 else LINEAR for l in range(32))
    num_attention_heads: int = 30
    num_key_value_heads: int = 30
    rms_norm_eps: float = 1e-6
    linear_num_key_heads: int = 30
    linear_num_value_heads: int = 30
    linear_key_head_dim: int = 96
    linear_value_head_dim: int = 192
    linear_conv_kernel_dim: int = 4
    linear_allow_neg_eigval: bool = True
    max_position_embeddings: int = 65536
    linear_chunk_size: int = 64
    dtype: object = jnp.bfloat16

    def __post_init__(self):
        object.__setattr__(self, "layer_types", tuple(self.layer_types))
        if (len(self.layer_types) != self.num_hidden_layers
                or set(self.layer_types) - {LINEAR, FULL}):
            raise ValueError(f"layer_types names {LINEAR!r} or {FULL!r} "
                             f"for each of num_hidden_layers layers")
        if FULL not in self.layer_types or LINEAR not in self.layer_types:
            raise ValueError("the cache is one paged group of full layers "
                             "beside the linear layers' per-slot state: "
                             "the model lacks one of the two")
        if self.linear_num_key_heads != self.linear_num_value_heads:
            raise ValueError("the delta rule is written for as many key "
                             "heads as value heads")
        if self.hidden_size % self.num_attention_heads:
            raise ValueError("num_attention_heads does not divide "
                             "hidden_size")
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
    def kv_width(self) -> int:
        return self.num_key_value_heads * self.head_dim

    @property
    def attention_multiplier(self) -> float:
        """The scores' scale, under the name the sibling families' configs
        publish it by."""
        return self.head_dim ** -0.5

    @property
    def key_width(self) -> int:
        return self.linear_num_key_heads * self.linear_key_head_dim

    @property
    def value_width(self) -> int:
        return self.linear_num_value_heads * self.linear_value_head_dim

    @property
    def conv_width(self) -> int:
        """Channels the convolution covers: ``q~``, ``k~`` and ``v~``."""
        return 2 * self.key_width + self.value_width

    @property
    def state_shape(self) -> tuple:
        """A slot's state in one layer, as ``ops/gated_delta.py`` keeps
        it."""
        h, dv = self.linear_num_value_heads, self.linear_value_head_dim
        pack = head_pack(h, dv)
        return (h // pack, self.linear_key_head_dim, pack * dv)

    def serving_model(self) -> "OlmoHybridServing":
        return OlmoHybridServing(self)


# -- parameters ---------------------------------------------------------------

def init_olmo_hybrid(key, cfg: OlmoHybridConfig) -> dict:
    """Parameter pytree, ``layers`` a LIST (one dict a layer).  Projections,
    embedding and head normal 0.02, the two head-wise gates' columns ``2 /
    sqrt(hidden_size)`` (``a`` and ``b`` spread with the stream whatever
    the width); the convolution normal ``d_conv^-0.5``;
    ``A_log = log(uniform(1, 16))`` and ``dt_bias`` such that its softplus
    lies log-uniform in 1e-3..1e-1 (heads that remember thousands of
    tokens beside heads that forget in one); the norms on a branch's output
    ``(2 layers) ** -0.5`` (every branch joins the stream at that size);
    the query, key and gated norms' weights uniform in 0.5..1.5, so that
    none is a value nothing can see."""
    d, f = cfg.hidden_size, cfg.intermediate_size
    h, kw, vw = cfg.linear_num_value_heads, cfg.key_width, cfg.value_width
    kd = cfg.linear_conv_kernel_dim
    dt = cfg.dtype
    std = 0.02
    post = (2.0 * cfg.num_hidden_layers) ** -0.5
    keys = iter(jax.random.split(key, 3 + 10 * cfg.num_hidden_layers))

    def w(shape, scale=std):
        return (jax.random.normal(next(keys), shape, jnp.float32)
                * scale).astype(dt)

    def uniform(shape):
        return jax.random.uniform(next(keys), shape, jnp.float32)

    def seen(width):
        return (0.5 + uniform((width,))).astype(dt)

    def mixer(kind):
        if kind == FULL:
            return {"w_qkv": w((d, 3 * d)), "q_norm": seen(d),
                    "k_norm": seen(d), "w_o": w((d, d))}
        step = jnp.exp(uniform((h,)) * np.log(100.0) + np.log(1e-3))
        return {"w_in": jnp.concatenate(
                    [w((d, cfg.conv_width + vw)),
                     w((d, 2 * h), 2.0 * d ** -0.5)], axis=1),
                "conv_w": w((kd, cfg.conv_width), kd ** -0.5),
                "A_log": jnp.log(1.0 + 15.0 * uniform((h,))).astype(dt),
                "dt_bias": (step + jnp.log(-jnp.expm1(-step))).astype(dt),
                "norm": seen(cfg.linear_value_head_dim),
                "w_o": w((vw, d))}

    def layer(kind):
        return {"mixer": mixer(kind), "norm_mix": jnp.full((d,), post, dt),
                "mlp": {"w1": w((d, 2 * f)), "w2": w((f, d))},
                "norm_mlp": jnp.full((d,), post, dt)}

    return {"embed": w((cfg.vocab_size, d)),
            "layers": [layer(kind) for kind in cfg.layer_types],
            "norm_f": jnp.ones((d,), dt),
            "unembed": w((d, cfg.vocab_size))}


# -- pieces -------------------------------------------------------------------

def join(x, branch, scale, cfg: OlmoHybridConfig):
    """``x + RMSNorm(branch)``: the norm sits on the branch's OUTPUT."""
    return x + rms_norm(branch, scale, cfg.rms_norm_eps, jnp.float32)


def mlp(x, lp, cfg: OlmoHybridConfig):
    """``x + RMSNorm(W_2 (silu(g) * u))``, ``[g | u] = W_1 x``."""
    with jax.named_scope("swiglu"):
        g, u = jnp.split(_dot(x.astype(cfg.dtype), lp["mlp"]["w1"]), 2,
                         axis=-1)
        return join(x, _dot((jax.nn.silu(g) * u).astype(cfg.dtype),
                            lp["mlp"]["w2"]), lp["norm_mlp"], cfg)


def head(x, params, cfg: OlmoHybridConfig):
    return _dot(rms_norm(x, params["norm_f"], cfg.rms_norm_eps, cfg.dtype),
                params["unembed"])


def project(x, ap, cfg: OlmoHybridConfig):
    """A full layer's ``(q, k, v)`` ``[n, hidden_size]`` in the served
    type from the stream ``x [n, d]``: queries and keys normed over the
    WHOLE projection, nothing rotated."""
    d, dt = cfg.hidden_size, cfg.dtype
    qkv = _dot(x.astype(dt), ap["w_qkv"])
    return (rms_norm(qkv[:, :d], ap["q_norm"], cfg.rms_norm_eps, dt),
            rms_norm(qkv[:, d:2 * d], ap["k_norm"], cfg.rms_norm_eps, dt),
            qkv[:, 2 * d:].astype(dt))


def _split_in(x, mp, cfg: OlmoHybridConfig):
    """``[q~ k~ v~ | z | a | b] = x W_in``: the convolution's input in the
    served type (as the tail store holds it: prefill and decode convolve
    the same values), the gate and the two head-wise gates in float32."""
    cw, vw = cfg.conv_width, cfg.value_width
    h = cfg.linear_num_value_heads
    wide = _dot(x.astype(cfg.dtype), mp["w_in"])
    return (wide[..., :cw].astype(cfg.dtype), wide[..., cw:cw + vw],
            wide[..., cw + vw:cw + vw + h], wide[..., cw + vw + h:])


def _delta_inputs(conv, a, b, mp, cfg: OlmoHybridConfig):
    """From the convolved, activated ``[q^ | k^ | v^]`` (float32) and the
    raw gates: ``(q, k [.., H, d_k], v [.., H, d_v])`` in the served type,
    ``g``, ``beta`` ``[.., H]`` float32: the recurrence's operands."""
    kw = cfg.key_width
    h, dk = cfg.linear_num_key_heads, cfg.linear_key_head_dim
    lead = conv.shape[:-1]

    def unit(x):
        x = x.reshape(*lead, h, dk)
        return x * jax.lax.rsqrt(jnp.sum(x * x, axis=-1, keepdims=True)
                                 + L2_EPS)

    q = (unit(conv[..., :kw]) * dk ** -0.5).astype(cfg.dtype)
    k = unit(conv[..., kw:2 * kw]).astype(cfg.dtype)
    v = conv[..., 2 * kw:].astype(cfg.dtype).reshape(
        *lead, h, cfg.linear_value_head_dim)
    g = (-jnp.exp(mp["A_log"].astype(jnp.float32))
         * jax.nn.softplus(a + mp["dt_bias"].astype(jnp.float32)))
    beta = jax.nn.sigmoid(b)
    return q, k, v, g, (2.0 * beta if cfg.linear_allow_neg_eigval else beta)


def _gate_out(o, z, mp, cfg: OlmoHybridConfig):
    """``o [.., H, d_v]`` normed a head at a time FIRST, then gated by
    ``silu(z)``, then ``W_o``."""
    normed = rms_norm(o, mp["norm"], cfg.rms_norm_eps, jnp.float32)
    y = normed.reshape(*o.shape[:-2], cfg.value_width) * jax.nn.silu(z)
    return _dot(y.astype(cfg.dtype), mp["w_o"])


def delta_prefill(x, mp, n_valid, cfg: OlmoHybridConfig):
    """One sequence ``x [t, d]`` from an empty state.  Returns ``(out [t,
    d] float32, state, tail [d_conv - 1, conv_width])``, state and tail
    as they stand after token ``n_valid - 1``."""
    t = x.shape[0]
    kd = cfg.linear_conv_kernel_dim
    qkv, z, a, b = _split_in(x, mp, cfg)
    xp = jnp.concatenate([jnp.zeros((kd - 1, cfg.conv_width), qkv.dtype),
                          qkv])
    conv = _conv(jnp.stack([xp[i:i + t] for i in range(kd)], axis=1), mp)
    q, k, v, g, beta = _delta_inputs(conv, a, b, mp, cfg)
    o, state = gated_delta_chunk_scan(
        q, k, v, g, beta, jnp.zeros(cfg.state_shape, jnp.float32), n_valid,
        chunk=cfg.linear_chunk_size, interpret=INTERPRET)
    tail = jax.lax.dynamic_slice(xp, (n_valid, 0), (kd - 1, cfg.conv_width))
    return _gate_out(o, z, mp, cfg), state, tail


def delta_decode(x, mp, state, tail, layer: int, alive,
                 cfg: OlmoHybridConfig):
    """One token a slot: ``x [b, d]``, ``state`` the WHOLE store ``[layers,
    b, *state_shape]`` of which ``layer``'s live rows are advanced in
    place, ``tail [b, d_conv - 1, conv_width]``.  Returns ``(out, state,
    tail)``."""
    qkv, z, a, b = _split_in(x, mp, cfg)
    window = jnp.concatenate([tail, qkv[:, None]], axis=1)
    q, k, v, g, beta = _delta_inputs(_conv(window, mp), a, b, mp, cfg)
    o, state = gated_delta_step(state, q, k, v, g, beta, alive, layer=layer,
                                interpret=INTERPRET)
    return _gate_out(o, z, mp, cfg), state, window[:, 1:]


# -- whole sequences ----------------------------------------------------------

def prefill_step(params, tokens, n_valid, cfg: OlmoHybridConfig,
                 last_only: bool = True):
    """A padded prompt ``tokens [bucket]`` from empty state; positions
    ``>= n_valid`` are padding, which advances neither state nor tail.
    With ``last_only`` the head runs for token ``n_valid - 1`` alone.

    Returns ``(logits [vocab] of the last real token (or [bucket, vocab]),
    left)``: ``left["k"]``/``["v"] [full layers, bucket, kv_width]``,
    ``left["state"] [linear layers, *state_shape]``, ``left["tail"]
    [linear layers, d_conv - 1, conv_width]``."""
    x = params["embed"][tokens].astype(jnp.float32)
    ks, vs, states, tails = [], [], [], []
    for kind, lp in zip(cfg.layer_types, params["layers"]):
        mp = lp["mixer"]
        if kind == LINEAR:
            with jax.named_scope("gated_delta"):
                mix, state, tail = delta_prefill(x, mp, n_valid, cfg)
            states.append(state)
            tails.append(tail)
        else:
            with jax.named_scope("mha_attention"):
                q, k, v = project(x, mp, cfg)
                mix = _dot(_afmoe.attend_prompt(
                    q, k, v, cfg, interpret=INTERPRET), mp["w_o"])
            ks.append(k)
            vs.append(v)
        x = mlp(join(x, mix, lp["norm_mix"], cfg), lp, cfg)
    if last_only:
        x = jax.lax.dynamic_slice_in_dim(x, n_valid - 1, 1, axis=0)
    logits = head(x, params, cfg)
    return (logits[0] if last_only else logits), {
        "k": jnp.stack(ks), "v": jnp.stack(vs),
        "state": jnp.stack(states), "tail": jnp.stack(tails)}


def forward_full(params, tokens, cfg: OlmoHybridConfig):
    """Every position of one sequence ``tokens [t]``: ``logits [t,
    vocab]``."""
    return prefill_step(params, tokens, jnp.int32(tokens.shape[0]), cfg,
                        last_only=False)[0]


def decode_step(params, tokens, lengths, stores, table,
                cfg: OlmoHybridConfig):
    """One token a slot.  ``tokens [slots]``; ``lengths [slots]``: the
    position of the new token, the count of cached ones (-1: an idle slot,
    whose state stays bit for bit as it is); ``stores = (k_pages, v_pages
    [full layers, pages, page, kv_width], state [linear layers, slots,
    *state_shape], tail [linear layers, slots, d_conv - 1, conv_width])``.

    A full layer attends its own paged layer: on the TPU where the pages
    lie (``afmoe.paged_attend``: ``ops/gqa_paged_attention.py``, every
    query head on its own key/value head), elsewhere over a gathered table
    row (``afmoe.gathered_attend``); ``kernel_runs`` says which, asked
    here and nowhere else.  The new token's own key and value are not in
    the store; the caller writes them at the end.  The state store is
    advanced layer by layer in place.

    Returns ``(logits [slots, vocab], new)``: ``new["k"]``/``["v"] [full
    layers, slots, kv_width]``, ``new["state"]`` and ``new["tail"]``
    whole."""
    k_pages, v_pages, state, tail = stores
    alive = lengths >= 0
    groups = {FULL: (table, 0, k_pages, v_pages)}
    attend = (_afmoe.paged_attend(lengths, groups, cfg, INTERPRET)
              if _paged.kernel_runs(INTERPRET) else
              _afmoe.gathered_attend(lengths, groups, cfg))

    x = params["embed"][tokens].astype(jnp.float32)
    new_k, new_v = [], []
    n_linear = 0
    for kind, lp in zip(cfg.layer_types, params["layers"]):
        mp = lp["mixer"]
        if kind == LINEAR:
            i, n_linear = n_linear, n_linear + 1
            with jax.named_scope("gated_delta"):
                mix, state, t_new = delta_decode(x, mp, state, tail[i], i,
                                                 alive, cfg)
                tail = tail.at[i].set(
                    jnp.where(alive[:, None, None], t_new, tail[i]))
        else:
            with jax.named_scope("mha_attention"):
                q, k, v = project(x, mp, cfg)
                mix = _dot(attend(FULL, len(new_k), q, k, v), mp["w_o"])
            new_k.append(k)
            new_v.append(v)
        x = mlp(join(x, mix, lp["norm_mix"], cfg), lp, cfg)
    return head(x, params, cfg), {
        "k": jnp.stack(new_k), "v": jnp.stack(new_v), "state": state,
        "tail": tail}


# -- what the serving engine asks ---------------------------------------------

class OlmoHybridServing:
    """The serving protocol (serving/models.py) for this model: one paged
    layer a full layer (a POOL of pages where the engine is given a byte
    budget: no chip holds every slot at capacity beside the state) and two
    per-slot stores."""

    speculative = False        # no verify / propose programs
    tensor_parallel = False
    tensor_parallel_why = ("its per-slot state stores are not written "
                           "for a sharded model axis")
    prefix_cache = False
    prefix_cache_why = ("delta-rule state and convolution tails are per "
                        "slot and not page-addressable: a cached prefix "
                        "page carries none of them (snapshots of "
                        "recurrent state are not written yet)")
    slot_state = True          # prefill is told which slot it fills

    def __init__(self, cfg: OlmoHybridConfig) -> None:
        self.cfg = cfg
        self.n_linear = cfg.layer_types.count(LINEAR)
        self.n_full = cfg.layer_types.count(FULL)
        # A slot's recurrent bytes in ONE linear layer: the float32 state
        # and the tail in the served type.
        self.slot_layer_bytes = (
            4 * int(np.prod(cfg.state_shape))
            + (cfg.linear_conv_kernel_dim - 1) * cfg.conv_width
            * jnp.dtype(cfg.dtype).itemsize)

    def identity(self) -> dict:
        c = self.cfg
        return {"family": "olmo_hybrid", "vocab_size": c.vocab_size,
                "hidden_size": c.hidden_size,
                "intermediate_size": c.intermediate_size,
                "layer_types": list(c.layer_types),
                "heads": [c.num_attention_heads, c.num_key_value_heads],
                "linear": [c.linear_num_key_heads, c.linear_num_value_heads,
                           c.linear_key_head_dim, c.linear_value_head_dim,
                           c.linear_conv_kernel_dim,
                           c.linear_allow_neg_eigval, c.linear_chunk_size],
                "rms_norm_eps": c.rms_norm_eps,
                "max_seq_len": c.max_seq_len,
                "dtype": jnp.dtype(c.dtype).name}

    def cache_entry(self) -> dict:
        """A paged layer (keys, values) for each full layer and the
        per-slot stores, each ``[layers, slots, *shape]`` in the cache
        manager.  The decode reads the pages in place and asks for no
        room to gather into."""
        c = self.cfg
        return {"n_layers": self.n_full,
                "n_heads": c.num_key_value_heads, "head_dim": c.head_dim,
                "widths": (c.kv_width,) * 2,
                "slot_stores": (
                    {"name": "delta_state", "kind": "state",
                     "shape": (self.n_linear, *c.state_shape),
                     "dtype": jnp.float32},
                    {"name": "conv_tail", "kind": "state",
                     "shape": (self.n_linear, c.linear_conv_kernel_dim - 1,
                               c.conv_width),
                     "dtype": c.dtype})}

    def observe_stores(self, nbytes: dict) -> None:
        """Bytes of the per-slot stores by kind, once at build."""
        _M_STATE_BYTES.set(nbytes.get("state", 0))

    def decode_view(self, lengths, page_size, pages_per_slot) -> float:
        """Positions a slot a full layer the decode program reads of the
        paged store at these (host) lengths: the live slots' entries in
        use, whole pages (what the kernel copies; its twin gathers the
        whole rows and masks the rest), over the slots."""
        return (_paged.tokens_read(lengths, pages_per_slot, page_size)
                / len(lengths))

    def observe_launch(self, lengths) -> None:
        """Count what a decode iteration attends and what state it must
        move, from the host's lengths of its launch (what
        ``Mamba2HybridServing`` counts of its own)."""
        live = lengths[lengths >= 0].astype(np.int64)
        _M_SHARED_KV.inc(int((live + 1).sum()))
        _M_STATE_MOVED.inc(2 * len(live) * self.n_linear
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
