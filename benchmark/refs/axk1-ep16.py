"""Plain reference for the latent-attention mixture-of-experts family as
``skt/A.X-K1`` configures it: the forward pass in straightforward
``jax.numpy`` and float32, full causal attention with keys and values
rebuilt from the latent, no cache, no batching, no absorbed form, no
sorted or grouped products.

It imports nothing of the program.  For hidden ``x`` of a token at
position ``t`` (all norms RMSNorm, pre-norm residual blocks, a final
norm, an untied head):

* attention: ``c_q = norm(x W_dq)``; ``[q_nope | q_rope] = c_q W_uq``
  a head; ``[c_kv | k_r] = x W_dkv``; ``c = norm(c_kv)``; ``q_rope``,
  ``k_rope`` = rope_t of ``q_rope``, ``k_r`` (``k_rope`` shared by all
  heads); ``[k_nope | v] = c W_ukv`` a head; ``s = (q_nope . k_nope +
  q_rope . k_rope) * scale``, causal softmax, ``o = sum p v``, out =
  ``concat_heads(o) W_o``.  YaRN as the DeepSeek family computes it;
  ``scale = (nope + rope)^-0.5 * m^2``, ``m = 0.1 ln(factor) + 1``.
* dense layers: ``down(silu(gate(h)) * up(h))``.
* expert layers: ``g = sigmoid(h W_r)`` over ALL routed experts, the
  ``num_experts_per_tok`` largest, weights ``g_e / sum of the chosen *
  routed_scaling_factor``; ``y = shared(h) + sum over the chosen experts
  HELD HERE of w_e expert_e(h)``.

The share is the configuration's: ``n_routed_experts`` experts from
``expert_offset`` on are held, of ``n_routed_experts_published`` the
router scores; what the absent ones would add is left out, and that
partial result goes on to the next layer (the configuration file says
why).  Departures and readings, as the configuration's ``assumed``
lists them: ``topk_method`` ``"none"`` is plain top-k over all scores
(no groups, no score-correction bias); rotary pairs are (i, i + rope/2).

The parameter tree has the program's shape (``layers`` is a list, one
dict of weights a layer), so one seeded tree feeds both sides.  ``served_logits`` upcasts ONE layer at a time and takes the
sequences one by one, so it fits beside the weights the program holds.
"""

from __future__ import annotations

import functools
import json
import math

import jax
import jax.numpy as jnp
import numpy as np

from benchmark import precision as P

HEAD_BLOCK = 8      # heads whose [s, s] scores are alive at once


# -- seeded weights (the benchmark's own, not the program's) -----------------

def _tree(model: dict, leaf):
    """The program's tree, every leaf made by ``leaf(shape, scale)``
    (``scale`` None: a norm's ones)."""
    d, f, fm = (model["hidden_size"], model["intermediate_size"],
                model["moe_intermediate_size"])
    h_n, rq, rkv = (model["num_attention_heads"], model["q_lora_rank"],
                    model["kv_lora_rank"])
    nope, rp, vd = (model["qk_nope_head_dim"], model["qk_rope_head_dim"],
                    model["v_head_dim"])
    e, v = model["n_routed_experts"], model["vocab_size"]
    std = model["initializer_range"]
    res = std / (2 * model["num_hidden_layers"]) ** 0.5

    def attn():
        return {"norm": leaf((d,), None),
                "w_dq": leaf((d, rq), std), "q_norm": leaf((rq,), None),
                "w_uq": leaf((rq, h_n * (nope + rp)), std),
                "w_dkv": leaf((d, rkv + rp), std),
                "kv_norm": leaf((rkv,), None),
                "w_ukv": leaf((rkv, h_n * (nope + vd)), std),
                "w_o": leaf((h_n * vd, d), res)}

    def ffn(width):
        return {"w_gate": leaf((d, width), std),
                "w_up": leaf((d, width), std),
                "w_down": leaf((width, d), res)}

    def layer(i):
        if i < model["first_k_dense_replace"]:
            return {"attn": attn(), "ffn_norm": leaf((d,), None),
                    "ffn": ffn(f)}
        return {"attn": attn(), "ffn_norm": leaf((d,), None),
                "router": leaf((d, model["n_routed_experts_published"]),
                               model["router_logit_std"] / d ** 0.5),
                "shared": ffn(fm * model["n_shared_experts"]),
                "w_gate": leaf((e, d, fm), std),
                "w_up": leaf((e, d, fm), std),
                "w_down": leaf((e, fm, d), res)}

    return {
        "embed": leaf((v, d), std),
        "layers": [layer(i) for i in range(model["num_hidden_layers"])],
        "norm_f": leaf((d,), None),
        "unembed": leaf((d, v), std),
    }


def init_params(model: dict, seed: int):
    """Normal init (residual projections scaled by depth; router rows
    such that ``h W_r`` has a standard deviation near
    ``router_logit_std``, so the sigmoid scores spread), drawn ON THE
    DEVICE leaf by leaf from the seed and rounded to the served type."""
    dt = jnp.dtype(model["dtype"])

    @jax.jit
    def make(key):
        count = iter(range(10 ** 6))

        def leaf(shape, scale):
            if scale is None:
                return jnp.ones(shape, dt)
            k = jax.random.fold_in(key, next(count))
            return (jax.random.normal(k, shape, jnp.float32)
                    * scale).astype(dt)

        return _tree(model, leaf)

    return make(P.key_from_seed(seed))


# -- the model ---------------------------------------------------------------

def _rms(x, w, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True)
                             + eps) * w


def yarn_inv_freq(model: dict) -> np.ndarray:
    r = model["rope_scaling"]
    dim, base = model["qk_rope_head_dim"], float(model["rope_theta"])
    extra = 1.0 / base ** (np.arange(0, dim, 2, dtype=np.float64) / dim)

    def correction_dim(rotations):
        return (dim * math.log(r["original_max_position_embeddings"]
                               / (rotations * 2 * math.pi))
                / (2 * math.log(base)))

    low = max(math.floor(correction_dim(r["beta_fast"])), 0)
    high = min(math.ceil(correction_dim(r["beta_slow"])), dim - 1)
    if low == high:
        high += 0.001
    ramp = np.clip((np.arange(dim // 2) - low) / (high - low), 0.0, 1.0)
    return (extra / r["factor"] * ramp + extra * (1 - ramp)).astype(
        np.float32)


def _mscale(factor, m):
    return 1.0 if factor <= 1 else 0.1 * m * math.log(factor) + 1.0


def softmax_scale(model: dict) -> float:
    r = model["rope_scaling"]
    m = _mscale(r["factor"], r["mscale_all_dim"])
    return (model["qk_nope_head_dim"]
            + model["qk_rope_head_dim"]) ** -0.5 * m * m


def _rope(x, pos, model):
    """``x [s, (heads,) rope]`` at positions ``pos [s]``; pairs are
    (i, i + rope/2)."""
    r = model["rope_scaling"]
    ang = pos.astype(jnp.float32)[:, None] * jnp.asarray(
        yarn_inv_freq(model))
    m = _mscale(r["factor"], r["mscale"]) / _mscale(r["factor"],
                                                    r["mscale_all_dim"])
    cos, sin = jnp.cos(ang) * m, jnp.sin(ang) * m
    if x.ndim == 3:
        cos, sin = cos[:, None, :], sin[:, None, :]
    x1, x2 = jnp.split(x, 2, axis=-1)
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin],
                           axis=-1)


def attention(model: dict, ap, x, mode: str):
    """One sequence ``x [s, d]``; returns the block's output (before the
    residual)."""
    dot = P.binary(jnp.dot, mode)
    qk = P.binary(lambda q, k: jnp.einsum("qhd,khd->hqk", q, k), mode)
    pv = P.binary(lambda p, v: jnp.einsum("hqk,khd->qhd", p, v), mode)
    s = x.shape[0]
    h_n, nope, rp = (model["num_attention_heads"], model["qk_nope_head_dim"],
                     model["qk_rope_head_dim"])
    rkv, eps = model["kv_lora_rank"], model["rms_norm_eps"]
    pos = jnp.arange(s)
    h = _rms(x, ap["norm"], eps)
    c_q = _rms(dot(h, ap["w_dq"]), ap["q_norm"], eps)
    q = dot(c_q, ap["w_uq"]).reshape(s, h_n, nope + rp)
    q = jnp.concatenate([q[..., :nope], _rope(q[..., nope:], pos, model)],
                        axis=-1)
    ckv = dot(h, ap["w_dkv"])
    c = _rms(ckv[:, :rkv], ap["kv_norm"], eps)
    k_rope = _rope(ckv[:, rkv:], pos, model)
    kv = dot(c, ap["w_ukv"]).reshape(s, h_n, -1)
    k = jnp.concatenate(
        [kv[..., :nope], jnp.broadcast_to(k_rope[:, None, :],
                                          (s, h_n, rp))], axis=-1)
    v = kv[..., nope:]
    causal = jnp.tril(jnp.ones((s, s), bool))
    outs = []
    for lo in range(0, h_n, HEAD_BLOCK):       # memory only: heads are
        hi = lo + HEAD_BLOCK                   # independent
        scores = qk(q[:, lo:hi], k[:, lo:hi]) * softmax_scale(model)
        p = jax.nn.softmax(jnp.where(causal, scores, -jnp.inf), axis=-1)
        outs.append(pv(p, v[:, lo:hi]))
    return dot(jnp.concatenate(outs, axis=1).reshape(s, -1), ap["w_o"])


def _swiglu(dot, h, w_gate, w_up, w_down):
    return dot(jax.nn.silu(dot(h, w_gate)) * dot(h, w_up), w_down)


def route(model: dict, h, router, mode: str):
    """``(experts [s, k], weights [s, k])`` over ALL routed experts."""
    g = jax.nn.sigmoid(P.binary(jnp.dot, mode)(h, router))
    gate, idx = jax.lax.top_k(g, model["num_experts_per_tok"])
    if model["norm_topk_prob"]:
        gate = gate / jnp.sum(gate, axis=-1, keepdims=True)
    return idx, gate * model["routed_scaling_factor"]


def expert_ffn(model: dict, lp, h, mode: str):
    """The expert layer's feed-forward on normed ``h [s, d]``: the shared
    expert, and of the routed ones those held here (``lp["w_gate"]``
    holds experts ``expert_offset ..``), each over every token and
    weighted by what the router gave it there (zero where unchosen)."""
    dot = P.binary(jnp.dot, mode)
    idx, w = route(model, h, lp["router"], mode)
    sh = lp["shared"]
    y = _swiglu(dot, h, sh["w_gate"], sh["w_up"], sh["w_down"])
    for e in range(lp["w_gate"].shape[0]):
        w_e = jnp.sum(jnp.where(idx == model["expert_offset"] + e, w, 0.0),
                      axis=-1)
        y = y + w_e[:, None] * _swiglu(dot, h, lp["w_gate"][e],
                                       lp["w_up"][e], lp["w_down"][e])
    return y


def dense_layer(model: dict, lp, x, mode: str):
    x = x + attention(model, lp["attn"], x, mode)
    h = _rms(x, lp["ffn_norm"], model["rms_norm_eps"])
    f = lp["ffn"]
    return x + _swiglu(P.binary(jnp.dot, mode), h, f["w_gate"], f["w_up"],
                       f["w_down"])


def moe_layer(model: dict, lp, x, mode: str):
    x = x + attention(model, lp["attn"], x, mode)
    h = _rms(x, lp["ffn_norm"], model["rms_norm_eps"])
    return x + expert_ffn(model, lp, h, mode)


def head(model: dict, norm_f, unembed, x, mode: str):
    return P.binary(jnp.dot, mode)(
        _rms(x, norm_f, model["rms_norm_eps"]), unembed)


@functools.lru_cache(maxsize=None)
def _programs(model_json: str, mode: str):
    model = json.loads(model_json)
    return (jax.jit(lambda lp, x: dense_layer(model, lp, x, mode)),
            jax.jit(lambda lp, x: moe_layer(model, lp, x, mode)),
            jax.jit(lambda n, u, x: head(model, n, u, x, mode)))


def served_logits(model: dict, params, sequences, mode: str = "f32"):
    """Reference logits of whole served sequences: ``sequences`` is a
    list of token lists (prompt + served tokens); the result is a list of
    ``[len(sequence), vocab]`` float32 arrays.  Layers outermost: one
    layer's weights are upcast, every sequence goes through it, the
    upcast copy is dropped."""
    width = max(len(s) for s in sequences)
    width += -width % 128         # one shape for a mix: one compile
    dense, moe, out = _programs(json.dumps(model, sort_keys=True), mode)
    nd = model["first_k_dense_replace"]
    with jax.default_matmul_precision("highest"):
        xs = []
        for s in sequences:
            row = np.zeros((width,), np.int32)
            row[:len(s)] = s          # causal: right padding is inert
            xs.append(params["embed"][jnp.asarray(row)].astype(jnp.float32))
        for i, lp in enumerate(params["layers"]):
            lp = jax.tree_util.tree_map(lambda a: a.astype(jnp.float32), lp)
            xs = [(dense if i < nd else moe)(lp, x) for x in xs]
            del lp
        norm_f = params["norm_f"].astype(jnp.float32)
        unembed = params["unembed"].astype(jnp.float32)
        return [np.asarray(out(norm_f, unembed, x))[:len(s)]
                for x, s in zip(xs, sequences)]
