"""Plain reference for the shortcut-connected mixture-of-experts decoder as
``meituan-longcat/LongCat-Flash-Omni`` configures it (the language
decoder): the forward pass in straightforward ``jax.numpy`` and float32,
full causal attention with keys and values rebuilt from the latent, no
cache, no batching, no absorbed form, no sorted or grouped products.

It imports nothing of the program.  For decoder layer ``i`` and residual
stream ``x`` of a sequence (all norms RMSNorm, a final norm, an untied
head)::

    for j in (0, 1):
        h = norm_in[j](x)
        cq = norm_q(h W_dq);  [q_nope | q_rope] = (cq W_uq) * sqrt(d / q_rank)
        [c | k_r] = h W_dkv;  c = norm_kv(c) * sqrt(d / kv_rank)
        q_rope, k_r = rope(q_rope), rope(k_r);  [k_nope | v] = c W_ukv
        a = softmax(([q_nope|q_rope] . [k_nope|k_r]) / sqrt(nope + rope), causal) v
        x = x + a W_o
        g = norm_post[j](x)
        if j == 0:
            s = softmax(g W_r) over real + zero-compute outputs
            E = top_k(s + b);  w_e = routed_scaling_factor * s_e
            m = sum_{e in E, real, HELD HERE} w_e SwiGLU_e(g)
                + sum_{e in E, zero-compute} w_e g
        x = x + SwiGLU_dense[j](g)
    x = x + m

The share is the configuration's: ``n_routed_experts`` real experts from
``expert_offset`` on are held, of the ``n_routed_experts_published`` the
router scores before its ``zero_expert_num`` zero-compute outputs; what
the absent ones would add is left out, and that partial result goes on to
the next layer.  The zero-compute term is computed where the token lives,
so it is computed here.  Readings of what the config does not state, as
the configuration's ``assumed`` lists them: the zero-compute outputs are
the router's LAST ones; the chosen weights are not renormalised; rotary
pairs are (i, i + rope/2), plain frequencies ``theta^(-2i/rope)``.

The parameter tree has the program's shape (``layers`` is a list; a
layer's two sublayers are lists of two under ``attn``, ``ffn_norm`` and
``ffn``), so one seeded tree feeds both sides.  ``served_logits`` upcasts
ONE attention, ONE dense feed-forward, ONE expert at a time and takes the
sequences one by one: a whole layer in float32 (4.97 GB at the published
widths) does not fit beside the weights the program holds.
"""

from __future__ import annotations

import functools
import json

import jax
import jax.numpy as jnp
import numpy as np

from benchmark import precision as P

HEAD_BLOCK = 8      # heads whose [s, s] scores are alive at once


# -- seeded weights (the benchmark's own, not the program's) -----------------

def router_outputs(model: dict) -> int:
    return model["n_routed_experts_published"] + model["zero_expert_num"]


def _tree(model: dict, leaf):
    """The program's tree, every leaf made by ``leaf(shape, scale,
    dtype)`` (``scale`` None: a norm's ones; ``dtype`` None: the served
    type)."""
    d, f, fm = (model["hidden_size"], model["ffn_hidden_size"],
                model["expert_ffn_hidden_size"])
    h_n, rq, rkv = (model["num_attention_heads"], model["q_lora_rank"],
                    model["kv_lora_rank"])
    nope, rp, vd = (model["qk_nope_head_dim"], model["qk_rope_head_dim"],
                    model["v_head_dim"])
    e, v = model["n_routed_experts"], model["vocab_size"]
    std = model["initializer_range"]
    res = std / (2 * 2 * model["num_layers"]) ** 0.5
    outputs = router_outputs(model)

    def attn():
        return {"norm": leaf((d,), None),
                "w_dq": leaf((d, rq), std), "q_norm": leaf((rq,), None),
                "w_uq": leaf((rq, h_n * (nope + rp)), std),
                "w_dkv": leaf((d, rkv + rp), std),
                "kv_norm": leaf((rkv,), None),
                "w_ukv": leaf((rkv, h_n * (nope + vd)), std),
                "w_o": leaf((h_n * vd, d), res)}

    def ffn():
        return {"w_gate": leaf((d, f), std), "w_up": leaf((d, f), std),
                "w_down": leaf((f, d), res)}

    def layer():
        return {"attn": [attn(), attn()],
                "ffn_norm": [leaf((d,), None), leaf((d,), None)],
                "ffn": [ffn(), ffn()],
                "router": leaf((d, outputs),
                               model["router_logit_std"] / d ** 0.5),
                "router_bias": leaf((outputs,), model["router_bias_std"],
                                    jnp.float32),
                "w_gate": leaf((e, d, fm), std),
                "w_up": leaf((e, d, fm), std),
                "w_down": leaf((e, fm, d), res)}

    return {
        "embed": leaf((v, d), std),
        "layers": [layer() for _ in range(model["num_layers"])],
        "norm_f": leaf((d,), None),
        "unembed": leaf((d, v), std),
    }


def init_params(model: dict, seed: int):
    """Normal init (residual projections scaled by the number of
    sublayers; router rows such that ``g W_r`` has a standard deviation
    near ``router_logit_std``, so the softmax's chosen few carry a real
    share of the mass; the score-correction bias normal
    ``router_bias_std``, float32), drawn ON THE DEVICE leaf by leaf from
    the seed and rounded to the served type."""
    dt = jnp.dtype(model["dtype"])

    @jax.jit
    def make(key):
        count = iter(range(10 ** 6))

        def leaf(shape, scale, dtype=None):
            dtype = dtype or dt
            if scale is None:
                return jnp.ones(shape, dtype)
            k = jax.random.fold_in(key, next(count))
            return (jax.random.normal(k, shape, jnp.float32)
                    * scale).astype(dtype)

        return _tree(model, leaf)

    return make(P.key_from_seed(seed))


# -- the model ---------------------------------------------------------------

def _rms(x, w, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True)
                             + eps) * w


def inv_freq(model: dict) -> np.ndarray:
    dim, base = model["qk_rope_head_dim"], float(model["rope_theta"])
    return (1.0 / base ** (np.arange(0, dim, 2, dtype=np.float64) / dim)
            ).astype(np.float32)


def softmax_scale(model: dict) -> float:
    return (model["qk_nope_head_dim"] + model["qk_rope_head_dim"]) ** -0.5


def lora_scales(model: dict) -> tuple:
    d = model["hidden_size"]
    return ((d / model["q_lora_rank"]) ** 0.5
            if model["mla_scale_q_lora"] else 1.0,
            (d / model["kv_lora_rank"]) ** 0.5
            if model["mla_scale_kv_lora"] else 1.0)


def _rope(x, pos, model):
    """``x [s, (heads,) rope]`` at positions ``pos [s]``; pairs are
    (i, i + rope/2)."""
    ang = pos.astype(jnp.float32)[:, None] * jnp.asarray(inv_freq(model))
    cos, sin = jnp.cos(ang), jnp.sin(ang)
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
    q_scale, kv_scale = lora_scales(model)
    pos = jnp.arange(s)
    h = _rms(x, ap["norm"], eps)
    c_q = _rms(dot(h, ap["w_dq"]), ap["q_norm"], eps)
    q = (dot(c_q, ap["w_uq"]) * q_scale).reshape(s, h_n, nope + rp)
    q = jnp.concatenate([q[..., :nope], _rope(q[..., nope:], pos, model)],
                        axis=-1)
    ckv = dot(h, ap["w_dkv"])
    c = _rms(ckv[:, :rkv], ap["kv_norm"], eps) * kv_scale
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


def swiglu(f, h, mode: str):
    """``f``: ``w_gate``, ``w_up``, ``w_down`` of one feed-forward."""
    dot = P.binary(jnp.dot, mode)
    return dot(jax.nn.silu(dot(h, f["w_gate"])) * dot(h, f["w_up"]),
               f["w_down"])


def route(model: dict, g, router, bias, mode: str):
    """``(experts [s, k], weights [s, k])`` over ALL of the router's
    outputs: chosen by ``s + b``, weighted by ``s``."""
    scores = jax.nn.softmax(P.binary(jnp.dot, mode)(g, router), axis=-1)
    _, idx = jax.lax.top_k(scores + bias, model["moe_topk"])
    return idx, (jnp.take_along_axis(scores, idx, axis=-1)
                 * model["routed_scaling_factor"])


def expert_weight(idx, w, e: int):
    """``[s]``: what the router gave output ``e`` at each token (zero
    where unchosen)."""
    return jnp.sum(jnp.where(idx == e, w, 0.0), axis=-1)


def zero_term(model: dict, g, idx, w):
    """The zero-compute experts' part: each chosen one returns its
    input."""
    first = model["n_routed_experts_published"]
    return jnp.sum(jnp.where(idx >= first, w, 0.0), axis=-1)[:, None] * g


def expert_ffn(model: dict, lp, g, mode: str):
    """The expert layer on normed ``g [s, d]``: the zero-compute term
    and, of the real experts, those held here (``lp["w_gate"]`` holds
    experts ``expert_offset ..``), each over every token and weighted by
    what the router gave it there."""
    idx, w = route(model, g, lp["router"], lp["router_bias"], mode)
    m = zero_term(model, g, idx, w)
    for e in range(lp["w_gate"].shape[0]):
        f = {k: lp[k][e] for k in ("w_gate", "w_up", "w_down")}
        m = m + (expert_weight(idx, w, model["expert_offset"] + e)[:, None]
                 * swiglu(f, g, mode))
    return m


def decoder_layer(model: dict, lp, x, mode: str):
    """One decoder layer on one sequence ``x [s, d]``."""
    eps = model["rms_norm_eps"]
    m = None
    for j in (0, 1):
        x = x + attention(model, lp["attn"][j], x, mode)
        g = _rms(x, lp["ffn_norm"][j], eps)
        if j == 0:
            m = expert_ffn(model, lp, g, mode)
        x = x + swiglu(lp["ffn"][j], g, mode)
    return x + m


def head(model: dict, norm_f, unembed, x, mode: str):
    return P.binary(jnp.dot, mode)(
        _rms(x, norm_f, model["rms_norm_eps"]), unembed)


@functools.lru_cache(maxsize=None)
def _programs(model_json: str, mode: str):
    model = json.loads(model_json)
    eps = model["rms_norm_eps"]

    def routed(router, bias, g):
        idx, w = route(model, g, router, bias, mode)
        return idx, w, zero_term(model, g, idx, w)

    return {
        "attn": jax.jit(lambda ap, x: x + attention(model, ap, x, mode)),
        "norm": jax.jit(lambda w, x: _rms(x, w, eps)),
        "route": jax.jit(routed),
        "expert": jax.jit(lambda f, g, idx, w, e, m: m + (
            expert_weight(idx, w, e)[:, None] * swiglu(f, g, mode))),
        "ffn": jax.jit(lambda f, g, x: x + swiglu(f, g, mode)),
        "head": jax.jit(lambda n, u, x: head(model, n, u, x, mode)),
    }


def _f32(tree):
    return jax.tree_util.tree_map(lambda a: a.astype(jnp.float32), tree)


def served_logits(model: dict, params, sequences, mode: str = "f32"):
    """Reference logits of whole served sequences: ``sequences`` is a
    list of token lists (prompt + served tokens); the result is a list of
    ``[len(sequence), vocab]`` float32 arrays.  Blocks outermost: one
    attention's, one dense feed-forward's or one expert's weights are
    upcast, every sequence goes through them, the upcast copy is
    dropped."""
    width = max(len(s) for s in sequences)
    width += -width % 128         # one shape for a mix: one compile
    run = _programs(json.dumps(model, sort_keys=True), mode)
    with jax.default_matmul_precision("highest"):
        xs = []
        for s in sequences:
            row = np.zeros((width,), np.int32)
            row[:len(s)] = s          # causal: right padding is inert
            xs.append(params["embed"][jnp.asarray(row)].astype(jnp.float32))
        for lp in params["layers"]:
            ms = None
            for j in (0, 1):
                ap = _f32(lp["attn"][j])
                xs = [run["attn"](ap, x) for x in xs]
                del ap
                w = lp["ffn_norm"][j].astype(jnp.float32)
                gs = [run["norm"](w, x) for x in xs]
                if j == 0:
                    router = lp["router"].astype(jnp.float32)
                    routed = [run["route"](router, lp["router_bias"], g)
                              for g in gs]
                    ms = [r[2] for r in routed]
                    for e in range(lp["w_gate"].shape[0]):
                        f = _f32({k: lp[k][e]
                                  for k in ("w_gate", "w_up", "w_down")})
                        at = jnp.int32(model["expert_offset"] + e)
                        ms = [run["expert"](f, g, r[0], r[1], at, m)
                              for g, r, m in zip(gs, routed, ms)]
                        del f
                    del routed
                f = _f32(lp["ffn"][j])
                xs = [run["ffn"](f, g, x) for g, x in zip(gs, xs)]
                del f, gs
            xs = [x + m for x, m in zip(xs, ms)]
        norm_f = params["norm_f"].astype(jnp.float32)
        unembed = params["unembed"].astype(jnp.float32)
        return [np.asarray(run["head"](norm_f, unembed, x))[:len(s)]
                for x, s in zip(xs, sequences)]
