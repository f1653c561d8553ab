"""Plain reference for ``deepseek-ai/DeepSeek-V3.2-Exp``: latent attention
with a lightning indexer that chooses, for every query, the cached tokens
the attention may read (DeepSeek Sparse Attention), dense and expert
layers with group-limited routing.  The forward pass in straightforward
``jax.numpy`` and float32, no cache, no batching, no absorbed form, no
kernels, no sorted or grouped products.  It imports nothing of the program.

With ``h`` the normed input of a layer (all norms RMSNorm but the
indexer's key norm, pre-norm residual blocks, a final norm, an untied
head), ``t`` a query position and ``s <= t`` a cached one:

* latent attention: ``c_q = rms(h W_dq)``; ``[q_nope | q_rope] = c_q W_uq``
  a head; ``[c_kv | k_r] = h W_dkv``; ``c = rms(c_kv)``; ``q_rope``,
  ``k_rope`` = rope_t of ``q_rope``, ``k_r`` (``k_rope`` shared by all
  heads); ``[k_nope | v] = c W_ukv`` a head; ``a = (q_nope . k_nope +
  q_rope . k_rope) * scale``; softmax over the SELECTED ``s`` only; out =
  ``concat_heads(sum p v) W_o``.  YaRN as the DeepSeek family computes
  it; ``scale = (nope + rope)^-0.5 * m^2``, ``m = 0.1 ln(factor) + 1``.
* the indexer: ``q^I_{t,j} = (c_q W_qI)_j`` for ``j`` in ``index_n_heads``
  heads of ``index_head_dim``, the FIRST ``qk_rope_head_dim`` dims of
  each rotated; ``k^I_s = layernorm(h_s W_kI)`` (one head; weight and
  bias; its first rope dims rotated); both multiplied by the orthonormal
  Hadamard matrix of ``index_head_dim`` (the source does so before it
  quantises them; the products are unchanged by it in exact arithmetic);
  ``w_{t,j} = (h_t W_w)_j * index_n_heads^-0.5 * index_head_dim^-0.5``;
  ``I_{t,s} = sum_j w_{t,j} relu(q^I_{t,j} . k^I_s)``.
* selection: ``S_t`` = the ``min(index_topk, t + 1)`` positions ``s <= t``
  of largest ``I_{t,s}`` (``jax.lax.top_k`` on float32 scores); attention
  scores outside ``S_t`` are ``-inf`` before the softmax.  ``select``
  takes the two controls beside the model's rule ``"topk"``: ``"all"``
  (every ``s <= t``: no selection) and ``"recent"`` (the last
  ``index_topk``).
* dense layers: ``down(silu(gate(h)) * up(h))``.
* expert layers: ``g = sigmoid(h W_r)`` over ALL routed experts; CHOICE by
  ``g + b`` (``e_score_correction_bias``), group-limited: the
  ``topk_group`` best of ``n_group`` groups by the sum of each group's
  two best biased scores, then the ``num_experts_per_tok`` best among
  those groups' experts; weights ``g_e / sum of the chosen *
  routed_scaling_factor`` from the UNBIASED scores; ``y = shared(h) + sum
  over the chosen experts HELD HERE of w_e expert_e(h)``.

The share is the configuration's: ``n_routed_experts`` experts from
``expert_offset`` on are held, of ``n_routed_experts_published`` the
router scores; what the absent ones would add is left out, and that
partial result goes on to the next layer.  Assumed, as the configuration
lists: rotary pairs are (i, i + rope/2) in the attention and the indexer
alike; the LayerNorm's epsilon is 1e-6.

The parameter tree has the program's shape, so one seeded tree feeds both
sides.  ``served_logits`` is written to fit beside the weights the program
holds: sequences one by one through small jitted pieces, a matrix upcast
only while it is used (the attention's, ONE expert's, a column block of
the dense layer's), queries in blocks of ``Q_BLOCK`` and heads in groups
of ``HEAD_GROUP``, the selection kept as one ``[s, s]`` mask a layer.
"""

from __future__ import annotations

import functools
import json
import math

import jax
import jax.numpy as jnp
import numpy as np

from benchmark import precision as P

Q_BLOCK = 256       # queries whose scores against every key are alive at once
HEAD_GROUP = 8      # attention heads alive at once
INDEX_HEAD_GROUP = 16   # indexer heads alive at once
FFN_BLOCK = 4608    # columns of the dense layer upcast at once
LN_EPS = 1e-6


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
    ih, idim = model["index_n_heads"], model["index_head_dim"]
    e, v = model["n_routed_experts"], model["vocab_size"]
    n_out = model["n_routed_experts_published"]
    std = model["initializer_range"]
    res = std / (2 * model["num_hidden_layers"]) ** 0.5

    def attn():
        return {"norm": leaf((d,), None),
                "w_dq": leaf((d, rq), std), "q_norm": leaf((rq,), None),
                "w_uq": leaf((rq, h_n * (nope + rp)),
                             std * model["query_gain"]),
                "w_dkv": leaf((d, rkv + rp), std),
                "kv_norm": leaf((rkv,), None),
                "w_ukv": leaf((rkv, h_n * (nope + vd)), std),
                "w_o": leaf((h_n * vd, d), res),
                "w_qi": leaf((rq, ih * idim), std),
                "w_ki": leaf((d, idim), std),
                "ki_norm": leaf((idim,), None),
                "ki_bias": leaf((idim,), std),
                "w_w": leaf((d, ih), std)}

    def ffn(width):
        return {"w_gate": leaf((d, width), std),
                "w_up": leaf((d, width), std),
                "w_down": leaf((width, d), res)}

    def layer(i):
        if i < model["first_k_dense_replace"]:
            return {"attn": attn(), "ffn_norm": leaf((d,), None),
                    "ffn": ffn(f)}
        return {"attn": attn(), "ffn_norm": leaf((d,), None),
                "router": leaf((d, n_out),
                               model["router_logit_std"] / d ** 0.5),
                "router_bias": leaf((n_out,), model["router_bias_std"]),
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
    """Normal init (the configuration's ``assumed.weights`` has every
    scale and why), drawn ON THE DEVICE leaf by leaf from the seed and
    rounded to the served type; the router's bias stays float32."""
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

        tree = _tree(model, leaf)
        for lp in tree["layers"]:
            if "router_bias" in lp:
                lp["router_bias"] = lp["router_bias"].astype(jnp.float32)
        return tree

    return make(P.key_from_seed(seed))


# -- the model ---------------------------------------------------------------

def _rms(x, w, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True)
                             + eps) * w


def _layernorm(x, w, b):
    mu = jnp.mean(x, axis=-1, keepdims=True)
    var = jnp.mean(jnp.square(x - mu), axis=-1, keepdims=True)
    return (x - mu) * jax.lax.rsqrt(var + LN_EPS) * w + b


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


def hadamard(n: int) -> np.ndarray:
    """The orthonormal Hadamard matrix of a power of two (Sylvester's)."""
    h = np.ones((1, 1))
    while h.shape[0] < n:
        h = np.block([[h, h], [h, -h]])
    if h.shape[0] != n:
        raise ValueError(f"index_head_dim {n} is no power of two")
    return (h / math.sqrt(n)).astype(np.float32)


def _q_block(s: int) -> int:
    return Q_BLOCK if s % Q_BLOCK == 0 else s


def index_queries(model: dict, ap, c_q, pos, mode: str):
    """``q^I [queries, heads, dim]`` of the queries whose normed latent is
    ``c_q [queries, q_lora_rank]`` at positions ``pos``, rotated and
    multiplied by the Hadamard matrix."""
    ih, idim, rp = (model["index_n_heads"], model["index_head_dim"],
                    model["qk_rope_head_dim"])
    q = P.binary(jnp.dot, mode)(c_q, ap["w_qi"]).reshape(-1, ih, idim)
    q = jnp.concatenate([_rope(q[..., :rp], pos, model), q[..., rp:]],
                        axis=-1)
    return jnp.dot(q, jnp.asarray(hadamard(idim)))


def index_keys(model: dict, ap, h, mode: str):
    """``(k^I [s, dim], w [s, heads])`` of one sequence: the keys rotated
    and multiplied by the Hadamard matrix."""
    dot = P.binary(jnp.dot, mode)
    ih, idim, rp = (model["index_n_heads"], model["index_head_dim"],
                    model["qk_rope_head_dim"])
    pos = jnp.arange(h.shape[0])
    k = _layernorm(dot(h, ap["w_ki"]), ap["ki_norm"], ap["ki_bias"])
    k = jnp.concatenate([_rope(k[:, :rp], pos, model), k[:, rp:]], axis=-1)
    w = dot(h, ap["w_w"]) * ih ** -0.5 * idim ** -0.5
    return jnp.dot(k, jnp.asarray(hadamard(idim))), w


def index_scores(model: dict, q, k, w, mode: str):
    """``I [queries, s]`` of the queries ``q [queries, heads, dim]``, ``w
    [queries, heads]`` against the keys ``k [s, dim]``: ``sum_j w_j
    relu(q_j . k)``, the heads in groups (memory only)."""
    qk = P.binary(lambda a, b: jnp.einsum("qhd,kd->hqk", a, b), mode)
    ih = q.shape[1]
    g = min(INDEX_HEAD_GROUP, ih)
    total = jnp.zeros((q.shape[0], k.shape[0]), jnp.float32)
    for lo in range(0, ih, g):
        part = jax.nn.relu(qk(q[:, lo:lo + g], k))
        total = total + jnp.einsum("hqk,qh->qk", part, w[:, lo:lo + g])
    return total


def selection_mask(model: dict, ap, h, c_q, mode: str, select: str):
    """``[s, s]`` bool: row ``t`` holds ``S_t`` (a subset of ``s <= t``)."""
    s = h.shape[0]
    top = model["index_topk"]
    row = jnp.arange(s)[:, None]
    col = jnp.arange(s)[None, :]
    causal = col <= row
    if select == "all":
        return causal
    if select == "recent":
        return causal & (col > row - top)
    if select != "topk":
        raise ValueError(f"selection rule {select!r}")
    k, w = index_keys(model, ap, h, mode)
    qb = _q_block(s)
    kk = min(top, s)

    def block(lo):
        at = lo + jnp.arange(qb)
        q = index_queries(model, ap,
                          jax.lax.dynamic_slice_in_dim(c_q, lo, qb), at,
                          mode)
        scores = index_scores(model, q, k,
                              jax.lax.dynamic_slice_in_dim(w, lo, qb), mode)
        valid = jnp.arange(s)[None, :] <= at[:, None]
        _, idx = jax.lax.top_k(jnp.where(valid, scores, -jnp.inf), kk)
        chosen = jnp.zeros((qb, s), bool).at[
            jnp.arange(qb)[:, None], idx].set(True)
        return chosen & valid

    return jax.lax.map(block, jnp.arange(0, s, qb)).reshape(s, s)


def attention(model: dict, ap, x, mode: str, select: str = "topk"):
    """One sequence ``x [s, d]``; returns the block's output (before the
    residual)."""
    dot = P.binary(jnp.dot, mode)
    proj = P.binary(lambda a, w: jnp.einsum("sr,rgd->sgd", a, w), mode)
    qk = P.binary(lambda q, k: jnp.einsum("qgd,kgd->gqk", q, k), mode)
    pv = P.binary(lambda p, v: jnp.einsum("gqk,kgd->qgd", p, v), mode)
    out = P.binary(lambda o, w: jnp.einsum("sgv,gvd->sd", o, w), mode)
    s, d = x.shape
    h_n, nope, rp, vd = (model["num_attention_heads"],
                         model["qk_nope_head_dim"],
                         model["qk_rope_head_dim"], model["v_head_dim"])
    rq, rkv, eps = (model["q_lora_rank"], model["kv_lora_rank"],
                    model["rms_norm_eps"])
    pos = jnp.arange(s)
    h = _rms(x, ap["norm"], eps)
    c_q = _rms(dot(h, ap["w_dq"]), ap["q_norm"], eps)
    ckv = dot(h, ap["w_dkv"])
    c = _rms(ckv[:, :rkv], ap["kv_norm"], eps)
    k_rope = _rope(ckv[:, rkv:], pos, model)
    allowed = selection_mask(model, ap, h, c_q, mode, select)
    scale = softmax_scale(model)
    g = min(HEAD_GROUP, h_n)
    qb = _q_block(s)
    n_g = h_n // g

    def grouped(w, lead):
        """``w`` with its heads axis (``lead`` axes before it) split into
        groups, the groups in front."""
        w = w.reshape(*w.shape[:lead], n_g, g, *w.shape[lead + 1:])
        return jnp.moveaxis(w, lead, 0)

    def group(y, ws):                      # memory only: heads are
        w_uq, w_ukv, w_o = ws              # independent
        q = proj(c_q, w_uq)
        q = jnp.concatenate([q[..., :nope], _rope(q[..., nope:], pos,
                                                  model)], axis=-1)
        kv = proj(c, w_ukv)
        k = jnp.concatenate(
            [kv[..., :nope], jnp.broadcast_to(k_rope[:, None, :],
                                              (s, g, rp))], axis=-1)
        v = kv[..., nope:]

        def block(at):
            scores = qk(jax.lax.dynamic_slice_in_dim(q, at, qb), k) * scale
            ok = jax.lax.dynamic_slice_in_dim(allowed, at, qb)
            p = jax.nn.softmax(jnp.where(ok[None], scores, -jnp.inf),
                               axis=-1)
            return pv(p, v)

        o = jax.lax.map(block, jnp.arange(0, s, qb)).reshape(s, g, vd)
        return y + out(o, w_o), None

    y, _ = jax.lax.scan(
        group, jnp.zeros((s, d), jnp.float32),
        (grouped(ap["w_uq"].reshape(rq, h_n, nope + rp), 1),
         grouped(ap["w_ukv"].reshape(rkv, h_n, nope + vd), 1),
         grouped(ap["w_o"].reshape(h_n, vd, d), 0)))
    return y


def _swiglu(dot, h, w_gate, w_up, w_down):
    return dot(jax.nn.silu(dot(h, w_gate)) * dot(h, w_up), w_down)


def route(model: dict, h, router, bias, mode: str):
    """``(experts [s, k], weights [s, k])``: group-limited choice by the
    biased sigmoid scores, weights from the unbiased ones."""
    s = h.shape[0]
    g = jax.nn.sigmoid(P.binary(jnp.dot, mode)(h, router))
    n_group, per = model["n_group"], g.shape[1] // model["n_group"]
    biased = (g + bias).reshape(s, n_group, per)
    group_score = jnp.sum(jax.lax.top_k(biased, 2)[0], axis=-1)
    _, best = jax.lax.top_k(group_score, model["topk_group"])
    kept = jnp.zeros((s, n_group), bool).at[
        jnp.arange(s)[:, None], best].set(True)
    biased = jnp.where(kept[:, :, None], biased, -jnp.inf).reshape(s, -1)
    _, idx = jax.lax.top_k(biased, model["num_experts_per_tok"])
    gate = jnp.take_along_axis(g, idx, axis=-1)
    if model["norm_topk_prob"]:
        gate = gate / jnp.sum(gate, axis=-1, keepdims=True)
    return idx, gate * model["routed_scaling_factor"]


def expert_ffn(model: dict, lp, h, mode: str):
    """The expert layer's feed-forward on normed ``h [s, d]``: the shared
    expert, and of the routed ones those held here (``lp["w_gate"]``
    holds experts ``expert_offset ..``), each over every token and
    weighted by what the router gave it there (zero where unchosen)."""
    dot = P.binary(jnp.dot, mode)
    idx, w = route(model, h, lp["router"], lp["router_bias"], mode)
    sh = lp["shared"]
    y = _swiglu(dot, h, sh["w_gate"], sh["w_up"], sh["w_down"])
    for e in range(lp["w_gate"].shape[0]):
        y = y + _expert(model, h, idx, w, e, lp["w_gate"][e],
                        lp["w_up"][e], lp["w_down"][e], mode)
    return y


def _expert(model, h, idx, w, e, w_gate, w_up, w_down, mode):
    w_e = jnp.sum(jnp.where(idx == model["expert_offset"] + e, w, 0.0),
                  axis=-1)
    return w_e[:, None] * _swiglu(P.binary(jnp.dot, mode), h, w_gate, w_up,
                                  w_down)


def dense_layer(model: dict, lp, x, mode: str, select: str = "topk"):
    x = x + attention(model, lp["attn"], x, mode, select)
    h = _rms(x, lp["ffn_norm"], model["rms_norm_eps"])
    f = lp["ffn"]
    return x + _swiglu(P.binary(jnp.dot, mode), h, f["w_gate"], f["w_up"],
                       f["w_down"])


def moe_layer(model: dict, lp, x, mode: str, select: str = "topk"):
    x = x + attention(model, lp["attn"], x, mode, select)
    h = _rms(x, lp["ffn_norm"], model["rms_norm_eps"])
    return x + expert_ffn(model, lp, h, mode)


def head(model: dict, norm_f, unembed, x, mode: str):
    return P.binary(jnp.dot, mode)(
        _rms(x, norm_f, model["rms_norm_eps"]), unembed)


@functools.lru_cache(maxsize=None)
def _programs(model_json: str, mode: str, select: str):
    """The jitted pieces ``served_logits`` strings together."""
    model = json.loads(model_json)
    dot = P.binary(jnp.dot, mode)
    eps = model["rms_norm_eps"]
    return {
        "attn": jax.jit(lambda ap, x: x + attention(model, ap, x, mode,
                                                    select)),
        "norm": jax.jit(lambda w, x: _rms(x, w, eps)),
        "ffn": jax.jit(lambda h, wg, wu, wd: _swiglu(dot, h, wg, wu, wd)),
        "route": jax.jit(lambda h, r, b: route(model, h, r, b, mode)),
        "expert": jax.jit(
            lambda h, idx, w, e, wg, wu, wd: _expert(
                model, h, idx, w, e, wg, wu, wd, mode)),
        "head": jax.jit(lambda n, u, x: head(model, n, u, x, mode)),
    }


def _f32(tree):
    return jax.tree_util.tree_map(lambda a: a.astype(jnp.float32), tree)


def _layer(model, prog, lp, x, dense: bool):
    """One layer over one sequence, a matrix upcast only while used."""
    x = prog["attn"](_f32(lp["attn"]), x)
    h = prog["norm"](lp["ffn_norm"].astype(jnp.float32), x)
    if dense:
        f = lp["ffn"]
        for lo in range(0, f["w_gate"].shape[1], FFN_BLOCK):
            hi = lo + FFN_BLOCK
            x = x + prog["ffn"](h, *_f32((f["w_gate"][:, lo:hi],
                                          f["w_up"][:, lo:hi],
                                          f["w_down"][lo:hi])))
        return x
    idx, w = prog["route"](h, lp["router"].astype(jnp.float32),
                           lp["router_bias"])
    sh = lp["shared"]
    x = x + prog["ffn"](h, *_f32((sh["w_gate"], sh["w_up"], sh["w_down"])))
    for e in range(lp["w_gate"].shape[0]):
        x = x + prog["expert"](h, idx, w, e, *_f32(
            (lp["w_gate"][e], lp["w_up"][e], lp["w_down"][e])))
    return x


def served_logits(model: dict, params, sequences, mode: str = "f32",
                  select: str = "topk"):
    """Reference logits of whole served sequences: ``sequences`` is a
    list of token lists (prompt + served tokens); the result is a list of
    ``[len(sequence), vocab]`` float32 arrays.  ``select``: the model's
    rule, or one of the two controls (module docstring)."""
    width = max(len(s) for s in sequences)
    width += -width % Q_BLOCK     # one shape for a mix: one compile
    prog = _programs(json.dumps(model, sort_keys=True), mode, select)
    nd = model["first_k_dense_replace"]
    out = []
    with jax.default_matmul_precision("highest"):
        norm_f = params["norm_f"].astype(jnp.float32)
        for s in sequences:
            row = np.zeros((width,), np.int32)
            row[:len(s)] = s          # causal: right padding is inert
            x = params["embed"][jnp.asarray(row)].astype(jnp.float32)
            for i, lp in enumerate(params["layers"]):
                x = _layer(model, prog, lp, x, i < nd)
            out.append(np.asarray(prog["head"](
                norm_f, params["unembed"].astype(jnp.float32), x)
            )[:len(s)])
    return out
