"""Plain reference for the ``afmoe`` decoder of
``arcee-ai/Trinity-Large-Preview``: the full forward pass at EVERY position
in straightforward ``jax.numpy`` and float32, no cache, no kernel, no
batching, the experts one after another over every token.

It imports nothing of the program.  ``d`` hidden, ``H`` query and ``G``
key/value heads of ``hd``, every norm an RMSNorm (weight only, ``eps``).
With ``x`` the residual stream of one sequence:

* ``x_0 = sqrt(d) E[token]`` (``mup_enabled``).
* ``h = norm_in(x)``; ``[q | k | v | g] = h W_in`` (``H hd | G hd | G hd |
  H hd``); ``q``, ``k`` normed over each head's ``hd`` with one learned
  weight each; on a ``sliding_attention`` layer BOTH are then rotated
  (rotate-half over the whole head, ``rope_theta``, pairs ``(i, i + hd /
  2)``), on a ``full_attention`` layer neither; scores ``q . k / sqrt(hd)``,
  causal, and on a sliding layer key ``j`` is seen by query ``i`` only
  while ``i - j < sliding_window``; query head ``i`` on key/value head ``i
  // (H / G)``; ``a = softmax(scores) v * sigmoid(g)``; ``x +=
  norm_post_attn(a W_o)``.
* ``h = norm_pre_mlp(x)``; a dense layer (the first ``num_dense_layers``):
  ``m = (silu(h W_gate) * (h W_up)) W_down``; an expert layer: ``s =
  sigmoid(h W_r)`` over ALL the router's outputs, the
  ``num_experts_per_tok`` largest of ``s + b`` chosen, weights ``s_e / (sum
  of the chosen s + 1e-20) * route_scale``, ``m = shared(h) + sum w_e
  expert_e(h)`` over the experts HELD here (``expert_offset ..``: what the
  absent ones would add is left out, as in the program); ``x +=
  norm_post_mlp(m)``.
* ``logits = norm_f(x_L) W_head``.

``variant`` names another reading of something the published config does
not say (the configuration file's ``assumed``); the tests hold each to
FAIL the comparison: ``"rope_full"`` (rotary on full layers too),
``"gate_after_o"`` (the gate's first ``d`` outputs on ``a W_o``),
``"norm_after_rope"``, ``"no_post_norm"``, ``"bias_in_weight"`` (weights
from ``s + b``), ``"window_inclusive"`` (``i - j <= window``), ``"no_mup"``.
Three more switch a mechanism off, to show that a comparison sees it:
``"no_gate"``, ``"no_window"``, ``"no_routed"``.

The parameter tree has the program's shape, so one seeded tree feeds both
sides.  ``served_logits`` hands a layer its parameters as they are held
and upcasts inside it, an expert at a time, and attends ``Q_BLOCK``
queries of one key/value head's group at a time, so that it fits beside
the bfloat16 parameters the program holds.
"""

from __future__ import annotations

import functools
import json

import jax
import jax.numpy as jnp
import numpy as np

from benchmark import precision as P

HEAD_ROWS = 2048    # rows of logits computed at once (205 MB in float32)
Q_BLOCK = 1024      # queries of one block of the attention
SLIDING = "sliding_attention"


def sizes(model: dict) -> dict:
    hd = model["head_dim"]
    return {"d": model["hidden_size"], "hd": hd,
            "qw": model["num_attention_heads"] * hd,
            "kvw": model["num_key_value_heads"] * hd,
            "held": model["n_routed_experts"],
            "router": model["n_routed_experts_published"]}


# -- seeded weights (the benchmark's own, not the program's) -----------------

def _tree(model: dict, leaf):
    """The program's tree, every leaf made by ``leaf(shape, how)``: ``how``
    a float is a normal's scale (``("f32", scale)``: kept in float32), else
    a constant's value as a string or ``"head_norm"`` (``qk_norm_scale`` times
    uniform 0.5..1.5)."""
    s = sizes(model)
    d, f, fe = s["d"], model["intermediate_size"], model["moe_intermediate_size"]
    std = model["initializer_range"]
    post = str((2.0 * model["num_hidden_layers"]) ** -0.5)

    def ffn(width, *lead):
        return {"w_gate": leaf((*lead, d, width), std),
                "w_up": leaf((*lead, d, width), std),
                "w_down": leaf((*lead, width, d), std)}

    def layer(i):
        lp = {"norm_in": leaf((d,), "1.0"),
              "attn": {"w_in": leaf((d, 2 * s["qw"] + 2 * s["kvw"]), std),
                       "w_o": leaf((s["qw"], d), std),
                       "q_norm": leaf((s["hd"],), "head_norm"),
                       "k_norm": leaf((s["hd"],), "head_norm")},
              "norm_post_attn": leaf((d,), post),
              "norm_pre_mlp": leaf((d,), "1.0"),
              "norm_post_mlp": leaf((d,), post)}
        if i < model["num_dense_layers"]:
            lp["mlp"] = ffn(f)
        else:
            lp["moe"] = {"router": leaf((d, s["router"]), std),
                         "bias": leaf((s["router"],),
                                      ("f32", model["router_bias_std"])),
                         **ffn(fe, s["held"]), "shared": ffn(fe)}
        return lp

    return {"embed": leaf((model["vocab_size"], d), std),
            "layers": [layer(i) for i in range(model["num_hidden_layers"])],
            "norm_f": leaf((d,), "1.0"),
            "unembed": leaf((d, model["vocab_size"]), std)}


def init_params(model: dict, seed: int):
    """The configuration's ``assumed`` initialisation, drawn ON THE DEVICE
    leaf by leaf from the seed and rounded to the served type."""
    dt = jnp.dtype(model["dtype"])

    @jax.jit
    def make(key):
        count = iter(range(10 ** 6))

        def leaf(shape, how):
            if isinstance(how, str) and how != "head_norm":
                return jnp.full(shape, float(how), dt)
            k = jax.random.fold_in(key, next(count))
            if how == "head_norm":
                return (model["qk_norm_scale"] * (0.5 + jax.random.uniform(
                    k, shape, jnp.float32))).astype(dt)
            kept, scale = (jnp.float32, how[1]) if isinstance(how, tuple) \
                else (dt, how)
            return (jax.random.normal(k, shape, jnp.float32)
                    * scale).astype(kept)

        return _tree(model, leaf)

    return make(P.key_from_seed(seed))


# -- the model ---------------------------------------------------------------

def _f32(a):
    return a.astype(jnp.float32)


def _rms(x, scale, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True)
                             + eps) * _f32(scale)


def _rope(x, model):
    """``x [s, heads, hd]`` at positions ``0 .. s - 1``."""
    half = model["head_dim"] // 2
    inv = model["rope_theta"] ** (-np.arange(half, dtype=np.float64) / half)
    ang = jnp.arange(x.shape[0], dtype=jnp.float32)[:, None, None] \
        * jnp.asarray(inv, jnp.float32)
    cos, sin = jnp.cos(ang), jnp.sin(ang)
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin],
                           axis=-1)


def attention(model: dict, ap, u, kind: str, mode: str, variant: str = ""):
    """``u [s, d]`` (normed).  Returns the branch ``[s, d]`` before its
    post-norm."""
    dot = P.binary(jnp.dot, mode)
    qk = P.binary(lambda a, b: jnp.einsum("qrd,kd->rqk", a, b), mode)
    pv = P.binary(lambda a, b: jnp.einsum("rqk,kd->qrd", a, b), mode)
    z = sizes(model)
    s, hd, eps = u.shape[0], z["hd"], model["rms_norm_eps"]
    g_n = model["num_key_value_heads"]
    per = model["num_attention_heads"] // g_n
    both = dot(u, _f32(ap["w_in"]))
    q = both[:, :z["qw"]].reshape(s, g_n, per, hd)
    k = both[:, z["qw"]:z["qw"] + z["kvw"]].reshape(s, g_n, hd)
    v = both[:, z["qw"] + z["kvw"]:z["qw"] + 2 * z["kvw"]].reshape(s, g_n, hd)
    gate = both[:, z["qw"] + 2 * z["kvw"]:]
    rotate = kind == SLIDING or variant == "rope_full"
    if variant == "norm_after_rope" and rotate:
        q = _rms(_rope(q.reshape(s, -1, hd), model), ap["q_norm"],
                 eps).reshape(q.shape)
        k = _rms(_rope(k, model), ap["k_norm"], eps)
    else:
        q, k = _rms(q, ap["q_norm"], eps), _rms(k, ap["k_norm"], eps)
        if rotate:
            q = _rope(q.reshape(s, -1, hd), model).reshape(q.shape)
            k = _rope(k, model)
    window = model["sliding_window"] + (variant == "window_inclusive")
    if kind != SLIDING or variant == "no_window":
        window = 0
    k_pos = jnp.arange(s)[None, :]
    rows = []
    for lo in range(0, s, Q_BLOCK):
        q_pos = jnp.arange(lo, min(lo + Q_BLOCK, s))[:, None]
        seen = k_pos <= q_pos
        if window:
            seen = seen & (q_pos - k_pos < window)
        outs = []
        for g in range(g_n):
            scores = qk(q[lo:lo + Q_BLOCK, g], k[:, g]) * hd ** -0.5
            probs = jax.nn.softmax(jnp.where(seen, scores, -jnp.inf),
                                   axis=-1)
            outs.append(pv(probs, v[:, g]))               # [q, per, hd]
        rows.append(jnp.stack(outs, axis=1).reshape(-1, z["qw"]))
    a = jnp.concatenate(rows)
    if variant == "gate_after_o":
        return dot(a, _f32(ap["w_o"])) * jax.nn.sigmoid(gate[:, :z["d"]])
    if variant != "no_gate":
        a = a * jax.nn.sigmoid(gate)
    return dot(a, _f32(ap["w_o"]))


def _swiglu(dot, h, w_gate, w_up, w_down):
    return dot(jax.nn.silu(dot(h, _f32(w_gate))) * dot(h, _f32(w_up)),
               _f32(w_down))


def route(model: dict, h, router, bias, mode: str, variant: str = ""):
    """``(experts [s, k], weights [s, k])`` over ALL the router's outputs:
    the bias moves the choice and not the weight."""
    scores = jax.nn.sigmoid(P.binary(jnp.dot, mode)(h, _f32(router)))
    biased = scores + _f32(bias)
    _, idx = jax.lax.top_k(biased, model["num_experts_per_tok"])
    gate = jnp.take_along_axis(
        biased if variant == "bias_in_weight" else scores, idx, axis=-1)
    if model["route_norm"]:
        gate = gate / (jnp.sum(gate, axis=-1, keepdims=True) + 1e-20)
    return idx, gate * model["route_scale"]


def expert_ffn(model: dict, e, h, mode: str, variant: str = ""):
    """The expert layer's feed-forward on normed ``h [s, d]``: the shared
    expert, and of the routed ones those held here, each over every token
    and weighted by what the router gave it there (zero where unchosen),
    one after another."""
    dot = P.binary(jnp.dot, mode)
    idx, w = route(model, h, e["router"], e["bias"], mode, variant)
    sh = e["shared"]
    y = _swiglu(dot, h, sh["w_gate"], sh["w_up"], sh["w_down"])
    if variant == "no_routed":
        return y

    def one(y, x):
        n, w_gate, w_up, w_down = x
        w_e = jnp.sum(jnp.where(idx == model["expert_offset"] + n, w, 0.0),
                      axis=-1)
        return y + w_e[:, None] * _swiglu(dot, h, w_gate, w_up, w_down), None

    held = e["w_gate"].shape[0]
    return jax.lax.scan(one, y, (jnp.arange(held), e["w_gate"], e["w_up"],
                                 e["w_down"]))[0]


def layer(model: dict, kind: str, lp, x, mode: str, variant: str = ""):
    """One layer over one sequence ``x [s, d]``; ``lp`` in any dtype."""
    eps = model["rms_norm_eps"]

    def post(branch, scale):
        return branch if variant == "no_post_norm" else _rms(branch, scale,
                                                             eps)

    u = _rms(x, lp["norm_in"], eps)
    x = x + post(attention(model, lp["attn"], u, kind, mode, variant),
                 lp["norm_post_attn"])
    h = _rms(x, lp["norm_pre_mlp"], eps)
    if "mlp" in lp:
        f = lp["mlp"]
        m = _swiglu(P.binary(jnp.dot, mode), h, f["w_gate"], f["w_up"],
                    f["w_down"])
    else:
        m = expert_ffn(model, lp["moe"], h, mode, variant)
    return x + post(m, lp["norm_post_mlp"])


def embed(model: dict, table, tokens, variant: str = ""):
    mult = (model["hidden_size"] ** 0.5
            if model["mup_enabled"] and variant != "no_mup" else 1.0)
    return _f32(table[jnp.asarray(tokens)]) * mult


def head(model: dict, norm_f, unembed, x, mode: str):
    return P.binary(jnp.dot, mode)(
        _rms(x, norm_f, model["rms_norm_eps"]), _f32(unembed))


def forward(model: dict, params, tokens, mode: str = "f32",
            variant: str = ""):
    """One sequence ``tokens [s]``, all at once (the CPU tests' sizes):
    ``logits [s, vocab]``."""
    with jax.default_matmul_precision("highest"):
        x = embed(model, params["embed"], tokens, variant)
        for kind, lp in zip(model["layer_types"], params["layers"]):
            x = layer(model, kind, lp, x, mode, variant)
        return head(model, params["norm_f"], params["unembed"], x, mode)


@functools.lru_cache(maxsize=None)
def _programs(model_json: str, mode: str):
    model = json.loads(model_json)
    layers = {kind: jax.jit(functools.partial(layer, model, kind, mode=mode))
              for kind in set(model["layer_types"])}
    return layers, jax.jit(lambda n, u, x: head(model, n, u, x, mode))


def served_logits(model: dict, params, sequences, mode: str = "f32"):
    """Reference logits of whole served sequences: ``sequences`` is a
    list of token lists (prompt + served tokens); the result is a list of
    ``[len(sequence), vocab]`` float32 arrays on the HOST (a row is 100 KB
    at 25024 entries).  Layers outermost; a layer takes its parameters as
    they are held and upcasts what it is using; the head goes
    ``HEAD_ROWS`` rows at a time."""
    width = max(len(s) for s in sequences)
    width += -width % 128         # one shape for a mix: one compile
    layers, out = _programs(json.dumps(model, sort_keys=True), mode)
    with jax.default_matmul_precision("highest"):
        xs = []
        for s in sequences:
            row = np.zeros((width,), np.int32)
            row[:len(s)] = s          # causal: right padding is inert
            xs.append(embed(model, params["embed"], row))
        for kind, lp in zip(model["layer_types"], params["layers"]):
            for i, x in enumerate(xs):
                xs[i] = layers[kind](lp, x)
        logits = []
        for x, s in zip(xs, sequences):
            rows = [np.asarray(out(params["norm_f"], params["unembed"],
                                   x[lo:lo + HEAD_ROWS]))
                    for lo in range(0, width, HEAD_ROWS) if lo < len(s)]
            logits.append(np.concatenate(rows)[:len(s)])
        return logits
