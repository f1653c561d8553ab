"""Plain reference for the decoder-hybrid-decoder of
``microsoft/Phi-4-mini-flash-reasoning`` (Ren et al., arXiv:2507.06607,
and the model repository's ``modeling_phi4flash.py``): the full forward
pass at EVERY position in straightforward ``jax.numpy`` and float32, the
recurrence as a plain sequential scan, every layer over every token (no
last-token shortcut), no cache, no kernel, no batching.

It imports nothing of the program.  ``d`` hidden, ``H`` query heads, ``G``
key/value heads of width ``hd = d / H``, ``d_inner = expand * d``, ``N =
d_state``, ``K = d_conv``, ``R = dt_rank``, ``W = sliding_window``; float
LayerNorm (scale and bias) before each mixer, each MLP and the head; NO
positional encoding; the head is the embedding, tied.  For layer ``l`` of
``L`` (``h = L / 2``):

* every layer: ``x += Mixer_l(LN(x))``; ``x += W2 (silu(g) * u)``, ``[g |
  u] = W1 LN'(x)``.
* ``l`` even, ``l <= h``, STATE-SPACE (Mamba-1): ``[x | z] = u W_in``;
  ``x_t <- silu(b_c + sum_k w_c[k] * x_{t-K+1+k})`` (depthwise, causal,
  zeros before the start); ``[dt' | B_t | C_t] = x_t W_x``; ``dt_t =
  softplus(dt'_t W_dt + b_dt)``; ``A = -exp(A_log)``; ``S_t = exp(dt_t A)
  * S_{t-1} + (dt_t x_t) B_t^T`` (``S_{-1} = 0``); ``y_t = S_t C_t + D *
  x_t``; out ``(y_t * silu(z_t)) W_out``.  Layer ``h`` also hands on ``m_t
  = y_t`` (before the gate).
* ``l`` odd, ``l < h``, WINDOW attention; ``l = h + 1``, FULL attention:
  ``[q | k | v] = u W_qkv + b``; differential attention (below); out ``a
  W_o + b_o``.  Masks: full ``j <= t``; window ``t - W < j <= t``.
* ``l`` even, ``l > h``, GATED MEMORY UNIT: ``(m_t * silu(u_t W_in))
  W_out``.
* ``l`` odd, ``l > h + 1``, CROSS attention: ``q = u W_q + b`` of its own
  onto layer ``h + 1``'s ``k`` and ``v``, mask ``j <= t``.
* differential attention: query heads ``(2i, 2i+1)`` are ``(q1, q2)_i``,
  key heads ``(2j, 2j+1)`` are ``(k1, k2)_j``, ``V_j = [v_2j | v_2j+1]``;
  pair ``i`` uses ``j = i // (H / G)``; ``a_i = P(q1 k1^T / sqrt(hd)) V -
  lambda P(q2 k2^T / sqrt(hd)) V``, then an RMS norm over the ``2 hd``
  values with a learned scale, then ``* (1 - lambda_init)``; ``lambda_init
  = 0.8 - 0.6 exp(-0.3 l)``, ``lambda = exp(lq1 . lk1) - exp(lq2 . lk2) +
  lambda_init``.

The parameter tree has the program's shape (``layers`` a list, one dict a
layer; ``A_log`` and the convolution's weights are stored ``[N, d_inner]``
and ``[K, d_inner]``), so one seeded tree feeds both sides.
``served_logits`` upcasts ONE layer at a time and takes the sequences one
by one, so it fits beside the bfloat16 parameters the program holds.
"""

from __future__ import annotations

import functools
import json
import math

import jax
import jax.numpy as jnp
import numpy as np

from benchmark import precision as P

HEAD_ROWS = 512     # rows of logits computed at once (410 MB in float32)


def layer_kinds(n_layers: int) -> list:
    h = n_layers // 2
    kinds = []
    for l in range(n_layers):
        if l % 2 == 0:
            kinds.append("ssm" if l <= h else "gmu")
        elif l < h:
            kinds.append("window")
        else:
            kinds.append("full" if l == h + 1 else "cross")
    return kinds


def lambda_init(layer: int) -> float:
    return 0.8 - 0.6 * math.exp(-0.3 * layer)


# -- seeded weights (the benchmark's own, not the program's) -----------------

def _tree(model: dict, leaf):
    """The program's tree, every leaf made by ``leaf(shape, how)``: ``how``
    a float is a normal's scale, else ``"ones"``, ``"zeros"``,
    ``"a_log"`` or ``"dt_bias"``."""
    d, f = model["hidden_size"], model["intermediate_size"]
    di = model["expand"] * d
    n, k, r = model["d_state"], model["d_conv"], model["dt_rank"]
    hd = d // model["num_attention_heads"]
    qw, kvw = d, model["num_key_value_heads"] * hd
    std = model["initializer_range"]
    res = std / (2 * model["num_hidden_layers"]) ** 0.5

    def norm(width=d):
        return {"scale": leaf((width,), "ones"),
                "bias": leaf((width,), "zeros")}

    def lambdas():
        return {name: leaf((hd,), model["lambda_std"]) for name in
                ("lambda_q1", "lambda_k1", "lambda_q2", "lambda_k2")}

    def mixer(kind):
        if kind == "ssm":
            return {"w_in": leaf((d, 2 * di), std),
                    "conv_w": leaf((k, di), k ** -0.5),
                    "conv_b": leaf((di,), std),
                    "w_x": leaf((di, r + 2 * n), std),
                    "w_dt": leaf((r, di), std),
                    "b_dt": leaf((di,), "dt_bias"),
                    "A_log": leaf((n, di), "a_log"),
                    "D": leaf((di,), "ones"),
                    "w_out": leaf((di, d), res)}
        if kind == "gmu":
            return {"w_in": leaf((d, di), std), "w_out": leaf((di, d), res)}
        if kind == "cross":
            return {"w_q": leaf((d, qw), std), "b_q": leaf((qw,), std),
                    "w_o": leaf((qw, d), res), "b_o": leaf((d,), std),
                    **lambdas(), "subln": leaf((2 * hd,), "ones")}
        return {"w_qkv": leaf((d, qw + 2 * kvw), std),
                "b_qkv": leaf((qw + 2 * kvw,), std),
                "w_o": leaf((qw, d), res), "b_o": leaf((d,), std),
                **lambdas(), "subln": leaf((2 * hd,), "ones")}

    def layer(kind):
        return {"norm1": norm(), "mixer": mixer(kind), "norm2": norm(),
                "mlp": {"w1": leaf((d, 2 * f), std),
                        "w2": leaf((f, d), res)}}

    return {"embed": leaf((model["vocab_size"], d), std),
            "layers": [layer(kind)
                       for kind in layer_kinds(model["num_hidden_layers"])],
            "norm_f": norm()}


def init_params(model: dict, seed: int):
    """The configuration's ``assumed`` initialisation, drawn ON THE DEVICE
    leaf by leaf from the seed and rounded to the served type."""
    dt = jnp.dtype(model["dtype"])

    @jax.jit
    def make(key):
        count = iter(range(10 ** 6))

        def leaf(shape, how):
            if how == "ones":
                return jnp.ones(shape, dt)
            if how == "zeros":
                return jnp.zeros(shape, dt)
            if how == "a_log":
                return jnp.broadcast_to(jnp.log(jnp.arange(
                    1, shape[0] + 1, dtype=jnp.float32))[:, None],
                    shape).astype(dt)
            k = jax.random.fold_in(key, next(count))
            if how == "dt_bias":
                lo, hi = math.log(1e-3), math.log(1e-1)
                step = jnp.exp(jax.random.uniform(k, shape, jnp.float32)
                               * (hi - lo) + lo)
                return (step + jnp.log(-jnp.expm1(-step))).astype(dt)
            return (jax.random.normal(k, shape, jnp.float32)
                    * how).astype(dt)

        return _tree(model, leaf)

    return make(P.key_from_seed(seed))


# -- the model ---------------------------------------------------------------

def _ln(x, p, eps):
    mu = jnp.mean(x, axis=-1, keepdims=True)
    var = jnp.mean(jnp.square(x - mu), axis=-1, keepdims=True)
    return (x - mu) * jax.lax.rsqrt(var + eps) * p["scale"] + p["bias"]


def diff_attention(model: dict, ap, q, k, v, lam_init, mask, mode: str):
    """``q [s, H, hd]``, ``k``/``v`` ``[s, G, hd]``, ``mask [s, s]`` (row
    ``t`` may see column ``j``).  Returns ``[s, H * hd]``."""
    qk = P.binary(lambda a, b: jnp.einsum("qd,kd->qk", a, b), mode)
    pv = P.binary(jnp.dot, mode)
    h_n, g_n = model["num_attention_heads"], model["num_key_value_heads"]
    hd = q.shape[-1]
    lam = (jnp.exp(jnp.sum(ap["lambda_q1"] * ap["lambda_k1"]))
           - jnp.exp(jnp.sum(ap["lambda_q2"] * ap["lambda_k2"])) + lam_init)

    def soft(scores):
        return jax.nn.softmax(jnp.where(mask, scores / math.sqrt(hd),
                                        -jnp.inf), axis=-1)

    outs = []
    for i in range(h_n // 2):
        j = i // (h_n // g_n)
        values = jnp.concatenate([v[:, 2 * j], v[:, 2 * j + 1]], axis=-1)
        a = (pv(soft(qk(q[:, 2 * i], k[:, 2 * j])), values)
             - lam * pv(soft(qk(q[:, 2 * i + 1], k[:, 2 * j + 1])), values))
        a = a * jax.lax.rsqrt(jnp.mean(a * a, axis=-1, keepdims=True)
                              + model["layer_norm_eps"])
        outs.append(a * ap["subln"] * (1.0 - lam_init))
    return jnp.concatenate(outs, axis=-1)


def _masks(s: int, window: int):
    t, j = jnp.arange(s)[:, None], jnp.arange(s)[None, :]
    return j <= t, (j <= t) & (j > t - window)


def state_space(model: dict, mp, u, mode: str):
    """``u [s, d]`` (normed).  Returns ``(out [s, d], y [s, d_inner])``."""
    dot = P.binary(jnp.dot, mode)
    s = u.shape[0]
    di = mp["w_out"].shape[0]
    n, k, r = model["d_state"], model["d_conv"], model["dt_rank"]
    xz = dot(u, mp["w_in"])
    x, z = xz[:, :di], xz[:, di:]
    xp = jnp.concatenate([jnp.zeros((k - 1, di)), x])
    x = jax.nn.silu(mp["conv_b"] + sum(mp["conv_w"][i] * xp[i:i + s]
                                       for i in range(k)))
    dbc = dot(x, mp["w_x"])
    delta = jax.nn.softplus(dot(dbc[:, :r], mp["w_dt"]) + mp["b_dt"])
    b_m, c_m = dbc[:, r:r + n], dbc[:, r + n:]
    a = -jnp.exp(mp["A_log"])                        # [n, d_inner]

    def step(state, inp):
        x_t, d_t, b_t, c_t = inp
        state = (jnp.exp(d_t[None, :] * a) * state
                 + (d_t * x_t)[None, :] * b_t[:, None])
        return state, jnp.sum(state * c_t[:, None], axis=0)

    _, y = jax.lax.scan(step, jnp.zeros((n, di)), (x, delta, b_m, c_m))
    y = y + mp["D"] * x
    return dot(y * jax.nn.silu(z), mp["w_out"]), y


def layer(model: dict, kind: str, lp, x, carry, lam_init, mode: str):
    """One layer over one sequence ``x [s, d]``.  ``carry`` holds what
    later layers read: ``m`` (the memory) and ``k``, ``v`` of the full
    layer.  Returns ``(x, carry)``."""
    dot = P.binary(jnp.dot, mode)
    eps = model["layer_norm_eps"]
    h_n, g_n = model["num_attention_heads"], model["num_key_value_heads"]
    s, d = x.shape
    hd = d // h_n
    mp = lp["mixer"]
    u = _ln(x, lp["norm1"], eps)
    causal, windowed = _masks(s, model["sliding_window"])
    if kind == "ssm":
        mix, y = state_space(model, mp, u, mode)
        carry = dict(carry, m=y)
    elif kind == "gmu":
        mix = dot(carry["m"] * jax.nn.silu(dot(u, mp["w_in"])), mp["w_out"])
    else:
        if kind == "cross":
            q = dot(u, mp["w_q"]) + mp["b_q"]
            k, v = carry["k"], carry["v"]
        else:
            qkv = dot(u, mp["w_qkv"]) + mp["b_qkv"]
            q, k, v = (qkv[:, :d], qkv[:, d:d + g_n * hd],
                       qkv[:, d + g_n * hd:])
            k, v = k.reshape(s, g_n, hd), v.reshape(s, g_n, hd)
            if kind == "full":
                carry = dict(carry, k=k, v=v)
        a = diff_attention(model, mp, q.reshape(s, h_n, hd), k, v, lam_init,
                           windowed if kind == "window" else causal, mode)
        mix = dot(a, mp["w_o"]) + mp["b_o"]
    x = x + mix
    g, up = jnp.split(dot(_ln(x, lp["norm2"], eps), lp["mlp"]["w1"]), 2,
                      axis=-1)
    return x + dot(jax.nn.silu(g) * up, lp["mlp"]["w2"]), carry


def head(model: dict, norm_f, embed, x, mode: str):
    return P.binary(lambda a, b: jnp.einsum("sd,vd->sv", a, b), mode)(
        _ln(x, norm_f, model["layer_norm_eps"]), embed)


def forward(model: dict, params, tokens, mode: str = "f32"):
    """One sequence ``tokens [s]`` through float32 copies of ``params``,
    all at once (the CPU tests' sizes): ``logits [s, vocab]``."""
    f32 = jax.tree_util.tree_map(lambda a: a.astype(jnp.float32), params)
    with jax.default_matmul_precision("highest"):
        x = f32["embed"][jnp.asarray(tokens)]
        carry = {}
        for l, (kind, lp) in enumerate(zip(
                layer_kinds(model["num_hidden_layers"]), f32["layers"])):
            x, carry = layer(model, kind, lp, x, carry, lambda_init(l), mode)
        return head(model, f32["norm_f"], f32["embed"], x, mode)


@functools.lru_cache(maxsize=None)
def _programs(model_json: str, mode: str):
    model = json.loads(model_json)
    layers = {kind: jax.jit(functools.partial(layer, model, kind, mode=mode))
              for kind in set(layer_kinds(model["num_hidden_layers"]))}
    return layers, jax.jit(lambda n, e, x: head(model, n, e, x, mode))


def served_logits(model: dict, params, sequences, mode: str = "f32"):
    """Reference logits of whole served sequences: ``sequences`` is a
    list of token lists (prompt + served tokens); the result is a list of
    ``[len(sequence), vocab]`` float32 arrays on the HOST (a row is 800 KB
    at 200064 entries).  Layers outermost: one layer's weights are upcast,
    every sequence goes through it, the upcast copy is dropped; the head
    goes ``HEAD_ROWS`` rows at a time."""
    width = max(len(s) for s in sequences)
    width += -width % 128         # one shape for a mix: one compile
    layers, out = _programs(json.dumps(model, sort_keys=True), mode)
    kinds = layer_kinds(model["num_hidden_layers"])

    def f32(tree):
        return jax.tree_util.tree_map(lambda a: a.astype(jnp.float32), tree)

    with jax.default_matmul_precision("highest"):
        embed = params["embed"].astype(jnp.float32)
        xs, carries = [], [{} for _ in sequences]
        for s in sequences:
            row = np.zeros((width,), np.int32)
            row[:len(s)] = s          # causal: right padding is inert
            xs.append(embed[jnp.asarray(row)])
        for l, (kind, lp) in enumerate(zip(kinds, params["layers"])):
            lp = f32(lp)
            for i, x in enumerate(xs):
                xs[i], carries[i] = layers[kind](
                    lp, x, carries[i], jnp.float32(lambda_init(l)))
            del lp
        del carries
        norm_f = f32(params["norm_f"])
        logits = []
        for x, s in zip(xs, sequences):
            rows = [np.asarray(out(norm_f, embed, x[lo:lo + HEAD_ROWS]))
                    for lo in range(0, width, HEAD_ROWS) if lo < len(s)]
            logits.append(np.concatenate(rows)[:len(s)])
        return logits
