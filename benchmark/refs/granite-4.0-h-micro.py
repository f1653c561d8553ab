"""Plain reference for the Mamba-2 hybrid decoder of
``ibm-granite/granite-4.0-h-micro`` (``model_type`` ``granitemoehybrid``,
no experts): the full forward pass at EVERY position in straightforward
``jax.numpy`` and float32, the recurrence as a plain SEQUENTIAL scan over
the tokens (never the chunked form), no cache, no kernel, no batching.

It imports nothing of the program.  ``d`` hidden, ``H`` state-space heads
of ``P`` channels (``d_inner = H P = expand d``), ``N = d_state``, ``K =
d_conv``; ``Hq`` query and ``G`` key/value heads of width ``hd = d / Hq``;
RMSNorm (``eps``) before each mixer, each MLP and the head; NO positional
encoding; the head is the embedding, tied.  With ``u = RMSNorm(x)`` and
``r = residual_multiplier``, for the layer kind ``layer_types`` names:

* ``mamba``: ``[z | xBC | dt'] = u W_in`` (``d_inner | d_inner + 2 N |
  H``); ``xBC_t <- silu(b_c + sum_k w_c[k] * xBC_{t-K+1+k})`` (depthwise,
  causal, zeros before the start, over ALL ``d_inner + 2 N`` channels);
  ``[x | B | C] = xBC`` (one group: ``B_t``, ``C_t`` ``[N]`` shared by all
  heads); ``dt^h = softplus(dt'^h + dt_bias^h)``, ``A^h = -exp(A_log^h)``
  (scalars a head, no clamp); ``S_t^h = exp(dt_t^h A^h) S_{t-1}^h + dt_t^h
  x_t^h B_t^T`` (``[P, N]``, ``S_{-1} = 0``); ``y_t^h = S_t^h C_t + D^h
  x_t^h``; ``g = y * silu(z)``; ``o = w_norm * g / sqrt(mean(g^2 over all
  d_inner) + eps)`` (the gate FIRST, then ONE norm group); out ``o W_out``.
* ``attention``: ``[q | k | v] = u W_qkv`` (no bias, no rotary); ``a =
  softmax(q k^T * attention_multiplier, causal) v`` with query head ``i``
  on key/value head ``i // (Hq / G)``; out ``a W_o``.
* every layer: ``x += r Mixer(u)``; ``x += r W2 (silu(g) * up)``, ``[g |
  up] = W1 RMSNorm'(x)``.
* the model: ``x_0 = embedding_multiplier E[token]``; ``logits =
  RMSNorm_f(x_L) E^T / logits_scaling``.

``variant`` (the tests' wrong readings, each of which must FAIL the
comparison): ``"residual_1"`` (``r = 1``), ``"scale_sqrt"`` (``hd **
-0.5`` for ``attention_multiplier``), ``"gate_after_norm"`` (the norm
first, the gate after it).

The parameter tree has the program's shape (``layers`` a list, one dict a
layer), so one seeded tree feeds both sides.  ``served_logits`` upcasts
ONE layer at a time and takes the sequences one by one, so it fits beside
the bfloat16 parameters the program holds.
"""

from __future__ import annotations

import functools
import json
import math

import jax
import jax.numpy as jnp
import numpy as np

from benchmark import precision as P

HEAD_ROWS = 1024    # rows of logits computed at once (411 MB in float32)


def sizes(model: dict) -> dict:
    d = model["hidden_size"]
    h, p = model["mamba_n_heads"], model["mamba_d_head"]
    if h * p != model["mamba_expand"] * d or model["mamba_n_groups"] != 1:
        raise ValueError("mamba_n_heads x mamba_d_head must be "
                         "mamba_expand x hidden_size, in one group")
    hd = d // model["num_attention_heads"]
    return {"d": d, "d_inner": h * p, "H": h, "P": p,
            "N": model["mamba_d_state"], "K": model["mamba_d_conv"],
            "conv": h * p + 2 * model["mamba_d_state"], "hd": hd,
            "qw": d, "kvw": model["num_key_value_heads"] * hd}


# -- seeded weights (the benchmark's own, not the program's) -----------------

def _tree(model: dict, leaf):
    """The program's tree, every leaf made by ``leaf(shape, how)``: ``how``
    a float is a normal's scale, else ``"ones"``, ``"a_log"`` or
    ``"dt_bias"``."""
    s = sizes(model)
    d, f, di, h = s["d"], model["intermediate_size"], s["d_inner"], s["H"]
    # The two multipliers stand where an initialisation would scale: the
    # embedding enters as 12 E, the branches at 0.22 (the depth scaling).
    # So the embedding is drawn 12 times smaller and the residual
    # projections are NOT divided by sqrt(2 L) again (the configuration
    # file's ``assumed.weights`` has what the other choice did).
    std = model["initializer_range"]
    small = std / model["embedding_multiplier"]

    def norm():
        return {"scale": leaf((d,), "ones")}

    def mixer(kind):
        if kind == "attention":
            return {"w_qkv": leaf((d, s["qw"] + 2 * s["kvw"]), std),
                    "w_o": leaf((s["qw"], d), std)}
        return {"w_in": leaf((d, di + s["conv"] + h), std),
                "conv_w": leaf((s["K"], s["conv"]), s["K"] ** -0.5),
                "conv_b": leaf((s["conv"],), std),
                "dt_bias": leaf((h,), "dt_bias"),
                "A_log": leaf((h,), "a_log"), "D": leaf((h,), "ones"),
                "norm": leaf((di,), "ones"), "w_out": leaf((di, d), std)}

    def layer(kind):
        return {"norm1": norm(), "mixer": mixer(kind), "norm2": norm(),
                "mlp": {"w1": leaf((d, 2 * f), std),
                        "w2": leaf((f, d), std)}}

    return {"embed": leaf((model["vocab_size"], d), small),
            "layers": [layer(kind) for kind in model["layer_types"]],
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
            k = jax.random.fold_in(key, next(count))
            u = jax.random.uniform(k, shape, jnp.float32)
            if how == "a_log":
                return jnp.log(1.0 + 15.0 * u).astype(dt)
            if how == "dt_bias":
                lo, hi = math.log(1e-3), math.log(1e-1)
                step = jnp.exp(u * (hi - lo) + lo)
                return (step + jnp.log(-jnp.expm1(-step))).astype(dt)
            return (jax.random.normal(k, shape, jnp.float32)
                    * how).astype(dt)

        return _tree(model, leaf)

    return make(P.key_from_seed(seed))


# -- the model ---------------------------------------------------------------

def _rms(x, scale, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True)
                             + eps) * scale


def state_space(model: dict, mp, u, mode: str, variant: str = ""):
    """``u [s, d]`` (normed).  Returns ``out [s, d]``."""
    dot = P.binary(jnp.dot, mode)
    z = sizes(model)
    s, di, h, p, n, k = (u.shape[0], z["d_inner"], z["H"], z["P"], z["N"],
                         z["K"])
    zxd = dot(u, mp["w_in"])
    gate, xbc, dt = (zxd[:, :di], zxd[:, di:di + z["conv"]],
                     zxd[:, di + z["conv"]:])
    xp = jnp.concatenate([jnp.zeros((k - 1, z["conv"])), xbc])
    xbc = jax.nn.silu(mp["conv_b"] + sum(mp["conv_w"][i] * xp[i:i + s]
                                         for i in range(k)))
    x = xbc[:, :di].reshape(s, h, p)
    b_m, c_m = xbc[:, di:di + n], xbc[:, di + n:]
    dt = jax.nn.softplus(dt + mp["dt_bias"])             # [s, H]
    a = -jnp.exp(mp["A_log"])                            # [H]

    def step(state, inp):                                # state [H, P, N]
        x_t, d_t, b_t, c_t = inp
        state = (jnp.exp(d_t * a)[:, None, None] * state
                 + (d_t[:, None] * x_t)[:, :, None] * b_t[None, None, :])
        return state, jnp.sum(state * c_t[None, None, :], axis=-1)

    _, y = jax.lax.scan(step, jnp.zeros((h, p, n)), (x, dt, b_m, c_m))
    y = (y + mp["D"][:, None] * x).reshape(s, di)
    eps = model["rms_norm_eps"]
    if variant == "gate_after_norm":
        o = _rms(y, mp["norm"], eps) * jax.nn.silu(gate)
    else:
        o = _rms(y * jax.nn.silu(gate), mp["norm"], eps)
    return dot(o, mp["w_out"])


def attention(model: dict, ap, u, mode: str, variant: str = ""):
    """``u [s, d]``.  Plain causal grouped-query attention, one key/value
    head's group of query heads at a time."""
    dot = P.binary(jnp.dot, mode)
    qk = P.binary(lambda a, b: jnp.einsum("rqd,kd->rqk", a, b), mode)
    pv = P.binary(lambda a, b: jnp.einsum("rqk,kd->qrd", a, b), mode)
    z = sizes(model)
    s, hd = u.shape[0], z["hd"]
    g_n = model["num_key_value_heads"]
    per = model["num_attention_heads"] // g_n
    scale = (hd ** -0.5 if variant == "scale_sqrt"
             else model["attention_multiplier"])
    qkv = dot(u, ap["w_qkv"])
    q = qkv[:, :z["qw"]].reshape(s, g_n, per, hd)
    k = qkv[:, z["qw"]:z["qw"] + z["kvw"]].reshape(s, g_n, hd)
    v = qkv[:, z["qw"] + z["kvw"]:].reshape(s, g_n, hd)
    causal = jnp.arange(s)[None, :] <= jnp.arange(s)[:, None]
    outs = []
    for g in range(g_n):
        scores = qk(q[:, g].transpose(1, 0, 2), k[:, g]) * scale
        probs = jax.nn.softmax(jnp.where(causal, scores, -jnp.inf), axis=-1)
        outs.append(pv(probs, v[:, g]))                   # [s, per, hd]
    return dot(jnp.stack(outs, axis=1).reshape(s, -1), ap["w_o"])


def layer(model: dict, kind: str, lp, x, mode: str, variant: str = ""):
    """One layer over one sequence ``x [s, d]``."""
    dot = P.binary(jnp.dot, mode)
    eps = model["rms_norm_eps"]
    r = 1.0 if variant == "residual_1" else model["residual_multiplier"]
    u = _rms(x, lp["norm1"]["scale"], eps)
    mix = (state_space if kind == "mamba" else attention)(
        model, lp["mixer"], u, mode, variant)
    x = x + r * mix
    g, up = jnp.split(dot(_rms(x, lp["norm2"]["scale"], eps),
                          lp["mlp"]["w1"]), 2, axis=-1)
    return x + r * dot(jax.nn.silu(g) * up, lp["mlp"]["w2"])


def head(model: dict, norm_f, embed, x, mode: str):
    return P.binary(lambda a, b: jnp.einsum("sd,vd->sv", a, b), mode)(
        _rms(x, norm_f["scale"], model["rms_norm_eps"]),
        embed) / model["logits_scaling"]


def forward(model: dict, params, tokens, mode: str = "f32",
            variant: str = "", layer_types=None):
    """One sequence ``tokens [s]`` through float32 copies of ``params``,
    all at once (the CPU tests' sizes): ``logits [s, vocab]``.
    ``layer_types``: another layout over the same tree (a test's)."""
    f32 = jax.tree_util.tree_map(lambda a: a.astype(jnp.float32), params)
    with jax.default_matmul_precision("highest"):
        x = f32["embed"][jnp.asarray(tokens)] * model["embedding_multiplier"]
        for kind, lp in zip(layer_types or model["layer_types"],
                            f32["layers"]):
            x = layer(model, kind, lp, x, mode, variant)
        return head(model, f32["norm_f"], f32["embed"], x, mode)


@functools.lru_cache(maxsize=None)
def _programs(model_json: str, mode: str):
    model = json.loads(model_json)
    layers = {kind: jax.jit(functools.partial(layer, model, kind, mode=mode))
              for kind in set(model["layer_types"])}
    return layers, jax.jit(lambda n, e, x: head(model, n, e, x, mode))


def served_logits(model: dict, params, sequences, mode: str = "f32"):
    """Reference logits of whole served sequences: ``sequences`` is a
    list of token lists (prompt + served tokens); the result is a list of
    ``[len(sequence), vocab]`` float32 arrays on the HOST (a row is 401 KB
    at 100352 entries).  Layers outermost: one layer's weights are upcast,
    every sequence goes through it, the upcast copy is dropped; the head
    goes ``HEAD_ROWS`` rows at a time."""
    width = max(len(s) for s in sequences)
    width += -width % 128         # one shape for a mix: one compile
    layers, out = _programs(json.dumps(model, sort_keys=True), mode)

    def f32(tree):
        return jax.tree_util.tree_map(lambda a: a.astype(jnp.float32), tree)

    with jax.default_matmul_precision("highest"):
        embed = params["embed"].astype(jnp.float32)
        xs = []
        for s in sequences:
            row = np.zeros((width,), np.int32)
            row[:len(s)] = s          # causal: right padding is inert
            xs.append(embed[jnp.asarray(row)]
                      * model["embedding_multiplier"])
        for kind, lp in zip(model["layer_types"], params["layers"]):
            lp = f32(lp)
            for i, x in enumerate(xs):
                xs[i] = layers[kind](lp, x)
            del lp
        norm_f = f32(params["norm_f"])
        logits = []
        for x, s in zip(xs, sequences):
            rows = [np.asarray(out(norm_f, embed, x[lo:lo + HEAD_ROWS]))
                    for lo in range(0, width, HEAD_ROWS) if lo < len(s)]
            logits.append(np.concatenate(rows)[:len(s)])
        return logits
