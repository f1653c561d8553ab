"""Plain reference for the hybrid decoder of ``allenai/Olmo-Hybrid-7B``
(``model_type`` ``olmo_hybrid``): the full forward pass at EVERY position
in straightforward ``jax.numpy`` and float32, the gated delta rule as a
plain SEQUENTIAL scan over the tokens (never the chunked form), no cache,
no kernel, no batching.

It imports nothing of the program.  ``d`` hidden, ``H`` linear heads of
``d_k`` key and ``d_v`` value channels, ``K = linear_conv_kernel_dim``;
``Hq`` attention heads of width ``hd = d / Hq``, as many key/value heads;
RMSNorm (weight only, ``eps``) on each branch's OUTPUT and before the head,
none before a branch; NO positional encoding (the published ``rope_theta``
is ``null``); an untied head.  For the layer kind ``layer_types`` names:

* ``linear_attention``: ``[q~ | k~ | v~ | z | a | b] = x W_in`` (``H d_k |
  H d_k | H d_v | H d_v | H | H``); ``c_t <- silu(sum_i w_c[i] *
  c_{t-K+1+i})`` over ALL ``2 H d_k + H d_v`` channels of ``[q~ | k~ |
  v~]`` (depthwise, causal, zeros before the start, no bias); per head ``q
  = q^ / sqrt(|q^|^2 + 1e-6) / sqrt(d_k)``, ``k = k^ / sqrt(|k^|^2 +
  1e-6)``; ``beta = 2 sigmoid(b)`` (``linear_allow_neg_eigval``), ``alpha
  = exp(-exp(A_log) softplus(a + dt_bias))`` (scalars a head); ``S' =
  alpha S_{t-1}``, ``S_t = S' + beta k (v^ - S'^T k)^T`` (``[d_k, d_v]``,
  ``S_{-1} = 0``), ``o = S_t^T q``; ``y = w_n * o / sqrt(mean(o^2 over
  d_v) + eps) * silu(z)`` a head at a time (the norm FIRST, the gate after
  it); out ``y W_o``.
* ``full_attention``: ``[q | k | v] = x W_qkv`` (no bias); ``q``, ``k``
  RMS-normed over ALL ``d`` values of the projection with one weight each;
  ``a = softmax(q k^T / sqrt(hd), causal) v`` a head; out ``a W_o``.
* every layer: ``x += RMSNorm(Mixer(x))``; ``x += RMSNorm(W_2 (silu(g) *
  u))``, ``[g | u] = W_1 x``.
* the model: ``x_0 = E[token]``; ``logits = RMSNorm_f(x_L) W_head``.

``variant`` (the tests' wrong readings, each of which must FAIL the
comparison): ``"norm_before"`` (the norm on a branch's input, the family's
older order), ``"qk_norm_per_head"`` (the query and key norm a head at a
time), ``"rope_500000"`` (rotary positions at the family's earlier theta
on the full layers), ``"beta_1"`` (``beta = sigmoid(b)``), ``"no_l2"`` (no
L2 norm on ``q^`` and ``k^``), ``"gate_before_norm"`` (the gate first, the
norm after it).

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
LINEAR, FULL = "linear_attention", "full_attention"
L2_EPS = 1e-6
VARIANTS = ("norm_before", "qk_norm_per_head", "rope_500000", "beta_1",
            "no_l2", "gate_before_norm")


def sizes(model: dict) -> dict:
    d = model["hidden_size"]
    h = model["linear_num_value_heads"]
    if model["linear_num_key_heads"] != h:
        raise ValueError("as many linear key heads as value heads")
    if model["num_key_value_heads"] != model["num_attention_heads"]:
        raise ValueError("the full layers are multi-head: a key/value head "
                         "a query head")
    dk, dv = model["linear_key_head_dim"], model["linear_value_head_dim"]
    return {"d": d, "H": h, "dk": dk, "dv": dv, "kw": h * dk, "vw": h * dv,
            "conv": 2 * h * dk + h * dv, "K": model["linear_conv_kernel_dim"],
            "Hq": model["num_attention_heads"],
            "hd": d // model["num_attention_heads"]}


# -- seeded weights (the benchmark's own, not the program's) -----------------

def _tree(model: dict, leaf):
    """The program's tree, every leaf made by ``leaf(shape, how)``: ``how``
    a float is a normal's scale, else ``"ones"``, ``"post"`` (a branch's
    output norm), ``"seen"`` (uniform 0.5..1.5), ``"a_log"`` or
    ``"dt_bias"``."""
    s = sizes(model)
    d, f, h = s["d"], model["intermediate_size"], s["H"]
    std = model["initializer_range"]

    def mixer(kind):
        if kind == FULL:
            return {"w_qkv": leaf((d, 3 * d), std), "q_norm": leaf((d,), "seen"),
                    "k_norm": leaf((d,), "seen"), "w_o": leaf((d, d), std)}
        # The two head-wise gates' columns are drawn at 2 / sqrt(d), so
        # that ``a`` and ``b`` spread over about twice the stream's rms
        # whatever the width (the configuration file's ``assumed.weights``).
        return {"w_in": jnp.concatenate(
                    [leaf((d, s["conv"] + s["vw"]), std),
                     leaf((d, 2 * h), 2.0 * d ** -0.5)], axis=1),
                "conv_w": leaf((s["K"], s["conv"]), s["K"] ** -0.5),
                "A_log": leaf((h,), "a_log"),
                "dt_bias": leaf((h,), "dt_bias"),
                "norm": leaf((s["dv"],), "seen"),
                "w_o": leaf((s["vw"], d), std)}

    def layer(kind):
        return {"mixer": mixer(kind), "norm_mix": leaf((d,), "post"),
                "mlp": {"w1": leaf((d, 2 * f), std),
                        "w2": leaf((f, d), std)},
                "norm_mlp": leaf((d,), "post")}

    return {"embed": leaf((model["vocab_size"], d), std),
            "layers": [layer(kind) for kind in model["layer_types"]],
            "norm_f": leaf((d,), "ones"),
            "unembed": leaf((d, model["vocab_size"]), std)}


def init_params(model: dict, seed: int):
    """The configuration's ``assumed`` initialisation, drawn ON THE DEVICE
    leaf by leaf from the seed and rounded to the served type."""
    dt = jnp.dtype(model["dtype"])
    post = (2.0 * model["num_hidden_layers"]) ** -0.5

    @jax.jit
    def make(key):
        count = iter(range(10 ** 6))

        def leaf(shape, how):
            if how == "ones":
                return jnp.ones(shape, dt)
            if how == "post":
                return jnp.full(shape, post, dt)
            k = jax.random.fold_in(key, next(count))
            u = jax.random.uniform(k, shape, jnp.float32)
            if how == "seen":
                return (0.5 + u).astype(dt)
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


def linear_attention(model: dict, mp, x, mode: str, variant: str = ""):
    """``x [s, d]`` (the stream, or its norm under ``norm_before``).
    Returns ``out [s, d]``."""
    dot = P.binary(jnp.dot, mode)
    z = sizes(model)
    s, h, dk, dv, kw, vw, k_n = (x.shape[0], z["H"], z["dk"], z["dv"],
                                 z["kw"], z["vw"], z["K"])
    wide = dot(x, mp["w_in"])
    c, gate = wide[:, :z["conv"]], wide[:, z["conv"]:z["conv"] + vw]
    a, b = (wide[:, z["conv"] + vw:z["conv"] + vw + h],
            wide[:, z["conv"] + vw + h:])
    cp = jnp.concatenate([jnp.zeros((k_n - 1, z["conv"])), c])
    c = jax.nn.silu(sum(mp["conv_w"][i] * cp[i:i + s] for i in range(k_n)))
    q = c[:, :kw].reshape(s, h, dk)
    k = c[:, kw:2 * kw].reshape(s, h, dk)
    v = c[:, 2 * kw:].reshape(s, h, dv)
    if variant != "no_l2":
        q = q / jnp.sqrt(jnp.sum(q * q, axis=-1, keepdims=True) + L2_EPS)
        k = k / jnp.sqrt(jnp.sum(k * k, axis=-1, keepdims=True) + L2_EPS)
    q = q / math.sqrt(dk)
    beta = jax.nn.sigmoid(b)
    if model["linear_allow_neg_eigval"] and variant != "beta_1":
        beta = 2.0 * beta
    alpha = jnp.exp(-jnp.exp(mp["A_log"])
                    * jax.nn.softplus(a + mp["dt_bias"]))      # [s, H]

    def step(state, inp):                                # state [H, dk, dv]
        q_t, k_t, v_t, a_t, b_t = inp
        state = a_t[:, None, None] * state
        read = jnp.sum(state * k_t[:, :, None], axis=1)          # S'^T k
        state = state + (k_t[:, :, None]
                         * (b_t[:, None] * (v_t - read))[:, None, :])
        return state, jnp.sum(state * q_t[:, :, None], axis=1)

    _, o = jax.lax.scan(step, jnp.zeros((h, dk, dv)), (q, k, v, alpha, beta))
    eps = model["rms_norm_eps"]
    g = jax.nn.silu(gate).reshape(s, h, dv)
    if variant == "gate_before_norm":
        y = _rms(o * g, mp["norm"], eps)
    else:
        y = _rms(o, mp["norm"], eps) * g
    return dot(y.reshape(s, vw), mp["w_o"])


def _rotate(x, theta: float):
    """Rotate-half over the whole head: ``x [s, heads, hd]``; pairs are
    ``(i, i + hd / 2)`` (the ``rope_500000`` variant only)."""
    half = x.shape[-1] // 2
    inv = theta ** (-jnp.arange(half, dtype=jnp.float32) / half)
    ang = jnp.arange(x.shape[0], dtype=jnp.float32)[:, None, None] * inv
    cos, sin = jnp.cos(ang), jnp.sin(ang)
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin],
                           axis=-1)


def full_attention(model: dict, ap, x, mode: str, variant: str = ""):
    """``x [s, d]``.  Plain causal multi-head attention."""
    dot = P.binary(jnp.dot, mode)
    qk = P.binary(lambda a, b: jnp.einsum("qhd,khd->hqk", a, b), mode)
    pv = P.binary(lambda a, b: jnp.einsum("hqk,khd->qhd", a, b), mode)
    z = sizes(model)
    s, d, hq, hd = x.shape[0], z["d"], z["Hq"], z["hd"]
    eps = model["rms_norm_eps"]
    qkv = dot(x, ap["w_qkv"])
    q, k, v = qkv[:, :d], qkv[:, d:2 * d], qkv[:, 2 * d:]
    if variant == "qk_norm_per_head":
        q = _rms(q.reshape(s, hq, hd), ap["q_norm"].reshape(hq, hd), eps)
        k = _rms(k.reshape(s, hq, hd), ap["k_norm"].reshape(hq, hd), eps)
    else:
        q = _rms(q, ap["q_norm"], eps).reshape(s, hq, hd)
        k = _rms(k, ap["k_norm"], eps).reshape(s, hq, hd)
    if variant == "rope_500000":
        q, k = _rotate(q, 500000.0), _rotate(k, 500000.0)
    causal = jnp.arange(s)[None, :] <= jnp.arange(s)[:, None]
    scores = qk(q, k) / math.sqrt(hd)
    probs = jax.nn.softmax(jnp.where(causal, scores, -jnp.inf), axis=-1)
    return dot(pv(probs, v.reshape(s, hq, hd)).reshape(s, d), ap["w_o"])


def layer(model: dict, kind: str, lp, x, mode: str, variant: str = ""):
    """One layer over one sequence ``x [s, d]``."""
    dot = P.binary(jnp.dot, mode)
    eps = model["rms_norm_eps"]
    mixer = linear_attention if kind == LINEAR else full_attention

    def ffn(u):
        g, up = jnp.split(dot(u, lp["mlp"]["w1"]), 2, axis=-1)
        return dot(jax.nn.silu(g) * up, lp["mlp"]["w2"])

    if variant == "norm_before":
        x = x + mixer(model, lp["mixer"], _rms(x, lp["norm_mix"], eps), mode)
        return x + ffn(_rms(x, lp["norm_mlp"], eps))
    x = x + _rms(mixer(model, lp["mixer"], x, mode, variant),
                 lp["norm_mix"], eps)
    return x + _rms(ffn(x), lp["norm_mlp"], eps)


def head(model: dict, norm_f, unembed, x, mode: str):
    return P.binary(jnp.dot, mode)(
        _rms(x, norm_f, model["rms_norm_eps"]), unembed)


def forward(model: dict, params, tokens, mode: str = "f32",
            variant: str = ""):
    """One sequence ``tokens [s]`` through float32 copies of ``params``,
    all at once (the CPU tests' sizes): ``logits [s, vocab]``."""
    f32 = jax.tree_util.tree_map(lambda a: a.astype(jnp.float32), params)
    with jax.default_matmul_precision("highest"):
        x = f32["embed"][jnp.asarray(tokens)]
        for kind, lp in zip(model["layer_types"], f32["layers"]):
            x = layer(model, kind, lp, x, mode, variant)
        return head(model, f32["norm_f"], f32["unembed"], x, mode)


@functools.lru_cache(maxsize=None)
def _programs(model_json: str, mode: str):
    model = json.loads(model_json)
    layers = {kind: jax.jit(functools.partial(layer, model, kind, mode=mode))
              for kind in set(model["layer_types"])}
    return layers, jax.jit(lambda n, w, x: head(model, n, w, x, mode))


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
        embed = params["embed"]
        xs = []
        for s in sequences:
            row = np.zeros((width,), np.int32)
            row[:len(s)] = s          # causal: right padding is inert
            xs.append(embed[jnp.asarray(row)].astype(jnp.float32))
        for kind, lp in zip(model["layer_types"], params["layers"]):
            lp = f32(lp)
            for i, x in enumerate(xs):
                xs[i] = layers[kind](lp, x)
            del lp
        norm_f = f32(params["norm_f"])
        unembed = f32(params["unembed"])
        logits = []
        for x, s in zip(xs, sequences):
            rows = [np.asarray(out(norm_f, unembed, x[lo:lo + HEAD_ROWS]))
                    for lo in range(0, width, HEAD_ROWS) if lo < len(s)]
            logits.append(np.concatenate(rows)[:len(s)])
        return logits
