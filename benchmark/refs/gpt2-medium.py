"""Plain reference for the GPT-2 family: forward, next-token loss,
gradients and the AdamW update in straightforward ``jax.numpy`` and
float32, dense attention, no cache, no kernels, no batching tricks.

It imports nothing of the program.  It follows Radford et al. 2019 as
``openai-community/gpt2-medium`` configures it (pre-LayerNorm blocks,
learned positions, GELU in the tanh form), with the departures the
configuration file lists: ``embed`` and ``unembed`` are two matrices where
GPT-2 ties them, and the attention projections carry no bias.  The
parameter tree has the program's shape (per-layer leaves stacked on a
leading axis), so that one seeded tree feeds both sides.
"""

from __future__ import annotations

import functools
import json

import jax
import jax.numpy as jnp

from benchmark import precision as P

LN_EPS = 1e-5


# -- seeded weights and data (the benchmark's own, not the program's) --------

def init_params(model: dict, seed: int):
    """GPT-2's initialisation (normal 0.02; residual projections scaled by
    1/sqrt(2 layers)), made on the device in one jitted call, in the type
    the model is trained and served in."""
    n, d, f = model["n_layer"], model["n_embd"], model["n_inner"]
    v, s = model["vocab_size"], model["n_positions"]
    dt = jnp.dtype(model["dtype"])
    std = model["initializer_range"]
    res = std / (2 * n) ** 0.5

    @jax.jit
    def make(key):
        ks = iter(jax.random.split(key, 12))

        def w(shape, scale):
            return (jax.random.normal(next(ks), shape, jnp.float32)
                    * scale).astype(dt)

        def ln(*lead):
            return {"scale": jnp.ones(lead + (d,), dt),
                    "bias": jnp.zeros(lead + (d,), dt)}

        return {
            "embed": w((v, d), std), "pos_embed": w((s, d), std),
            "ln_f": ln(), "unembed": w((d, v), std),
            "layers": {
                "ln1": ln(n), "wq": w((n, d, d), std), "wk": w((n, d, d), std),
                "wv": w((n, d, d), std), "wo": w((n, d, d), res),
                "ln2": ln(n), "w_in": w((n, d, f), std),
                "b_in": jnp.zeros((n, f), dt), "w_out": w((n, f, d), res),
                "b_out": jnp.zeros((n, d), dt)},
        }

    return make(P.key_from_seed(seed))


def make_batch(model: dict, job: dict, seed: int, rows: int):
    """``rows`` sequences of random tokens and their shifted targets."""
    seq = job["seq_len"]

    @jax.jit
    def make(key):
        t = jax.random.randint(jax.random.fold_in(key, 1), (rows, seq + 1),
                               0, model["vocab_size"])
        return t[:, :-1].astype(jnp.int32), t[:, 1:].astype(jnp.int32)

    return make(P.key_from_seed(seed))


def items_per_row(model: dict, job: dict) -> int:
    return job["seq_len"]


# -- the model ---------------------------------------------------------------

def _ln(x, p):
    mu = jnp.mean(x, axis=-1, keepdims=True)
    var = jnp.mean(jnp.square(x - mu), axis=-1, keepdims=True)
    return (x - mu) * jax.lax.rsqrt(var + LN_EPS) * p["scale"] + p["bias"]


def _layer(model, mode):
    h_n = model["n_head"]
    dot = P.binary(jnp.dot, mode)
    qk = P.binary(lambda q, k: jnp.einsum("bqhd,bkhd->bhqk", q, k), mode)
    pv = P.binary(lambda p, v: jnp.einsum("bhqk,bkhd->bqhd", p, v), mode)

    def layer(x, lp):
        b, s, d = x.shape
        hd = d // h_n
        h = _ln(x, lp["ln1"])
        q, k, v = (dot(h, lp[n]).reshape(b, s, h_n, hd)
                   for n in ("wq", "wk", "wv"))
        scores = qk(q, k) * hd ** -0.5
        causal = jnp.tril(jnp.ones((s, s), bool))
        p = jax.nn.softmax(jnp.where(causal, scores, -jnp.inf), axis=-1)
        x = x + dot(pv(p, v).reshape(b, s, d), lp["wo"])
        h = _ln(x, lp["ln2"])
        h = jax.nn.gelu(dot(h, lp["w_in"]) + lp["b_in"], approximate=True)
        return x + dot(h, lp["w_out"]) + lp["b_out"]

    return layer


def logits_fn(model: dict, params, tokens, mode: str):
    """Logits ``[rows, positions, vocab]`` of whole sequences."""
    params = jax.tree_util.tree_map(lambda a: a.astype(jnp.float32), params)
    s = tokens.shape[1]
    x = params["embed"][tokens] + params["pos_embed"][:s]
    layer = jax.checkpoint(_layer(model, mode))

    def body(x, lp):
        return layer(x, lp), None

    x, _ = jax.lax.scan(body, x, params["layers"])
    return P.binary(jnp.dot, mode)(_ln(x, params["ln_f"]), params["unembed"])


def loss_fn(model: dict, job: dict, params, batch, mode: str):
    tokens, targets = batch
    logp = jax.nn.log_softmax(logits_fn(model, params, tokens, mode))
    return -jnp.mean(jnp.take_along_axis(logp, targets[..., None], axis=-1))


# -- three steps of the job, in blocks of rows -------------------------------

@functools.lru_cache(maxsize=None)
def _programs(model_json: str, job_json: str, mode: str):
    """The jitted programs of the reference, built once a process for each
    (configuration, job, precision)."""
    model, job = json.loads(model_json), json.loads(job_json)
    o = job.get("optimizer") or {}

    @jax.jit
    def block_grad(params, share):
        return jax.value_and_grad(
            lambda p: loss_fn(model, job, p, share, mode))(params)

    @jax.jit
    def update(params, mu, nu, grads, t):
        lr, b1, b2, eps, wd = (o["learning_rate"], o["b1"], o["b2"],
                               o["eps"], o["weight_decay"])
        mu = jax.tree_util.tree_map(lambda m, g: b1 * m + (1 - b1) * g,
                                    mu, grads)
        nu = jax.tree_util.tree_map(lambda n, g: b2 * n + (1 - b2) * g * g,
                                    nu, grads)

        def leaf(p, m, n):
            m_hat = m / (1 - b1 ** t)
            n_hat = n / (1 - b2 ** t)
            return p - lr * (m_hat / (jnp.sqrt(n_hat) + eps) + wd * p)

        return jax.tree_util.tree_map(leaf, params, mu, nu), mu, nu

    @jax.jit
    def one_row(params, tokens):
        return logits_fn(model, params, tokens, mode)[0]

    return block_grad, update, one_row


def train_reference(model: dict, job: dict, params0, batch, n_steps: int,
                    n_replicas: int, mode: str = "f32") -> dict:
    """Losses of ``n_steps`` steps, per-leaf norms of the first gradient as
    the optimizer gets it and of the parameters' change after the steps.
    Rows are independent, so the mean over the batch is taken block by
    block (``reference_rows`` at a time) whatever the replica count."""
    if job["optimizer"]["name"] != "adamw":
        raise ValueError("the LM reference follows AdamW, not "
                         f"{job['optimizer']}")
    total = batch[0].shape[0]
    rows = min(job.get("reference_rows", total), total)
    blocks = total // rows
    block_grad, update, _ = _programs(json.dumps(model, sort_keys=True),
                                      json.dumps(job, sort_keys=True), mode)

    p0 = jax.tree_util.tree_map(lambda x: x.astype(jnp.float32), params0)
    params = p0
    mu = jax.tree_util.tree_map(jnp.zeros_like, params)
    nu = jax.tree_util.tree_map(jnp.zeros_like, params)
    losses, grad_norms = [], None
    with jax.default_matmul_precision("highest"):
        for step in range(n_steps):
            loss, grads = 0.0, None
            for r in range(blocks):
                share = jax.tree_util.tree_map(
                    lambda x: x[r * rows:(r + 1) * rows], batch)
                lo, g = block_grad(params, share)
                loss += float(lo) / blocks
                grads = g if grads is None else jax.tree_util.tree_map(
                    jnp.add, grads, g)
            grads = jax.tree_util.tree_map(lambda g: g / blocks, grads)
            if step == 0:
                grad_norms = P.named(params, P.leaf_norms(grads))
            params, mu, nu = update(params, mu, nu, grads,
                                    jnp.float32(step + 1))
            losses.append(loss)
        dparam = P.named(params, P.leaf_diff_norms(params, p0))
    return {"losses": losses, "grad_norms": grad_norms,
            "dparam_norms": dparam}


def served_logits(model: dict, params, sequences, mode: str = "f32"):
    """Reference logits of whole served sequences, one row at a time:
    ``sequences`` is a list of token lists (prompt + served tokens); the
    result is a list of ``[len(sequence), vocab]`` float32 arrays."""
    import numpy as np

    width = max(len(s) for s in sequences)
    width += -width % 128         # one shape for a mix: one compile

    one = _programs(json.dumps(model, sort_keys=True), "{}", mode)[2]
    out = []
    with jax.default_matmul_precision("highest"):
        for s in sequences:
            row = np.zeros((1, width), np.int32)
            row[0, :len(s)] = s           # causal: right padding is inert
            out.append(np.asarray(one(params, jnp.asarray(row)))[:len(s)])
    return out
