"""Plain reference for the ResNet family: forward, loss, gradients and the
SGD-with-momentum update in straightforward ``jax.numpy`` and float32.

It imports nothing of the program.  It follows He et al. 2015
(arXiv:1512.03385) in the v1.5 form (stride 2 in the 3x3 of a stage's first
block) with the program's two stated choices: the MLPerf space-to-depth
stem (2x2 pixel blocks to 12 channels, then a 4x4 stride-1 convolution) and
the parameter tree named as flax names it, so that one seeded tree feeds
both sides.  Batch norm normalises over the rows of ONE replica's share of
the batch (the program does not synchronise the normalisation, only the
running statistics, which the training forward never reads).
"""

from __future__ import annotations

import functools
import json
from functools import partial

import jax
import jax.numpy as jnp

from benchmark import precision as P

EPS = 1e-5


# -- seeded weights and data (the benchmark's own, not the program's) --------

def _block_plan(model):
    """(name, in_channels, filters, stride, has_projection) per block."""
    plan, cin, idx = [], model["num_filters"], 0
    for i, count in enumerate(model["stage_sizes"]):
        f = model["num_filters"] * 2 ** i
        for j in range(count):
            stride = 2 if i > 0 and j == 0 else 1
            plan.append((f"BottleneckBlock_{idx}", cin, f, stride,
                         cin != 4 * f or stride != 1))
            cin, idx = 4 * f, idx + 1
    return plan, cin


def init_params(model: dict, seed: int):
    """He-normal kernels, unit scales, zero biases; float32, on the device
    in one jitted call."""
    nf = model["num_filters"]
    stem = (4, 4, 12, nf) if model["space_to_depth"] else (7, 7, 3, nf)
    plan, c_out = _block_plan(model)

    @jax.jit
    def make(key):
        keys = iter(jax.random.split(key, 4 * len(plan) + 8))

        def kernel(shape):
            fan_in = shape[0] * shape[1] * shape[2] if len(shape) == 4 \
                else shape[0]
            return jax.random.normal(next(keys), shape, jnp.float32) \
                * (2.0 / fan_in) ** 0.5

        def bn(c, scale=1.0):
            return {"scale": jnp.full((c,), scale, jnp.float32),
                    "bias": jnp.zeros((c,), jnp.float32)}

        p = {"conv_init": {"kernel": kernel(stem)}, "bn_init": bn(nf)}
        for name, cin, f, _stride, proj in plan:
            b = {"Conv_0": {"kernel": kernel((1, 1, cin, f))},
                 "BatchNorm_0": bn(f),
                 "Conv_1": {"kernel": kernel((3, 3, f, f))},
                 "BatchNorm_1": bn(f),
                 "Conv_2": {"kernel": kernel((1, 1, f, 4 * f))},
                 "BatchNorm_2": bn(4 * f, model["last_bn_scale"])}
            if proj:
                b["conv_proj"] = {"kernel": kernel((1, 1, cin, 4 * f))}
                b["norm_proj"] = bn(4 * f)
            p[name] = b
        p["head"] = {
            "kernel": jax.random.normal(
                next(keys), (c_out, model["num_classes"]), jnp.float32) * 0.01,
            "bias": jnp.zeros((model["num_classes"],), jnp.float32)}
        return p

    return make(P.key_from_seed(seed))


def make_batch(model: dict, job: dict, seed: int, rows: int):
    """Synthetic ImageNet-shaped rows from the seed, all different."""
    size = model["image_size"]

    @jax.jit
    def make(key):
        k1, k2 = jax.random.split(jax.random.fold_in(key, 1))
        images = jax.random.uniform(k1, (rows, size, size, 3), jnp.float32)
        labels = jax.random.randint(k2, (rows,), 0, model["num_classes"])
        return images, labels.astype(jnp.int32)

    return make(P.key_from_seed(seed))


def items_per_row(model: dict, job: dict) -> int:
    return 1


# -- the model ---------------------------------------------------------------

def _conv(mode, stride):
    def f(x, k):
        return jax.lax.conv_general_dilated(
            x, k, (stride, stride), "SAME",
            dimension_numbers=("NHWC", "HWIO", "NHWC"))
    return P.binary(f, mode)


def _bn(x, p):
    mean = jnp.mean(x, axis=(0, 1, 2))
    var = jnp.mean(jnp.square(x), axis=(0, 1, 2)) - jnp.square(mean)
    return (x - mean) * jax.lax.rsqrt(var + EPS) * p["scale"] + p["bias"]


def _block(x, bp, stride, mode):
    y = jax.nn.relu(_bn(_conv(mode, 1)(x, bp["Conv_0"]["kernel"]),
                        bp["BatchNorm_0"]))
    y = jax.nn.relu(_bn(_conv(mode, stride)(y, bp["Conv_1"]["kernel"]),
                        bp["BatchNorm_1"]))
    y = _bn(_conv(mode, 1)(y, bp["Conv_2"]["kernel"]), bp["BatchNorm_2"])
    if "conv_proj" in bp:
        x = _bn(_conv(mode, stride)(x, bp["conv_proj"]["kernel"]),
                bp["norm_proj"])
    return jax.nn.relu(x + y)


def logits_fn(model: dict, params, images, mode: str):
    x = images.astype(jnp.float32)
    if model["space_to_depth"]:
        b, h, w, c = x.shape
        x = x.reshape(b, h // 2, 2, w // 2, 2, c)
        x = x.transpose(0, 1, 3, 2, 4, 5).reshape(b, h // 2, w // 2, 4 * c)
        x = _conv(mode, 1)(x, params["conv_init"]["kernel"])
    else:
        x = _conv(mode, 2)(x, params["conv_init"]["kernel"])
    x = jax.nn.relu(_bn(x, params["bn_init"]))
    x = jax.lax.reduce_window(x, -jnp.inf, jax.lax.max, (1, 3, 3, 1),
                              (1, 2, 2, 1), "SAME")
    plan, _ = _block_plan(model)
    for name, _cin, _f, stride, _proj in plan:
        # Recompute a block's activations in the backward pass: float32
        # rows of the timed batch would not fit beside their gradients.
        x = jax.checkpoint(partial(_block, stride=stride, mode=mode))(
            x, params[name])
    x = jnp.mean(x, axis=(1, 2))
    return P.binary(jnp.dot, mode)(x, params["head"]["kernel"]) \
        + params["head"]["bias"]


def loss_fn(model: dict, job: dict, params, batch, mode: str):
    images, labels = batch
    logp = jax.nn.log_softmax(logits_fn(model, params, images, mode))
    ce = -jnp.mean(jnp.take_along_axis(logp, labels[:, None], axis=-1))
    l2 = sum(jnp.sum(jnp.square(p)) for p in jax.tree_util.tree_leaves(params)
             if p.ndim > 1)
    return ce + job["optimizer"]["l2"] * 0.5 * l2


# -- three steps of the job, one replica's rows at a time ---------------------

@functools.lru_cache(maxsize=None)
def _programs(model_json: str, job_json: str, mode: str):
    """The two jitted programs of a reference step, built once a process
    for each (configuration, job, precision)."""
    model, job = json.loads(model_json), json.loads(job_json)
    lr, mom = (job["optimizer"]["learning_rate"],
               job["optimizer"]["momentum"])

    @jax.jit
    def share_grad(params, share):
        return jax.value_and_grad(
            lambda p: loss_fn(model, job, p, share, mode))(params)

    @jax.jit
    def update(params, trace, grads):
        trace = jax.tree_util.tree_map(lambda t, g: g + mom * t, trace, grads)
        return jax.tree_util.tree_map(lambda p, t: p - lr * t, params,
                                      trace), trace

    return share_grad, update


def train_reference(model: dict, job: dict, params0, batch, n_steps: int,
                    n_replicas: int, mode: str = "f32") -> dict:
    """Losses of ``n_steps`` steps, per-leaf norms of the first gradient as
    the optimizer gets it (the mean over replicas) and of the parameters'
    change after the steps."""
    opt = job["optimizer"]
    if opt["name"] != "sgd":
        raise ValueError(f"the ResNet reference follows SGD, not {opt}")
    rows = batch[0].shape[0] // n_replicas
    share_grad, update = _programs(json.dumps(model, sort_keys=True),
                                   json.dumps(job, sort_keys=True), mode)

    params = jax.tree_util.tree_map(lambda x: x.astype(jnp.float32), params0)
    trace = jax.tree_util.tree_map(jnp.zeros_like, params)
    losses, grad_norms = [], None
    with jax.default_matmul_precision("highest"):
        for step in range(n_steps):
            loss, grads = 0.0, None
            for r in range(n_replicas):
                share = jax.tree_util.tree_map(
                    lambda x: x[r * rows:(r + 1) * rows], batch)
                lo, g = share_grad(params, share)
                loss += float(lo) / n_replicas
                grads = g if grads is None else jax.tree_util.tree_map(
                    jnp.add, grads, g)
            grads = jax.tree_util.tree_map(lambda g: g / n_replicas, grads)
            if step == 0:
                grad_norms = P.named(params, P.leaf_norms(grads))
            params, trace = update(params, trace, grads)
            losses.append(loss)
        dparam = P.named(params, P.leaf_diff_norms(params, params0))
    return {"losses": losses, "grad_norms": grad_norms,
            "dparam_norms": dparam}
