"""Precisions the plain references compute in.

``f32``  — float32 operands at ``highest`` matmul precision: the reference.
``bf16`` — operands rounded to bfloat16, float32 accumulation: what the
           configurations state (a sanity reading, never a control).
``fp8``  — operands rounded to e4m3 (3 mantissa bits) with one scale per
           tensor, float32 accumulation: the nearest precision below bf16,
           the step that would tempt a later PR.  This is the control.

A low precision applies to every matmul and convolution, forward and
backward: the cotangent is rounded too.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

MODES = ("f32", "bf16", "fp8")


def _round_bf16(x):
    return x.astype(jnp.bfloat16).astype(jnp.float32)


def _round_fp8(x):
    """Scaled e4m3 round trip in float32 arithmetic (no fp8 dtype needed):
    the largest magnitude maps to 448, normals keep 3 mantissa bits down to
    2^-6, below that the step is 2^-9."""
    amax = jnp.max(jnp.abs(x))
    scale = jnp.where(amax > 0, 448.0 / amax, 1.0)
    y = x * scale
    _, ex = jnp.frexp(y)                      # |y| = m * 2^ex, m in [.5, 1)
    e = jnp.maximum(ex - 1, -6)
    step = jnp.ldexp(jnp.ones_like(y), e - 3)
    return jnp.round(y / step) * step / scale


_ROUND = {"bf16": _round_bf16, "fp8": _round_fp8}


def binary(f, mode: str):
    """``f(a, b)`` (a matmul, an einsum, a convolution) computed in
    ``mode``.  For ``f32`` it is ``f`` itself; the caller holds
    ``jax.default_matmul_precision("highest")`` around the whole
    computation."""
    if mode not in MODES:
        raise ValueError(f"precision {mode!r}: expected one of {MODES}")
    if mode == "f32":
        return f
    q = _ROUND[mode]

    @jax.custom_vjp
    def op(a, b):
        return f(q(a), q(b))

    def fwd(a, b):
        qa, qb = q(a), q(b)
        return f(qa, qb), (qa, qb)

    def bwd(res, g):
        _, vjp = jax.vjp(f, *res)
        return vjp(q(g))

    op.defvjp(fwd, bwd)
    return op


def key_from_seed(seed: int):
    """A PRNG key from any whole number up to a little over 2**31."""
    seed = int(seed)
    return jax.random.fold_in(jax.random.PRNGKey(seed & 0x7FFFFFFF),
                              seed >> 31)


def leaf_names(tree) -> list:
    return [jax.tree_util.keystr(p)
            for p, _ in jax.tree_util.tree_flatten_with_path(tree)[0]]


@jax.jit
def leaf_norms(tree):
    return jnp.stack([jnp.sqrt(jnp.sum(jnp.square(x.astype(jnp.float32))))
                      for x in jax.tree_util.tree_leaves(tree)])


@jax.jit
def leaf_diff_norms(a, b):
    return jnp.stack([
        jnp.sqrt(jnp.sum(jnp.square(x.astype(jnp.float32)
                                    - y.astype(jnp.float32))))
        for x, y in zip(jax.tree_util.tree_leaves(a),
                        jax.tree_util.tree_leaves(b))])


def named(tree, vector) -> dict:
    import numpy as np

    return dict(zip(leaf_names(tree), np.asarray(vector, np.float64).tolist()))
