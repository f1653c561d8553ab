"""Chunked selective scan: the state-space recurrence of a whole prompt in
one Pallas kernel.

    S_t = exp(dt_t * A) * S_{t-1} + (dt_t * x_t) B_t^T        [d_state, lanes]
    y_t = C_t^T S_t                                           [lanes]

for ``t = 0 .. T-1`` over ``d_inner`` independent channels.  The kernel
walks time INSIDE itself: the grid is (blocks of channels, chunks of
time), a block's ``[d_state, lanes]`` float32 state lives in VMEM across
its chunks, and each step is a handful of vector operations on it.  The
two things it replaces are no served path: a ``lax.scan`` over the tokens
is thousands of tiny sequential device steps a layer, and the expanded
``[T, d_inner, d_state]`` state of the parallel form is 671 MB a layer at
2048 tokens.

It takes an initial state and a count of valid steps: a step at or past
``n_valid`` (the padding of a prefill bucket) leaves the state as it is,
so ``s_out`` is the state after the last REAL token; chunks wholly past
it are skipped and their ``y`` rows are zero.

``ssm_step`` is the same recurrence for one token a slot, plain ``jnp``:
what decode runs.  ``ssm_scan_reference`` is the sequential ``lax.scan``
the kernel is tested against (and what runs off the TPU unless a test
asks for the interpreter).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from .flash_attention import kernel_runs

TIME_BLOCK = 128        # steps a grid cell walks
LANE_BLOCK = 1024       # channels a grid cell holds


def ssm_step(state, x, dt, b, c, a):
    """One step for a batch: ``state [b, n, d]`` float32, ``x``/``dt``
    ``[b, d]``, ``b``/``c`` ``[b, n]``, ``a [n, d]``.  Returns ``(y [b, d],
    state)``."""
    state = (jnp.exp(dt[:, None, :] * a[None]) * state
             + (dt * x)[:, None, :] * b[:, :, None])
    return jnp.sum(state * c[:, :, None], axis=1), state


def ssm_scan_reference(x, dt, b, c, a, s0, n_valid):
    """The recurrence as a sequential scan: ``x``/``dt`` ``[t, d]``,
    ``b``/``c`` ``[t, n]``, ``a``/``s0`` ``[n, d]``, all float32.  Returns
    ``(y [t, d], s_out [n, d])``; steps ``>= n_valid`` leave the state."""
    def step(s, inp):
        i, xi, di, bi, ci = inp
        y, new = ssm_step(s[None], xi[None], di[None], bi[None], ci[None], a)
        return jnp.where(i < n_valid, new[0], s), y[0]

    s_out, y = jax.lax.scan(
        step, s0, (jnp.arange(x.shape[0]), x, dt, b, c))
    return y, s_out


def _kernel(nv_ref, x_ref, dt_ref, b_ref, c_ref, a_ref, s0_ref,
            y_ref, s_out_ref, s_scr, *, bt: int):
    chunk = pl.program_id(1)

    @pl.when(chunk == 0)
    def _():
        s_scr[...] = s0_ref[...]

    n_valid = nv_ref[0]
    t0 = chunk * bt

    @pl.when(t0 >= n_valid)
    def _():
        y_ref[...] = jnp.zeros_like(y_ref)

    @pl.when(t0 < n_valid)
    def _():
        a = a_ref[...]

        def body(t, s):
            xr = x_ref[pl.ds(t, 1), :]              # [1, lanes]
            dr = dt_ref[pl.ds(t, 1), :]
            new = jnp.exp(dr * a) * s + (dr * xr) * b_ref[t]   # b: [n, 1]
            y_ref[pl.ds(t, 1), :] = jnp.sum(new * c_ref[t], axis=0,
                                            keepdims=True)
            return jnp.where(t0 + t < n_valid, new, s)

        s_scr[...] = jax.lax.fori_loop(0, bt, body, s_scr[...])

    s_out_ref[...] = s_scr[...]


def _blocks(t: int, d: int):
    bt = min(TIME_BLOCK, -(-t // 8) * 8)
    bc = LANE_BLOCK if d % LANE_BLOCK == 0 else d
    return bt, bc


@functools.partial(jax.jit, static_argnames=("interpret",))
def _pallas_scan(x, dt, b, c, a, s0, n_valid, interpret: bool):
    t, d = x.shape
    n = a.shape[0]
    bt, bc = _blocks(t, d)
    pad = -t % bt
    if pad:
        x, dt = (jnp.pad(v, ((0, pad), (0, 0))) for v in (x, dt))
        b, c = (jnp.pad(v, ((0, pad), (0, 0))) for v in (b, c))
    tp = t + pad
    # A step's B and C as [n, 1] columns (state rows on sublanes): the
    # arrays go in as [t, n, 1] and a step reads its own.
    b, c = b[:, :, None], c[:, :, None]
    row = pl.BlockSpec((bt, bc), lambda ch, tc, nv: (tc, ch))
    col = pl.BlockSpec((bt, n, 1), lambda ch, tc, nv: (tc, 0, 0))
    lane = pl.BlockSpec((n, bc), lambda ch, tc, nv: (0, ch))
    y, s_out = pl.pallas_call(
        functools.partial(_kernel, bt=bt),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1, grid=(d // bc, tp // bt),
            in_specs=[row, row, col, col, lane, lane],
            out_specs=[row, lane],
            scratch_shapes=[pltpu.VMEM((n, bc), jnp.float32)]),
        out_shape=[jax.ShapeDtypeStruct((tp, d), jnp.float32),
                   jax.ShapeDtypeStruct((n, d), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary"),
            vmem_limit_bytes=32 << 20),
        interpret=interpret, name="ssm_scan",
    )(jnp.asarray(n_valid, jnp.int32).reshape(1), x, dt, b, c, a, s0)
    return y[:t], s_out


def ssm_scan(x, dt, b, c, a, s0, n_valid, *, interpret=None):
    """``(y [t, d], s_out [n, d])`` of the recurrence above, float32.
    On the TPU the Pallas kernel; elsewhere the sequential reference,
    unless ``interpret=True`` asks for the kernel in the interpreter (its
    own tests)."""
    x, dt, b, c, a, s0 = (v.astype(jnp.float32)
                          for v in (x, dt, b, c, a, s0))
    if not kernel_runs(interpret):
        return ssm_scan_reference(x, dt, b, c, a, s0, n_valid)
    return _pallas_scan(x, dt, b, c, a, s0, n_valid, bool(interpret))
