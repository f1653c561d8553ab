"""State-space duality: the Mamba-2 recurrence in its two forms, one Pallas
kernel each.

    S_t^h = exp(dt_t^h A^h) S_{t-1}^h + dt_t^h x_t^h B_t^T      [P, N] a head
    y_t^h = S_t^h C_t                                           [P]

``H`` heads of ``P`` channels, ONE scalar decay a head, ``B_t``/``C_t``
``[N]`` shared by every head (one group).  Over a prompt the recurrence is
computed in chunks of ``Q`` steps as matrix products
(:func:`ssd_chunk_scan`): with ``l_t = sum_{s <= t in the chunk} dt_s A``,

    y_t = sum_{s <= t} exp(l_t - l_s) (C_t . B_s) dt_s x_s  +  exp(l_t) S_0 C_t
    S_Q = exp(l_Q) S_0 + sum_s exp(l_Q - l_s) dt_s x_s B_s^T

and only one state a head crosses a chunk's edge.  In decode it is one step
a slot (:func:`ssd_step`): the state is read once, updated, contracted with
``C`` and written once, in place.

**The state's layout.**  A head's state is kept TRANSPOSED, ``[N, P]``,
and ``pack = 128 // P`` heads lie side by side in a 128-lane row
(:func:`head_pack`): ``[H / pack, N, pack * P]`` float32
(:func:`pack_state`).  A row of that array is then laid out like ``x``
itself (``[H * P]``: head after head), so the decay, ``dt x`` and ``y`` of a
step are plain lane rows that broadcast over the ``N`` sublane rows, ``y``
is a sum over sublanes, and the chunked form's products read ``x`` where
it lies: no operand is a column, nothing is relaid.

Products take their operands in ``x``'s type (the served one) and
accumulate in float32; decays, ``l``, the state and the product ``C S``
are float32.  ``*_jnp`` are the plain twins that run off the TPU (and that
the tests hold the kernels to in the interpreter); :func:`ssd_sequential`
is the recurrence step by step.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from .flash_attention import kernel_runs

LANES = 128
HEAD_BLOCK = 8          # heads a grid cell of the chunked scan holds
_EXACT = jax.lax.Precision.HIGHEST


def head_pack(n_heads: int, head_dim: int) -> int:
    """Heads side by side in one row of the state: as many as fill 128
    lanes, and a divisor of the head count."""
    pack = max(1, min(n_heads, LANES // head_dim))
    while n_heads % pack:
        pack -= 1
    return pack


def pack_state(s):
    """``[.., H, P, N]`` (a head's matrix as the equations write it) as
    the kept layout ``[.., H / pack, N, pack * P]``."""
    *lead, h, p, n = s.shape
    pack = head_pack(h, p)
    s = s.reshape(*lead, h // pack, pack, p, n)
    return jnp.moveaxis(s, -1, -3).reshape(*lead, h // pack, n, pack * p)


def unpack_state(s, head_dim: int):
    """The inverse of :func:`pack_state`."""
    *lead, r, n, w = s.shape
    pack = w // head_dim
    s = s.reshape(*lead, r, n, pack, head_dim)
    return jnp.moveaxis(s, -3, -1).reshape(*lead, r * pack, head_dim, n)


# -- one token a slot ---------------------------------------------------------

def _step_operands(x, dt, a, state_shape):
    """The step's per-slot rows in the state's lane layout ``[slots, R,
    W]``: the decay and ``dt x``."""
    b, h, p = x.shape
    r, w = state_shape[-3], state_shape[-1]
    decay = jnp.exp(dt * a)                                   # [b, h]
    decay = jnp.broadcast_to(decay[..., None], (b, h, p)).reshape(b, r, w)
    dtx = (dt[..., None] * x.astype(jnp.float32)).reshape(b, r, w)
    return decay, dtx


def ssd_step_jnp(state, x, dt, a, b, c, d, alive, *, layer=None):
    """:func:`ssd_step` in plain ``jnp``."""
    s = state if layer is None else state[layer]
    decay, dtx = _step_operands(x, dt, a, s.shape)
    bf, cf = b.astype(jnp.float32), c.astype(jnp.float32)
    new = (s * decay[:, :, None, :]
           + dtx[:, :, None, :] * bf[:, None, :, None])
    y = jnp.sum(new * cf[:, None, :, None], axis=2).reshape(x.shape)
    y = y + d[:, None] * x.astype(jnp.float32)
    new = jnp.where(alive[:, None, None, None], new, s)
    return y, (new if layer is None else state.at[layer].set(new))


def _step_kernel(ids_ref, n_ref, s_ref, decay_ref, dtx_ref, b_ref, c_ref,
                 y_ref, s_out_ref):
    i = pl.program_id(0)
    n_live = n_ref[0]
    rows, n, w = s_ref.shape[-3:]

    @pl.when(i < n_live)
    def _():
        # B and C as [N, W]: a row broadcast over W sublanes, transposed.
        bb = jnp.broadcast_to(b_ref[0], (w, n)).T
        cb = jnp.broadcast_to(c_ref[0], (w, n)).T

        def row(r, carry):
            new = (s_ref[0, 0, r] * decay_ref[0, pl.ds(r, 1), :]
                   + dtx_ref[0, pl.ds(r, 1), :] * bb)
            s_out_ref[0, 0, r] = new
            y_ref[0, pl.ds(r, 1), :] = jnp.sum(new * cb, axis=0,
                                               keepdims=True)
            return carry

        jax.lax.fori_loop(0, rows, row, 0)

    # Nobody alive: the one block this call maps is written back as it
    # came (a grid step past the live ones maps the last live slot's
    # block again and leaves it alone).
    @pl.when((n_live == 0) & (i == 0))
    def _():
        s_out_ref[...] = s_ref[...]
        y_ref[...] = jnp.zeros_like(y_ref)


@functools.partial(jax.jit, static_argnames=("layer", "interpret"),
                   inline=True)
def _pallas_step(state, decay, dtx, b, c, alive, layer, interpret: bool):
    whole = state if layer is not None else state[None]
    at = layer or 0
    slots, rows, n, w = whole.shape[1:]
    # Live slots first; the steps past them repeat the last live one, so
    # their blocks are neither fetched nor written again.
    order = jnp.argsort(~alive, stable=True).astype(jnp.int32)
    n_live = jnp.sum(alive).astype(jnp.int32)
    ids = jnp.where(jnp.arange(slots) < n_live, order,
                    order[jnp.maximum(n_live - 1, 0)])
    a_slot = pl.BlockSpec((1, rows, w), lambda i, ids, n: (ids[i], 0, 0))
    a_vec = pl.BlockSpec((1, 1, n), lambda i, ids, n: (ids[i], 0, 0))
    a_state = pl.BlockSpec((1, 1, rows, n, w),
                           lambda i, ids, n: (at, ids[i], 0, 0, 0))
    y, whole = pl.pallas_call(
        _step_kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2, grid=(slots,),
            in_specs=[a_state, a_slot, a_slot, a_vec, a_vec],
            out_specs=[a_slot, a_state]),
        out_shape=[jax.ShapeDtypeStruct((slots, rows, w), jnp.float32),
                   jax.ShapeDtypeStruct(whole.shape, whole.dtype)],
        # The store goes in and comes out as ONE buffer: only the live
        # slots' blocks of this layer are touched.
        input_output_aliases={2: 1},
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",),
            vmem_limit_bytes=32 << 20),
        interpret=interpret, name="ssd_step",
    )(ids, n_live.reshape(1), whole, decay, dtx,
      b.astype(jnp.float32)[:, None, :], c.astype(jnp.float32)[:, None, :])
    return y, (whole if layer is not None else whole[0])


def ssd_step(state, x, dt, a, b, c, d, alive, *, layer=None,
             interpret=None):
    """One step for every slot alive.  ``state [slots, R, N, W]`` float32
    in the kept layout, or with ``layer`` given the whole store ``[layers,
    slots, R, N, W]`` of which that layer's rows are updated; ``x [slots,
    H, P]``; ``dt [slots, H]`` (after its softplus); ``a``/``d`` ``[H]``;
    ``b``/``c`` ``[slots, N]``; ``alive [slots]`` bool.  Returns ``(y
    [slots, H, P] float32 = S C + D x, state)``: the state given, with the
    live slots' rows advanced and every other row BIT FOR BIT what it was
    (on the TPU the kernel's output IS its input buffer, and an idle
    slot's block is never moved; an idle slot's ``y`` is zero)."""
    if not kernel_runs(interpret):
        y, state = ssd_step_jnp(state, x, dt, a, b, c, d, alive,
                                layer=layer)
        return jnp.where(alive[:, None, None], y, 0.0), state
    s_shape = state.shape if layer is None else state.shape[1:]
    decay, dtx = _step_operands(x, dt, a, s_shape)
    y, state = _pallas_step(state, decay, dtx, b, c, alive, layer,
                            bool(interpret))
    y = y.reshape(x.shape) + d[:, None] * x.astype(jnp.float32)
    return jnp.where(alive[:, None, None], y, 0.0), state


# -- a prompt -----------------------------------------------------------------

def ssd_sequential(x, dt, a, b, c, s0, n_valid):
    """The recurrence step by step (``lax.scan``), everything float32:
    ``x [t, H, P]``, ``dt [t, H]``, ``a [H]``, ``b``/``c`` ``[t, N]``,
    ``s0 [H, P, N]`` as the equations write it.  Returns ``(y [t, H, P],
    s_out [H, P, N])``; steps ``>= n_valid`` leave the state."""
    f = jnp.float32

    def step(s, inp):
        i, xi, di, bi, ci = inp
        new = (jnp.exp(di * a)[:, None, None] * s
               + (di[:, None] * xi)[:, :, None] * bi[None, None, :])
        return (jnp.where(i < n_valid, new, s),
                jnp.sum(new * ci[None, None, :], axis=-1))

    s_out, y = jax.lax.scan(step, s0.astype(f), (
        jnp.arange(x.shape[0]), x.astype(f), dt.astype(f), b.astype(f),
        c.astype(f)))
    return y, s_out


def _chunked(x, dt, a, b, c, n_valid, chunk: int):
    """``(q, x, dt, l, b, c)``: the time axis padded to whole chunks of
    ``q`` steps, ``dt`` zero at and past ``n_valid`` (a step with ``dt =
    0`` decays by ``exp(0)`` and adds nothing: the state stays bit for
    bit), and ``l``, the running sum of ``dt a`` inside each chunk."""
    t, h = dt.shape
    q = min(chunk, -(-t // 8) * 8)
    dt = jnp.where(jnp.arange(t)[:, None] < n_valid, dt, 0.0)
    x, dt, b, c = (jnp.pad(v, ((0, -t % q),) + ((0, 0),) * (v.ndim - 1))
                   for v in (x, dt, b, c))
    l = jnp.cumsum((dt * a).reshape(-1, q, h), axis=1).reshape(-1, h)
    return q, x, dt, l, b, c


def ssd_chunk_scan_jnp(x, dt, a, b, c, s0, n_valid, *, chunk: int = 256):
    """:func:`ssd_chunk_scan` in plain ``jnp``: the same chunked
    equations, a ``lax.scan`` over the chunks."""
    t, h, p = x.shape
    od = x.dtype
    q, x, dt, l, b, c = _chunked(x, dt, a, b, c, n_valid, chunk)
    causal = jnp.tril(jnp.ones((q, q), bool))

    def one(s, inp):                  # s [h, p, n]
        xq, dq, lq, bq, cq = inp      # [q, h, p], [q, h], [q, h], [q, n] x 2
        cb = jnp.einsum("tn,sn->ts", cq, bq,
                        preferred_element_type=jnp.float32)
        gap = lq.T[:, :, None] - lq.T[:, None, :]            # [h, t, s]
        m = (jnp.where(causal, jnp.exp(jnp.where(causal, gap, 0.0)), 0.0)
             * cb * dq.T[:, None, :])
        y = jnp.einsum("hts,shp->thp", m.astype(od), xq,
                       preferred_element_type=jnp.float32)
        y = y + jnp.exp(lq)[:, :, None] * jnp.einsum(
            "tn,hpn->thp", cq.astype(jnp.float32), s, precision=_EXACT)
        w = jnp.exp(lq[-1] - lq) * dq                         # [q, h]
        xw = (xq.astype(jnp.float32) * w[:, :, None]).astype(od)
        s = (jnp.exp(lq[-1])[:, None, None] * s
             + jnp.einsum("shp,sn->hpn", xw, bq,
                          preferred_element_type=jnp.float32))
        return s, y

    split = lambda v: v.reshape(-1, q, *v.shape[1:])
    s_out, y = jax.lax.scan(one, unpack_state(s0, p), (
        split(x), split(dt), split(l), split(b), split(c)))
    return y.reshape(-1, h, p)[:t], pack_state(s_out)


def _chunk_kernel(nv_ref, x_ref, b_ref, c_ref, lc_ref, lr_ref, dc_ref,
                  dr_ref, s0_ref, y_ref, s_out_ref, s_scr, *, q: int,
                  pack: int, p: int):
    chunk = pl.program_id(1)
    rows, n, w = s_scr.shape

    @pl.when(chunk == 0)
    def _():
        s_scr[...] = s0_ref[...]

    @pl.when(chunk * q >= nv_ref[0])
    def _():
        y_ref[...] = jnp.zeros_like(y_ref)

    @pl.when(chunk * q < nv_ref[0])
    def _():
        od = x_ref.dtype
        bq, cq = b_ref[...], c_ref[...]
        cb = jax.lax.dot_general(cq, bq, (((1,), (1,)), ((), ())),
                                 preferred_element_type=jnp.float32)
        causal = (jax.lax.broadcasted_iota(jnp.int32, (q, q), 0)
                  >= jax.lax.broadcasted_iota(jnp.int32, (q, q), 1))
        head_of = jax.lax.broadcasted_iota(jnp.int32, (1, w), 1) // p
        cf = cq.astype(jnp.float32)
        for r in range(rows):
            xr = x_ref[:, r * w:(r + 1) * w]                  # [q, w]
            s = s_scr[r]                                      # [n, w]
            carried = jnp.dot(cf, s, precision=_EXACT,
                              preferred_element_type=jnp.float32)
            y = jnp.zeros((q, w), jnp.float32)
            weight = jnp.zeros((q, w), jnp.float32)
            keep = jnp.zeros((1, w), jnp.float32)
            for e in range(pack):
                h = r * pack + e
                lc, lr = lc_ref[0, :, h:h + 1], lr_ref[0, h:h + 1, :]
                dc, dr = dc_ref[0, :, h:h + 1], dr_ref[0, h:h + 1, :]
                m = jnp.where(causal,
                              jnp.exp(jnp.where(causal, lc - lr, 0.0)),
                              0.0) * cb * dr
                mine = head_of == e
                y = jnp.where(
                    mine, jnp.dot(m.astype(od), xr,
                                  preferred_element_type=jnp.float32)
                    + jnp.exp(lc) * carried, y)
                last = lc[q - 1:q, :]                         # [1, 1]
                weight = jnp.where(mine, jnp.exp(last - lc) * dc, weight)
                keep = jnp.where(mine, jnp.exp(last), keep)
            y_ref[:, r * w:(r + 1) * w] = y
            xw = (xr.astype(jnp.float32) * weight).astype(od)
            s_scr[r] = keep * s + jax.lax.dot_general(
                bq, xw, (((0,), (0,)), ((), ())),
                preferred_element_type=jnp.float32)

    s_out_ref[...] = s_scr[...]


@functools.partial(jax.jit, static_argnames=("chunk", "interpret"),
                   inline=True)
def _pallas_chunk_scan(x, dt, a, b, c, s0, n_valid, chunk: int,
                       interpret: bool):
    t, h, p = x.shape
    n = b.shape[-1]
    rows_all, _, w = s0.shape
    pack = w // p
    q, x, dt, l, b, c = _chunked(x, dt, a, b, c, n_valid, chunk)
    tp = x.shape[0]
    # Heads a grid cell: whole rows of the state, HEAD_BLOCK heads or all.
    hb = HEAD_BLOCK if h % HEAD_BLOCK == 0 and HEAD_BLOCK % pack == 0 else h
    rows = hb // pack
    groups = h // hb
    # A head's l and dt both as a column of its steps and as a row.
    cols = lambda v: v.reshape(tp, groups, hb).transpose(1, 0, 2)
    as_rows = lambda v: v.reshape(tp, groups, hb).transpose(1, 2, 0)
    lanes = pl.BlockSpec((q, rows * w), lambda g, ch, nv: (ch, g))
    shared = pl.BlockSpec((q, n), lambda g, ch, nv: (ch, 0))
    col = pl.BlockSpec((1, q, hb), lambda g, ch, nv: (g, ch, 0))
    row = pl.BlockSpec((1, hb, q), lambda g, ch, nv: (g, 0, ch))
    st = pl.BlockSpec((rows, n, w), lambda g, ch, nv: (g, 0, 0))
    y, s_out = pl.pallas_call(
        functools.partial(_chunk_kernel, q=q, pack=pack, p=p),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1, grid=(groups, tp // q),
            in_specs=[lanes, shared, shared, col, row, col, row, st],
            out_specs=[lanes, st],
            scratch_shapes=[pltpu.VMEM((rows, n, w), jnp.float32)]),
        out_shape=[jax.ShapeDtypeStruct((tp, h * p), jnp.float32),
                   jax.ShapeDtypeStruct(s0.shape, jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary"),
            vmem_limit_bytes=48 << 20),
        interpret=interpret, name="ssd_chunk_scan",
    )(jnp.asarray(n_valid, jnp.int32).reshape(1), x.reshape(tp, h * p),
      b.astype(x.dtype), c.astype(x.dtype), cols(l), as_rows(l), cols(dt),
      as_rows(dt), s0)
    return y[:t].reshape(t, h, p), s_out


def ssd_chunk_scan(x, dt, a, b, c, s0, n_valid, *, chunk: int = 256,
                   interpret=None):
    """A prompt's recurrence in chunks.  ``x [t, H, P]`` (its type is the
    products' operand type), ``dt [t, H]`` float32 after its softplus,
    ``a [H]`` (negative), ``b``/``c`` ``[t, N]``, ``s0 [H / pack, N, pack *
    P]`` float32 in the kept layout, ``n_valid``: steps at and past it
    (a bucket's padding) leave the state alone.  Returns ``(y [t, H, P]
    float32 = S_t C_t, s_out)``: the state after step ``n_valid - 1``;
    rows of ``y`` at and past ``n_valid`` mean nothing.  On the TPU the
    Pallas kernel (grid: blocks of heads by chunks, the block's state in
    VMEM across its chunks, chunks wholly past ``n_valid`` skipped);
    elsewhere :func:`ssd_chunk_scan_jnp`, unless ``interpret=True`` asks
    for the kernel in the interpreter (its own tests)."""
    dt, a, s0 = (v.astype(jnp.float32) for v in (dt, a, s0))
    if not kernel_runs(interpret):
        return ssd_chunk_scan_jnp(x, dt, a, b, c, s0, n_valid, chunk=chunk)
    return _pallas_chunk_scan(x, dt, a, b, c, s0, n_valid, chunk,
                              bool(interpret))
