"""The gated delta rule: linear attention whose state is CORRECTED, not only
decayed and added to, in its two forms, one Pallas kernel each.

    S'  = exp(g_t) S_{t-1}                            [d_k, d_v] a head
    S_t = S' + beta_t k_t (v_t - S'^T k_t)^T          (= (I - beta k k^T) S' + beta k v^T)
    o_t = S_t^T q_t                                   [d_v]

``g_t <= 0`` is the logarithm of the step's decay, ``beta_t`` in ``(0, 2)``
how much of what the state holds under ``k_t`` is replaced (past 1 the
transition's eigenvalue along ``k_t``, ``exp(g) (1 - beta)``, is negative),
``|k_t| = 1``.  Every step first READS the state under the new key
(``S'^T k``) and writes the difference: where :mod:`~horovod_tpu.ops.ssd`'s
recurrence is one scaled add, this one is a matrix-vector product, a
rank-one update and a second product.

Over a prompt the recurrence runs in chunks of ``C`` steps
(:func:`gated_delta_chunk_scan`, the WY / UT form of Yang et al.,
arXiv:2406.06484 and arXiv:2412.06464).  With ``c_t`` the running sum of
``g`` inside the chunk and ``u_t = beta_t (v_t - S'^T k_t)`` the value a
step really writes, ``S_t = exp(c_t) S_0 + sum_{s <= t} exp(c_t - c_s) k_s
u_s^T``, so the ``u`` of a chunk solve a UNIT LOWER TRIANGULAR system,

    (I + A) U = diag(beta) (V - diag(exp(c)) K S_0),   A[t, s] = beta_t exp(c_t - c_s) (k_t . k_s), s < t
    O   = diag(exp(c)) Q S_0 + (M o Q K^T) U,          M[t, s] = exp(c_t - c_s), s <= t
    S_C = exp(c_C) S_0 + (diag(exp(c_C - c)) K)^T U

and only one state a head crosses a chunk's edge.  ``(I + A)^-1`` is formed
by inverting the diagonal blocks and doubling them (:func:`unit_lower_
inverse`: block forward substitution as ten small products; the series
``sum (-A)^n`` is the same matrix and cancels catastrophically once keys
repeat).  In decode it is one step a slot (:func:`gated_delta_step`): the
state is read once, decayed, corrected, read out and written once, in
place.

**The state's layout.**  A head's state stays as the equations write it,
``[d_k, d_v]``: ``d_k`` sublane rows, so that ``S^T k`` and ``S^T q`` are
sums over sublanes and ``k u^T`` a column times a row, and ``pack`` heads
lie side by side in the lanes (:func:`head_pack`: the fewest that fill
whole 128-lane rows; 96 x 192 a head is two heads to a ``[96, 384]`` row
block): ``[H / pack, d_k, pack * d_v]`` float32 (:func:`pack_state`).  A
row of that array is laid out like ``v`` itself (``[H * d_v]``, head after
head), so the decay, ``beta``, ``v`` and ``o`` of a step are plain lane
rows; only ``k`` and ``q`` come in as columns.  The chunked kernel works a
head at a time on the unpacked state (its products want one head's
columns); the wrapper packs what it leaves.

The state, the decays, ``beta`` and the triangular solve are float32, and
so is every product that READS the state (``K S_0``, ``Q S_0``).  The
chunked form's other products (``K K^T``, ``Q K^T``, the masked scores
times ``U``, the decayed keys times ``U``) take their operands in ``q``'s
type (the served one) and accumulate in float32.  ``*_jnp`` are the plain
twins that run off the TPU (and that the tests hold the kernels to in the
interpreter); :func:`gated_delta_sequential` is the recurrence step by
step.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from .flash_attention import kernel_runs

LANES = 128
HEAD_BLOCK = 6          # heads a grid cell of the chunked scan holds
_EXACT = jax.lax.Precision.HIGHEST


def head_pack(n_heads: int, d_v: int) -> int:
    """Heads side by side in one row of the state: the fewest that fill
    whole 128-lane rows and divide the head count (one where none does)."""
    for pack in range(1, n_heads + 1):
        if n_heads % pack == 0 and (pack * d_v) % LANES == 0:
            return pack
    return 1


def pack_state(s):
    """``[.., H, d_k, d_v]`` (a head's matrix as the equations write it)
    as the kept layout ``[.., H / pack, d_k, pack * d_v]``."""
    *lead, h, dk, dv = s.shape
    pack = head_pack(h, dv)
    s = s.reshape(*lead, h // pack, pack, dk, dv)
    return jnp.moveaxis(s, -3, -2).reshape(*lead, h // pack, dk, pack * dv)


def unpack_state(s, d_v: int):
    """The inverse of :func:`pack_state`."""
    *lead, r, dk, w = s.shape
    pack = w // d_v
    s = s.reshape(*lead, r, dk, pack, d_v)
    return jnp.moveaxis(s, -2, -3).reshape(*lead, r * pack, dk, d_v)


# -- the recurrence, step by step ---------------------------------------------

def gated_delta_sequential(q, k, v, g, beta, s0, n_valid):
    """The recurrence step by step (``lax.scan``), everything float32:
    ``q``/``k`` ``[t, H, d_k]``, ``v [t, H, d_v]``, ``g``/``beta`` ``[t,
    H]``, ``s0 [H, d_k, d_v]`` as the equations write it.  Returns ``(o [t,
    H, d_v], s_out [H, d_k, d_v])``; steps ``>= n_valid`` leave the
    state."""
    f = jnp.float32

    def step(s, inp):
        i, qi, ki, vi, gi, bi = inp
        sp = jnp.exp(gi)[:, None, None] * s
        read = jnp.einsum("hkv,hk->hv", sp, ki, precision=_EXACT)
        new = sp + ki[:, :, None] * (bi[:, None] * (vi - read))[:, None, :]
        return (jnp.where(i < n_valid, new, s),
                jnp.einsum("hkv,hk->hv", new, qi, precision=_EXACT))

    s_out, o = jax.lax.scan(step, s0.astype(f), (
        jnp.arange(q.shape[0]), q.astype(f), k.astype(f), v.astype(f),
        g.astype(f), beta.astype(f)))
    return o, s_out


# -- one token a slot ---------------------------------------------------------

def gated_delta_step_jnp(state, q, k, v, g, beta, alive, *, layer=None):
    """:func:`gated_delta_step` in plain ``jnp``."""
    f = jnp.float32
    packed = state if layer is None else state[layer]
    s = unpack_state(packed, v.shape[-1])            # [b, H, d_k, d_v]
    kf, qf = k.astype(f), q.astype(f)
    sp = jnp.exp(g)[:, :, None, None] * s
    read = jnp.einsum("bhkv,bhk->bhv", sp, kf, precision=_EXACT)
    u = beta[..., None] * (v.astype(f) - read)
    new = sp + kf[..., None] * u[:, :, None, :]
    o = jnp.einsum("bhkv,bhk->bhv", new, qf, precision=_EXACT)
    new = jnp.where(alive[:, None, None, None], pack_state(new), packed)
    return o, (new if layer is None else state.at[layer].set(new))


def _lane_rows(x, state_shape):
    """``x [slots, H]`` or ``[slots, H, d_v]`` as float32 rows in the
    state's lane layout ``[slots, R, W]``."""
    b, h = x.shape[:2]
    r, w = state_shape[-3], state_shape[-1]
    if x.ndim == 2:
        x = jnp.broadcast_to(x[..., None], (b, h, w * r // h))
    return x.astype(jnp.float32).reshape(b, r, w)


def _step_kernel(ids_ref, n_ref, s_ref, decay_ref, beta_ref, v_ref, kt_ref,
                 qt_ref, o_ref, s_out_ref, *, pack: int, d_v: int):
    i = pl.program_id(0)
    n_live = n_ref[0]
    rows, dk, w = s_ref.shape[-3:]

    @pl.when(i < n_live)
    def _():
        kt, qt = kt_ref[0], qt_ref[0]                        # [d_k, H]
        lane = jax.lax.broadcasted_iota(jnp.int32, (1, w), 1)

        def columns(xt, r):
            """The ``pack`` heads' columns of row block ``r``, each over
            its own head's lanes: ``[d_k, W]``."""
            out = jnp.broadcast_to(xt[:, r * pack:r * pack + 1], (dk, w))
            for e in range(1, pack):
                h = r * pack + e
                out = jnp.where(lane >= e * d_v, xt[:, h:h + 1], out)
            return out

        for r in range(rows):
            kcol = columns(kt, r)
            sp = s_ref[0, 0, r] * decay_ref[0, r:r + 1, :]
            read = jnp.sum(sp * kcol, axis=0, keepdims=True)
            u = beta_ref[0, r:r + 1, :] * (v_ref[0, r:r + 1, :] - read)
            new = sp + kcol * u
            s_out_ref[0, 0, r] = new
            o_ref[0, r:r + 1, :] = jnp.sum(new * columns(qt, r), axis=0,
                                           keepdims=True)

    # Nobody alive: the one block this call maps is written back as it
    # came (a grid step past the live ones maps the last live slot's
    # block again and leaves it alone).
    @pl.when((n_live == 0) & (i == 0))
    def _():
        s_out_ref[...] = s_ref[...]
        o_ref[...] = jnp.zeros_like(o_ref)


@functools.partial(jax.jit, static_argnames=("layer", "interpret"),
                   inline=True)
def _pallas_step(state, q, k, v, g, beta, alive, layer, interpret: bool):
    whole = state if layer is not None else state[None]
    at = layer or 0
    slots, rows, dk, w = whole.shape[1:]
    h, d_v = v.shape[1:]
    pack = h // rows
    # Live slots first; the steps past them repeat the last live one, so
    # their blocks are neither fetched nor written again.
    order = jnp.argsort(~alive, stable=True).astype(jnp.int32)
    n_live = jnp.sum(alive).astype(jnp.int32)
    ids = jnp.where(jnp.arange(slots) < n_live, order,
                    order[jnp.maximum(n_live - 1, 0)])
    a_row = pl.BlockSpec((1, rows, w), lambda i, ids, n: (ids[i], 0, 0))
    a_col = pl.BlockSpec((1, dk, h), lambda i, ids, n: (ids[i], 0, 0))
    a_state = pl.BlockSpec((1, 1, rows, dk, w),
                           lambda i, ids, n: (at, ids[i], 0, 0, 0))
    columns = lambda x: x.astype(jnp.float32).transpose(0, 2, 1)
    o, whole = pl.pallas_call(
        functools.partial(_step_kernel, pack=pack, d_v=d_v),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2, grid=(slots,),
            in_specs=[a_state, a_row, a_row, a_row, a_col, a_col],
            out_specs=[a_row, a_state]),
        out_shape=[jax.ShapeDtypeStruct((slots, rows, w), jnp.float32),
                   jax.ShapeDtypeStruct(whole.shape, whole.dtype)],
        # The store goes in and comes out as ONE buffer: only the live
        # slots' blocks of this layer are touched.
        input_output_aliases={2: 1},
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",),
            vmem_limit_bytes=48 << 20),
        interpret=interpret, name="gdn_step",
    )(ids, n_live.reshape(1), whole,
      _lane_rows(jnp.exp(g), whole.shape), _lane_rows(beta, whole.shape),
      _lane_rows(v, whole.shape), columns(k), columns(q))
    return o.reshape(slots, h, d_v), (whole if layer is not None
                                      else whole[0])


def gated_delta_step(state, q, k, v, g, beta, alive, *, layer=None,
                     interpret=None):
    """One step for every slot alive.  ``state [slots, R, d_k, W]`` float32
    in the kept layout, or with ``layer`` given the whole store ``[layers,
    slots, R, d_k, W]`` of which that layer's rows are updated; ``q``/``k``
    ``[slots, H, d_k]`` (``k`` of unit length, ``q`` scaled); ``v [slots,
    H, d_v]``; ``g``/``beta`` ``[slots, H]`` float32 (the decay's
    logarithm; the write strength); ``alive [slots]`` bool.  Returns ``(o
    [slots, H, d_v] float32 = S_t^T q, state)``: the state given, with the
    live slots' rows advanced and every other row BIT FOR BIT what it was
    (on the TPU the kernel's output IS its input buffer, and an idle
    slot's block is never moved; an idle slot's ``o`` is zero)."""
    g, beta = g.astype(jnp.float32), beta.astype(jnp.float32)
    if kernel_runs(interpret):
        o, state = _pallas_step(state, q, k, v, g, beta, alive, layer,
                                bool(interpret))
    else:
        o, state = gated_delta_step_jnp(state, q, k, v, g, beta, alive,
                                        layer=layer)
    return jnp.where(alive[:, None, None], o, 0.0), state


# -- a prompt -----------------------------------------------------------------

def unit_lower_inverse(a):
    """``(I + A)^-1`` for ``A [n, n]`` float32 of which only the STRICT
    lower triangle is read, ``n`` a power of two: the 2 x 2 diagonal
    blocks are inverted by hand and pairs of inverted blocks ``P^-1``,
    ``Q^-1`` of ``[[P, 0], [B, Q]]`` joined as ``[[P^-1, 0], [-Q^-1 B
    P^-1, Q^-1]]``, every pair of a level in two ``[n, n]`` products under
    a mask: block forward substitution, and plain ``jnp`` that runs inside
    a kernel as it does outside."""
    n = a.shape[-1]
    row = jax.lax.broadcasted_iota(jnp.int32, (n, n), 0)
    col = jax.lax.broadcasted_iota(jnp.int32, (n, n), 1)
    dot = functools.partial(jnp.dot, precision=_EXACT,
                            preferred_element_type=jnp.float32)
    t = (row == col).astype(jnp.float32) - jnp.where(
        (row == col + 1) & ((row & 1) == 1), a, 0.0)
    b = 2
    while b < n:
        joins = (((row ^ col) < 2 * b) & ((row & b) != 0)
                 & ((col & b) == 0))
        t = t - dot(dot(t, jnp.where(joins, a, 0.0)), t)
        b *= 2
    return t


def _chunk_head(q, k, v, beta, c_col, c_row, s):
    """One chunk of one head (module docstring): ``q``/``k`` ``[C, d_k]``,
    ``v [C, d_v]``, ``beta``/``c_col`` ``[C, 1]`` and ``c_row [1, C]``
    float32 (``c`` the running sum of ``g`` inside the chunk), ``s [d_k,
    d_v]`` float32.  Returns ``(o [C, d_v] float32, s_out)``.  The
    kernel's body and the twin's: plain ``jnp``."""
    f, od = jnp.float32, q.dtype
    n = q.shape[0]
    row = jax.lax.broadcasted_iota(jnp.int32, (n, n), 0)
    col = jax.lax.broadcasted_iota(jnp.int32, (n, n), 1)
    causal = row >= col
    exact = functools.partial(jnp.dot, precision=_EXACT,
                              preferred_element_type=f)
    over_keys = lambda x: jax.lax.dot_general(
        x, k, (((1,), (1,)), ((), ())), preferred_element_type=f)
    m = jnp.where(causal, jnp.exp(jnp.where(causal, c_col - c_row, 0.0)),
                  0.0)
    t = unit_lower_inverse(beta * m * over_keys(k))
    grown = jnp.exp(c_col)
    kf = k.astype(f)
    u = exact(t, beta * (v.astype(f) - grown * exact(kf, s)))
    o = grown * exact(q.astype(f), s) + jnp.dot(
        (m * over_keys(q)).astype(od), u.astype(od),
        preferred_element_type=f)
    last = c_col[n - 1:n, :]
    # (The TPU has no broadcast over sublanes and lanes at once: the [1, 1]
    # goes down a column, and the exponential keeps the two apart.)
    kept = jnp.exp(jnp.broadcast_to(last, (s.shape[0], 1)))
    s = kept * s + jax.lax.dot_general(
        (kf * jnp.exp(last - c_col)).astype(od), u.astype(od),
        (((0,), (0,)), ((), ())), preferred_element_type=f)
    return o, s


def _chunked(q, k, v, g, beta, n_valid, chunk: int):
    """``(c, n, q, k, v, beta, cum)`` with heads leading and the time axis
    padded to ``n`` whole chunks of ``c`` steps: ``q``/``k`` ``[H, n * c,
    d_k]``, ``v [H, n * c, d_v]``, ``beta``/``cum`` ``[H, n * c]``;
    ``beta`` and ``g`` zero at and past ``n_valid`` (such a step decays by
    ``exp(0)`` and writes nothing: the state stays bit for bit), ``cum``
    the running sum of ``g`` inside each chunk."""
    t = q.shape[0]
    if chunk & (chunk - 1):
        raise ValueError(f"the chunk length {chunk} is not a power of two")
    c = min(chunk, max(8, 1 << (t - 1).bit_length()))
    live = jnp.arange(t)[:, None] < n_valid
    g, beta = jnp.where(live, g, 0.0), jnp.where(live, beta, 0.0)
    q, k, v, g, beta = (
        jnp.moveaxis(jnp.pad(x, ((0, -t % c),) + ((0, 0),) * (x.ndim - 1)),
                     1, 0) for x in (q, k, v, g, beta))
    h, tp = g.shape
    cum = jnp.cumsum(g.reshape(h, -1, c), axis=-1).reshape(h, tp)
    return c, tp // c, q, k, v, beta, cum


def gated_delta_chunk_scan_jnp(q, k, v, g, beta, s0, n_valid, *,
                               chunk: int = 64):
    """:func:`gated_delta_chunk_scan` in plain ``jnp``: the same chunked
    equations a head at a time (``vmap``), a ``lax.scan`` over the
    chunks."""
    t, h, dv = v.shape
    c, n, q, k, v, beta, cum = _chunked(q, k, v, g, beta, n_valid, chunk)
    heads = jax.vmap(_chunk_head)

    def one(s, inp):
        qc, kc, vc, bc, cc = inp
        o, s = heads(qc, kc, vc, bc[..., None], cc[..., None],
                     cc[:, None, :], s)
        return s, o

    split = lambda x: jnp.moveaxis(x.reshape(h, n, c, *x.shape[2:]), 1, 0)
    s_out, o = jax.lax.scan(one, unpack_state(s0, dv), (
        split(q), split(k), split(v), split(beta), split(cum)))
    return (jnp.moveaxis(o, 1, 0).reshape(h, n * c, dv).transpose(1, 0, 2)[:t],
            pack_state(s_out))


def _chunk_kernel(nv_ref, q_ref, k_ref, v_ref, cols_ref, rows_ref, s0_ref,
                  o_ref, s_out_ref, s_scr, *, c: int):
    chunk = pl.program_id(1)

    @pl.when(chunk == 0)
    def _():
        s_scr[...] = s0_ref[...]

    @pl.when(chunk * c >= nv_ref[0])
    def _():
        o_ref[...] = jnp.zeros_like(o_ref)

    @pl.when(chunk * c < nv_ref[0])
    def _():
        for j in range(s_scr.shape[0]):
            cols = cols_ref[j]                               # [C, 2]
            o_ref[j], s_scr[j] = _chunk_head(
                q_ref[j], k_ref[j], v_ref[j], cols[:, 0:1], cols[:, 1:2],
                rows_ref[j, 0], s_scr[j])

    s_out_ref[...] = s_scr[...]


@functools.partial(jax.jit, static_argnames=("chunk", "interpret"),
                   inline=True)
def _pallas_chunk_scan(q, k, v, g, beta, s0, n_valid, chunk: int,
                       interpret: bool):
    t, h, dv = v.shape
    dk = q.shape[-1]
    c, n, q, k, v, beta, cum = _chunked(q, k, v, g, beta, n_valid, chunk)
    # Heads a grid cell: HEAD_BLOCK, or the most below it that divide H.
    hb = max(d for d in range(1, HEAD_BLOCK + 1) if h % d == 0)
    # A head's beta and running sum as columns of its steps, and the sum
    # as a row of each chunk besides.
    cols = jnp.stack([beta, cum], axis=-1)                    # [H, tp, 2]
    rows = cum.reshape(h, n, 1, c)
    steps = lambda w: pl.BlockSpec((hb, c, w), lambda b, ch, nv: (b, ch, 0))
    st = pl.BlockSpec((hb, dk, dv), lambda b, ch, nv: (b, 0, 0))
    o, s_out = pl.pallas_call(
        functools.partial(_chunk_kernel, c=c),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1, grid=(h // hb, n),
            in_specs=[steps(dk), steps(dk), steps(dv), steps(2),
                      pl.BlockSpec((hb, 1, 1, c),
                                   lambda b, ch, nv: (b, ch, 0, 0)), st],
            out_specs=[steps(dv), st],
            scratch_shapes=[pltpu.VMEM((hb, dk, dv), jnp.float32)]),
        out_shape=[jax.ShapeDtypeStruct((h, n * c, dv), jnp.float32),
                   jax.ShapeDtypeStruct((h, dk, dv), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary"),
            vmem_limit_bytes=48 << 20),
        interpret=interpret, name="gdn_chunk_scan",
    )(jnp.asarray(n_valid, jnp.int32).reshape(1), q, k, v, cols, rows,
      unpack_state(s0, dv))
    return o.transpose(1, 0, 2)[:t], pack_state(s_out)


def gated_delta_chunk_scan(q, k, v, g, beta, s0, n_valid, *,
                           chunk: int = 64, interpret=None):
    """A prompt's recurrence in chunks.  ``q``/``k`` ``[t, H, d_k]`` (``k``
    of unit length, ``q`` scaled; their type is the products' operand
    type) and ``v [t, H, d_v]`` in that type, ``g``/``beta`` ``[t, H]``
    float32, ``s0 [H / pack, d_k, pack * d_v]`` float32 in the kept
    layout, ``n_valid``: steps at and past it (a bucket's padding) leave
    the state alone.  Returns ``(o [t, H, d_v] float32 = S_t^T q_t,
    s_out)``: the state after step ``n_valid - 1``; rows of ``o`` at and
    past ``n_valid`` mean nothing.  On the TPU the Pallas kernel (grid:
    blocks of heads by chunks, the block's state in VMEM across its
    chunks, chunks wholly past ``n_valid`` skipped); elsewhere
    :func:`gated_delta_chunk_scan_jnp`, unless ``interpret=True`` asks for
    the kernel in the interpreter (its own tests)."""
    g, beta, s0 = (x.astype(jnp.float32) for x in (g, beta, s0))
    k, v = k.astype(q.dtype), v.astype(q.dtype)
    if not kernel_runs(interpret):
        return gated_delta_chunk_scan_jnp(q, k, v, g, beta, s0, n_valid,
                                          chunk=chunk)
    return _pallas_chunk_scan(q, k, v, g, beta, s0, n_valid, chunk,
                              bool(interpret))
