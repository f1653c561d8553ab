"""Learned sparse attention over the paged latent store: the three steps a
decode iteration adds to ``ops/latent_paged_attention.py`` when a lightning
indexer chooses, per query, which cached tokens the latent attention may
read (``models/latent_moe.py``, docs/inference.md "Learned sparse attention
over the latent store").

    store   [cache layers, pages, page, width]   latent entries
    keys    [cache layers, pages, page, dim]     the indexer's keys
    table   [slots, pages a slot]                both stores' ONE page table
    lengths [slots]                              entries cached; -1 idle

* :func:`index_paged_scores` (kernel ``dsa_index_score``): ``I[slot, s] =
  sum_j w[slot, j] * relu(q[slot, j] . keys[s])`` for the cached positions
  ``s < lengths[slot]`` of the live slots, the keys read where they lie:
  the page walk of ``latent_paged_attention`` (live slots first, two VMEM
  blocks, a page a transfer), 256 B a token.
* :func:`select_paged` (kernel ``dsa_select``) and :func:`topk_mask`: the
  ``k`` largest of a row, exactly: the ``k``-th largest found by
  bisection over the float's bits (32 counting passes, no sort), ties at
  it broken towards the lower index as ``jax.lax.top_k`` breaks them.
  The kernel takes the decode's rows, one slot a grid step; ``topk_mask``
  is the same in XLA, for a prompt's blocks of queries, under the
  caller's ``jax.named_scope("dsa_select")``.
* :func:`sparse_paged_attention` (kernel ``dsa_sparse_attn``): the
  absorbed latent attention of ``latent_paged_attention`` with scores
  outside the selection at ``-inf``.  It copies the live slots' WHOLE
  pages and masks: selected rows lie scattered over the pages, a row is
  1280 B, and on the v5e a transfer a row costs more than the page around
  it at the contexts this was measured at (PERF.md section 6, PR 49 has
  both readings).

They run on the TPU, or wherever a test asks by name (``interpret=True``);
elsewhere the caller keeps its plain twin (gather the row, score,
``top_k``, mask): ``kernel_runs`` (``ops/flash_attention.py``) is the rule.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from .latent_paged_attention import block_pages, live_first

# Tokens of one VMEM block of indexer keys (256 B a token: two blocks are
# 256 KB, a block's per-head scores [heads, block] float32 128 KB at 64).
INDEX_BLOCK_TOKENS = 512


# -- selection ----------------------------------------------------------------

def _ordered_bits(x):
    """float32 -> uint32 whose unsigned order is the floats' order
    (``-inf`` lowest; no NaN comes in)."""
    bits = jax.lax.bitcast_convert_type(x.astype(jnp.float32), jnp.uint32)
    return jnp.where(bits >> 31 == 1, ~bits, bits | jnp.uint32(1 << 31))


def topk_mask(scores, valid, k: int):
    """``[..., n]`` bool: the ``min(k, valid entries)`` largest ``scores``
    among the ``valid`` entries of each row, ties broken towards the
    lower index: what ``jax.lax.top_k`` over the valid entries chooses,
    without a sort.  The ``k``-th largest value is built bit by bit from
    the top (the largest threshold that at least ``k`` entries reach: 32
    counting passes over the row)."""
    u = jnp.where(valid, _ordered_bits(scores), jnp.uint32(0))
    # A valid -inf maps to 0x007fffff > 0, so 0 marks the invalid alone.

    def bit(i, t):
        cand = t | (jnp.uint32(1) << (jnp.uint32(31) - i.astype(jnp.uint32)))
        n = jnp.sum(u >= cand[..., None], axis=-1, dtype=jnp.int32)
        return jnp.where(n >= k, cand, t)

    t = jax.lax.fori_loop(0, 32, bit, jnp.zeros(u.shape[:-1], jnp.uint32))
    t = t[..., None]
    above = valid & (u > t)
    at = valid & (u == t)
    room = k - jnp.sum(above, axis=-1, keepdims=True, dtype=jnp.int32)
    # Rows with fewer valid entries than k end at t = 0 with room to
    # spare: every valid entry is kept.  Ties past the room are dropped
    # from the higher index down; the cumulative count is only computed
    # when some row has such ties.
    surplus = jnp.sum(at, axis=-1, keepdims=True, dtype=jnp.int32) > room
    at = jax.lax.cond(
        jnp.any(surplus),
        lambda: at & (jnp.cumsum(at, axis=-1, dtype=jnp.int32) <= room),
        lambda: at)
    return above | at


def _select_kernel(len_ref, s_ref, o_ref, *, k: int, block: int):
    length = len_ref[pl.program_id(0)]

    @pl.when(length < 0)
    def _():
        o_ref[...] = jnp.zeros_like(o_ref)

    @pl.when(length >= 0)
    def _():
        x = s_ref[0]                                         # [rows, block]
        at = (jax.lax.broadcasted_iota(jnp.int32, x.shape, 0) * block
              + jax.lax.broadcasted_iota(jnp.int32, x.shape, 1))
        valid = at <= length
        bits = jax.lax.bitcast_convert_type(x, jnp.int32)
        # Signed keys in the floats' order; the invalid lowest of all.
        low = jnp.int32(-2 ** 31)
        key = jnp.where(valid, bits ^ ((bits >> 31) & jnp.int32(2 ** 31 - 1)),
                        low)

        def count(mask):
            n = jnp.sum(mask.astype(jnp.int32), axis=1, keepdims=True)
            return jnp.sum(n, axis=0, keepdims=True)          # [1, 1]

        def bit(i, t):
            # The k-th largest key, bit by bit from the top, as an
            # unsigned number offset by 2^31 (int32 wraps as it must).
            cand = t + (jnp.int32(1) << (31 - i))
            return jnp.where(count(key >= cand) >= k, cand, t)

        t = jax.lax.fori_loop(0, 32, bit, jnp.full((1, 1), low))
        above = valid & (key > t)
        ties = valid & (key == t)
        room = k - count(above)

        def bound(i, j):
            # The largest position bound that leaves no more ties than
            # there is room for: ties go to the lower index.
            cand = j | (jnp.int32(1) << (30 - i))
            return jnp.where(count(ties & (at < cand)) <= room, cand, j)

        j = jax.lax.fori_loop(0, 31, bound, jnp.zeros((1, 1), jnp.int32))
        o_ref[0] = (above | (ties & (at < j))).astype(o_ref.dtype)


@functools.partial(jax.jit, static_argnames=("k", "interpret"), inline=True)
def _pallas_select(scores, lengths, k: int, interpret: bool):
    slots, cap = scores.shape
    block = 512 if cap % 512 == 0 else 128
    rows = -(-cap // block)
    x = jnp.pad(scores, ((0, 0), (0, rows * block - cap))
                ).reshape(slots, rows, block)
    out = pl.pallas_call(
        functools.partial(_select_kernel, k=k, block=block),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1, grid=(slots,),
            in_specs=[pl.BlockSpec((1, rows, block),
                                   lambda i, *_: (i, 0, 0))],
            out_specs=pl.BlockSpec((1, rows, block),
                                   lambda i, *_: (i, 0, 0))),
        out_shape=jax.ShapeDtypeStruct((slots, rows, block), jnp.float32),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",)),
        interpret=interpret, name="dsa_select",
    )(lengths.astype(jnp.int32), x)
    return out.reshape(slots, -1)[:, :cap]


def select_paged(scores, lengths, k: int, *, interpret=None):
    """:func:`topk_mask` for the decode's rows, as a kernel (``dsa_select``:
    a row stays in VMEM through its 63 counting passes, and the device
    trace can name it): ``scores [slots, positions]`` float32, of which a
    slot's positions ``0..lengths[slot]`` are valid (the new token's own
    included; -1 idle: nothing is).  Returns ``[slots, positions]``
    float32, 1 at the ``min(k, lengths + 1)`` selected positions, 0
    elsewhere."""
    return _pallas_select(scores, lengths, int(k), bool(interpret))


# -- the page walk both kernels share -------------------------------------------

def _walker(store_ref, buf, sem, len_ref, table_ref, layer, *, page: int,
            bp: int, pps: int):
    """``(fetch, wait)`` of block ``j`` of ``slot`` into buffer ``b``: one
    transfer a page that holds cached entries, none past them."""

    def pages_of(slot, j):
        total = (len_ref[slot] + page - 1) // page
        return jnp.clip(total - j * bp, 0, bp)

    def each_page(slot, j, b, do):
        def one(k, carry):
            do(pltpu.make_async_copy(
                store_ref.at[layer, table_ref[slot * pps + j * bp + k]],
                buf.at[b, pl.ds(k * page, page)], sem.at[b]))
            return carry
        jax.lax.fori_loop(0, pages_of(slot, j), one, 0)

    def fetch(slot, j, b):
        each_page(slot, j, b, lambda c: c.start())

    def wait(slot, j, b):
        each_page(slot, j, b, lambda c: c.wait())

    return fetch, wait


def _walk(i, n_live, order_ref, len_ref, par, buf, block: int, fetch, wait,
          compute):
    """Grid step ``i`` of a kernel that takes the live slots in order:
    ``compute(j, b, slot, length)`` for every block ``j`` of slot
    ``order[i]`` that holds cached entries, the next block (this slot's
    or the next live slot's first) under way meanwhile.  ``par`` keeps the
    buffer the next wait looks in, across grid steps."""

    @pl.when(i == 0)
    def _():
        # Rows of a block past a slot's pages keep what was there before:
        # masked out of the scores, but a product's operand all the same.
        buf[...] = jnp.zeros_like(buf)
        par[0] = 0

    @pl.when((i == 0) & (n_live > 0))
    def _():
        fetch(order_ref[0], 0, 0)

    @pl.when(i < n_live)
    def _():
        slot = order_ref[i]
        length = len_ref[slot]
        nb = (length + block - 1) // block
        has_next = i + 1 < n_live
        nxt = order_ref[jnp.minimum(i + 1, pl.num_programs(0) - 1)]

        @pl.when((nb == 0) & has_next)
        def _():
            fetch(nxt, 0, par[0])

        def one(j, carry):
            b = par[0]
            wait(slot, j, b)

            @pl.when(j + 1 < nb)
            def _():
                fetch(slot, j + 1, 1 - b)

            @pl.when((j + 1 == nb) & has_next)
            def _():
                fetch(nxt, 0, 1 - b)

            compute(j, b, slot, length)
            par[0] = 1 - b
            return carry

        jax.lax.fori_loop(0, nb, one, 0)


def _live_block(i, order, n, *_):
    # A step past the live ones maps the last live slot's block again.
    return (order[jnp.minimum(i, jnp.maximum(n[0] - 1, 0))], 0, 0)


# -- index scores ---------------------------------------------------------------

def _score_kernel(order_ref, n_ref, len_ref, table_ref, layer_ref,
                  q_ref, w_ref, keys_ref, o_ref, buf, sem, par, *,
                  page: int, bp: int, pps: int):
    i = pl.program_id(0)
    n_live = n_ref[0]
    block = bp * page
    fetch, wait = _walker(keys_ref, buf, sem, len_ref, table_ref,
                          layer_ref[0], page=page, bp=bp, pps=pps)
    # Blocks past the length, and an idle slot's row, hold -inf.
    o_ref[...] = jnp.full(o_ref.shape, -jnp.inf, o_ref.dtype)

    def compute(j, b, slot, length):
        s = jax.lax.dot_general(
            q_ref[0], buf[b], (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32)            # [heads, block]
        total = jnp.sum(jnp.maximum(s, 0.0) * w_ref[0], axis=0,
                        keepdims=True)                      # [1, block]
        at = j * block + jax.lax.broadcasted_iota(jnp.int32, total.shape, 1)
        o_ref[0, pl.ds(j, 1), :] = jnp.where(at < length, total, -jnp.inf)

    _walk(i, n_live, order_ref, len_ref, par, buf, block, fetch, wait,
          compute)


@functools.partial(jax.jit, static_argnames=("interpret",), inline=True)
def _pallas_scores(q, w, keys, table, lengths, layer, order, n_live,
                   interpret: bool):
    slots, heads, dim = q.shape
    page = keys.shape[2]
    pps = table.shape[1]
    bp = max(1, min(INDEX_BLOCK_TOKENS // page, pps))
    nb = -(-pps // bp)
    out = pl.pallas_call(
        functools.partial(_score_kernel, page=page, bp=bp, pps=pps),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=5, grid=(slots,),
            in_specs=[pl.BlockSpec((1, heads, dim), _live_block),
                      pl.BlockSpec((1, heads, 1), _live_block),
                      pl.BlockSpec(memory_space=pl.ANY)],
            out_specs=pl.BlockSpec((1, nb, bp * page),
                                   lambda i, order, *_: (order[i], 0, 0)),
            scratch_shapes=[
                pltpu.VMEM((2, bp * page, dim), keys.dtype),
                pltpu.SemaphoreType.DMA((2,)),
                pltpu.SMEM((1,), jnp.int32)]),
        out_shape=jax.ShapeDtypeStruct((slots, nb, bp * page), jnp.float32),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",)),
        interpret=interpret, name="dsa_index_score",
    )(order, n_live, lengths.astype(jnp.int32),
      table.reshape(-1).astype(jnp.int32),
      jnp.asarray(layer, jnp.int32).reshape(1), q,
      w.astype(jnp.float32)[:, :, None], keys)
    return out.reshape(slots, -1)[:, :pps * page]


def index_paged_scores(q, w, keys, table, lengths, layer, *, order=None,
                       n_live=None, interpret=None):
    """The indexer's scores of one query a slot against the slot's cached
    keys.  ``q [slots, heads, dim]`` (rotated, the keys' type); ``w
    [slots, heads]`` float32; ``keys [cache layers, pages, page, dim]`` of
    which layer ``layer`` is read; ``table [slots, pages a slot]``;
    ``lengths [slots]`` (-1 idle).  Returns ``[slots, pages a slot * page]``
    float32: ``sum_j w_j relu(q_j . key_s)`` at column ``s <
    lengths[slot]``, ``-inf`` at every other (the new token's own column
    is the caller's: its key is not in the store yet)."""
    if order is None:
        order, n_live = live_first(lengths)
    return _pallas_scores(q, w, keys, table, lengths, layer, order, n_live,
                          bool(interpret))


# -- attention over the selection ------------------------------------------------

def _attn_kernel(order_ref, n_ref, len_ref, table_ref, layer_ref, own_ref,
                 q_ref, e_ref, sel_ref, store_ref, o_ref,
                 buf, sem, m_scr, l_scr, acc_scr, par, *,
                 scale: float, kv_rank: int, page: int, bp: int, pps: int):
    i = pl.program_id(0)
    n_live = n_ref[0]
    block = bp * page
    od = buf.dtype
    fetch, wait = _walker(store_ref, buf, sem, len_ref, table_ref,
                          layer_ref[0], page=page, bp=bp, pps=pps)

    @pl.when(i >= n_live)
    def _():
        o_ref[...] = jnp.zeros_like(o_ref)

    @pl.when(i < n_live)
    def _():
        # The new token's own entry is the first column where the
        # selection holds it (weight 1), else nothing is: a finite floor
        # under the running maximum keeps the first block's rescale 0.
        own = own_ref[order_ref[i]] > 0
        q = q_ref[0]
        e = e_ref[0]
        s_own = jnp.sum(q.astype(jnp.float32) * e.astype(jnp.float32),
                        axis=1, keepdims=True) * scale
        m_scr[...] = jnp.where(own, s_own, -1e30)
        l_scr[...] = jnp.where(own, 1.0, 0.0) * jnp.ones_like(l_scr)
        acc_scr[...] = jnp.where(own, 1.0, 0.0) * jnp.broadcast_to(
            e[:, :kv_rank].astype(jnp.float32), acc_scr.shape)

    def compute(j, b, slot, length):
        rows = buf[b]                                          # [block, w]
        s = jax.lax.dot_general(
            q_ref[0], rows, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32) * scale        # [heads, block]
        at = j * block + jax.lax.broadcasted_iota(jnp.int32, s.shape, 1)
        keep = (at < length) & (sel_ref[0, pl.ds(j, 1), :] > 0)
        s = jnp.where(keep, s, -jnp.inf)
        m_old = m_scr[...]
        m_new = jnp.maximum(m_old, jnp.max(s, axis=1, keepdims=True))
        alpha = jnp.exp(m_old - m_new)
        p = jnp.exp(s - m_new)
        l_scr[...] = alpha * l_scr[...] + jnp.sum(p, axis=1, keepdims=True)
        acc_scr[...] = alpha * acc_scr[...] + jnp.dot(
            p.astype(od), buf[b, :, :kv_rank],
            preferred_element_type=jnp.float32)
        m_scr[...] = m_new

    _walk(i, n_live, order_ref, len_ref, par, buf, block, fetch, wait,
          compute)

    @pl.when(i < n_live)
    def _():
        o_ref[0] = (acc_scr[...] / l_scr[...]).astype(o_ref.dtype)


@functools.partial(jax.jit, static_argnames=("scale", "kv_rank",
                                             "interpret"), inline=True)
def _pallas_attend(q, entry, store, table, lengths, layer, selected, order,
                   n_live, scale: float, kv_rank: int, interpret: bool):
    slots, heads, width = q.shape
    page = store.shape[2]
    pps = table.shape[1]
    bp = block_pages(page, pps, heads, width, store.dtype.itemsize)
    nb = -(-pps // bp)
    block = bp * page
    pos = jnp.clip(lengths, 0, None)
    selected = selected.astype(jnp.float32)
    own = jnp.take_along_axis(
        selected, jnp.minimum(pos, pps * page - 1)[:, None], axis=1)[:, 0]
    sel = jnp.pad(selected, ((0, 0), (0, nb * block - pps * page))
                  ).reshape(slots, nb, block)
    return pl.pallas_call(
        functools.partial(_attn_kernel, scale=scale, kv_rank=kv_rank,
                          page=page, bp=bp, pps=pps),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=6, grid=(slots,),
            in_specs=[pl.BlockSpec((1, heads, width), _live_block),
                      pl.BlockSpec((1, 1, width), _live_block),
                      pl.BlockSpec((1, nb, block), _live_block),
                      pl.BlockSpec(memory_space=pl.ANY)],
            out_specs=pl.BlockSpec((1, heads, kv_rank),
                                   lambda i, order, *_: (order[i], 0, 0)),
            scratch_shapes=[
                pltpu.VMEM((2, block, width), store.dtype),
                pltpu.SemaphoreType.DMA((2,)),
                pltpu.VMEM((heads, 1), jnp.float32),
                pltpu.VMEM((heads, 1), jnp.float32),
                pltpu.VMEM((heads, kv_rank), jnp.float32),
                pltpu.SMEM((1,), jnp.int32)]),
        out_shape=jax.ShapeDtypeStruct((slots, heads, kv_rank), q.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",)),
        interpret=interpret, name="dsa_sparse_attn",
    )(order, n_live, lengths.astype(jnp.int32),
      table.reshape(-1).astype(jnp.int32),
      jnp.asarray(layer, jnp.int32).reshape(1), own.astype(jnp.int32),
      q, entry[:, None, :], sel, store)


def sparse_paged_attention(q, entry, store, table, lengths, layer, selected,
                           *, scale: float, kv_rank: int, order=None,
                           n_live=None, interpret=None):
    """``latent_paged_attention`` over the SELECTED positions only.
    ``selected [slots, pages a slot * page]`` (bool, or 0/1 as
    :func:`select_paged` gives it): column ``s`` of a row says whether
    position ``s`` takes part, the new token's own position
    ``lengths[slot]`` included (its entry joins from ``entry``, not from
    the store); columns past it are ignored.  Every live slot selects at
    least one position.  The other arguments and the result are
    ``latent_paged_attention``'s."""
    if order is None:
        order, n_live = live_first(lengths)
    return _pallas_attend(q, entry, store, table, lengths, layer, selected,
                          order, n_live, float(scale), int(kv_rank),
                          bool(interpret))
