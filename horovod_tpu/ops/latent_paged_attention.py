"""Latent attention over the paged store where it lies: one query a slot
against the cached entries of the slots ALIVE, up to each slot's own
length, the softmax computed online.

    store   [cache layers, pages, page, width]   one entry a token a layer
    table   [slots, pages a slot]                a slot's pages in order
    lengths [slots]                              entries cached; -1 idle

A latent entry is key AND value: the scores contract whole ``width``-wide
rows against the absorbed, padded query, the output takes the rows' first
``kv_rank`` values (``models/latent_moe.py::mla_absorbed_attention``, whose
arithmetic this is: scores, softmax and accumulation float32, the
probabilities rounded to the store's type as a product's operand).

The kernel (:func:`latent_paged_attention`) walks the page table: grid step
``i`` is the ``i``-th live slot (live slots first, their ids by scalar
prefetch), the store stays in HBM and the slot's pages are copied, a page
a transfer, into one of two VMEM blocks of :func:`block_pages` pages while
the other is computed on; the first block of the NEXT live slot is under
way before the last of this one is computed.  Pages past ``ceil(length /
page)`` are never copied, blocks past the length never computed, an idle
slot costs one grid step that writes its zeros.  The new token's own entry
(the engine scatters it into the store after the program) joins from its
operand as the softmax's first column.  Nothing is gathered, sorted or
relaid, and no slice of the store is made: the kernel takes the WHOLE store
and a ``layer`` that may be traced (``ops/ssd.py::ssd_step`` likewise).

It runs on the TPU, or wherever a test asks for it by name
(``interpret=True``); elsewhere the caller keeps its own twin
(``latent_moe.gathered_attend``: every slot's table row gathered whole):
:func:`kernel_runs` (``ops/flash_attention.py``) is the rule.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from .flash_attention import kernel_runs   # the rule a caller asks

# The two blocks a slot's pages are copied into may take this much VMEM.
BLOCK_VMEM_BYTES = 2 << 20
# A block's scores ``[heads, block]`` float32 stay within this many bytes:
# 512 tokens at 64 heads.  (On the v5e, a decode step of the
# `longcat-serve-turns` shapes took 3.2 / 2.1 / 1.7 / 1.4 ms at blocks of
# 64 / 128 / 256 / 512 tokens: a block's fixed cost outweighs the columns
# a longer block masks.)
SCORE_TILE_BYTES = 128 << 10


def block_pages(page_size: int, pages_per_slot: int, heads: int,
                width: int, itemsize: int) -> int:
    """Pages of one VMEM block, from the shapes alone: as many tokens as
    keep a block's float32 scores in ``SCORE_TILE_BYTES`` and the two
    blocks in ``BLOCK_VMEM_BYTES``, whole pages, no more than a slot
    has."""
    tokens = min(SCORE_TILE_BYTES // (4 * heads),
                 BLOCK_VMEM_BYTES // (2 * width * itemsize))
    return max(1, min(tokens // page_size, pages_per_slot))


def tokens_read(lengths, page_size: int) -> int:
    """Tokens the kernel copies out of the store in ONE cache layer at
    these (host) lengths: every live slot's length rounded up to the
    page, the unit of a copy; idle slots (``< 0``) nothing."""
    lengths = np.asarray(lengths)
    live = lengths[lengths >= 0]
    return int((-(-live // page_size) * page_size).sum())


def live_first(lengths):
    """``(order [slots], n_live [1])``: the slots with the live ones
    first, both parts in slot order, and their count.  A comparison and a
    sum, no sort: a slot's place is the count of its kind before it."""
    alive = lengths >= 0
    n_live = jnp.sum(alive).astype(jnp.int32)
    place = jnp.where(alive, jnp.cumsum(alive) - 1,
                      n_live + jnp.cumsum(~alive) - 1).astype(jnp.int32)
    slot = jnp.arange(lengths.shape[0], dtype=jnp.int32)
    order = jnp.sum(jnp.where(place[None, :] == slot[:, None],
                              slot[None, :], 0), axis=1).astype(jnp.int32)
    return order, n_live.reshape(1)


def _kernel(order_ref, n_ref, len_ref, table_ref, layer_ref,   # scalars
            q_ref, e_ref, store_ref, o_ref,
            buf, sem, m_scr, l_scr, acc_scr, par, *,
            scale: float, kv_rank: int, page: int, bp: int, pps: int):
    i = pl.program_id(0)
    n_live = n_ref[0]
    layer = layer_ref[0]
    block = bp * page
    od = buf.dtype

    def pages_of(slot, j):
        """Pages of block ``j`` of ``slot`` that hold cached entries."""
        total = (len_ref[slot] + page - 1) // page
        return jnp.clip(total - j * bp, 0, bp)

    def copy(slot, j, b, k):
        return pltpu.make_async_copy(
            store_ref.at[layer, table_ref[slot * pps + j * bp + k]],
            buf.at[b, pl.ds(k * page, page)], sem.at[b])

    def each_page(slot, j, b, do):
        def one(k, carry):
            do(copy(slot, j, b, k))
            return carry
        jax.lax.fori_loop(0, pages_of(slot, j), one, 0)

    def fetch(slot, j, b):
        each_page(slot, j, b, lambda page_copy: page_copy.start())

    def wait(slot, j, b):
        each_page(slot, j, b, lambda page_copy: page_copy.wait())

    def blocks_of(slot):
        return (len_ref[slot] + block - 1) // block

    @pl.when(i == 0)
    def _():
        # Rows of a block past a slot's pages keep what was there before:
        # masked out of the scores, but a product's operand all the same.
        buf[...] = jnp.zeros_like(buf)
        par[0] = 0

    @pl.when((i == 0) & (n_live > 0))
    def _():
        fetch(order_ref[0], 0, 0)

    @pl.when(i >= n_live)
    def _():
        o_ref[...] = jnp.zeros_like(o_ref)

    @pl.when(i < n_live)
    def _():
        slot = order_ref[i]
        length = len_ref[slot]
        nb = blocks_of(slot)
        has_next = i + 1 < n_live
        nxt = order_ref[jnp.minimum(i + 1, pl.num_programs(0) - 1)]
        q = q_ref[0]                                           # [heads, w]
        e = e_ref[0]                                           # [1, w]
        # The new token's own entry is the first column: weight 1.
        m_scr[...] = jnp.sum(q.astype(jnp.float32) * e.astype(jnp.float32),
                             axis=1, keepdims=True) * scale
        l_scr[...] = jnp.ones_like(l_scr)
        acc_scr[...] = jnp.broadcast_to(
            e[:, :kv_rank].astype(jnp.float32), acc_scr.shape)

        # Nothing of this slot to wait for: the next one's first block
        # goes where the next wait will look.
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

            rows = buf[b]                                      # [block, w]
            s = jax.lax.dot_general(
                q, rows, (((1,), (1,)), ((), ())),
                preferred_element_type=jnp.float32) * scale    # [heads, block]
            at = j * block + jax.lax.broadcasted_iota(jnp.int32, s.shape, 1)
            s = jnp.where(at < length, s, -jnp.inf)
            m_old = m_scr[...]
            m_new = jnp.maximum(m_old, jnp.max(s, axis=1, keepdims=True))
            alpha = jnp.exp(m_old - m_new)
            p = jnp.exp(s - m_new)
            l_scr[...] = alpha * l_scr[...] + jnp.sum(p, axis=1,
                                                      keepdims=True)
            acc_scr[...] = alpha * acc_scr[...] + jnp.dot(
                p.astype(od), buf[b, :, :kv_rank],
                preferred_element_type=jnp.float32)
            m_scr[...] = m_new
            par[0] = 1 - b
            return carry

        jax.lax.fori_loop(0, nb, one, 0)
        o_ref[0] = (acc_scr[...] / l_scr[...]).astype(o_ref.dtype)


@functools.partial(jax.jit, static_argnames=("scale", "kv_rank",
                                             "interpret"), inline=True)
def _pallas_attend(q, entry, store, table, lengths, layer, order, n_live,
                   scale: float, kv_rank: int, interpret: bool):
    slots, heads, width = q.shape
    page = store.shape[2]
    pps = table.shape[1]
    bp = block_pages(page, pps, heads, width, store.dtype.itemsize)

    def live(i, order, n, *_):
        # A step past the live ones maps the last live slot's block again.
        return (order[jnp.minimum(i, jnp.maximum(n[0] - 1, 0))], 0, 0)

    return pl.pallas_call(
        functools.partial(_kernel, scale=scale, kv_rank=kv_rank, page=page,
                          bp=bp, pps=pps),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=5, grid=(slots,),
            in_specs=[pl.BlockSpec((1, heads, width), live),
                      pl.BlockSpec((1, 1, width), live),
                      pl.BlockSpec(memory_space=pl.ANY)],
            out_specs=pl.BlockSpec((1, heads, kv_rank),
                                   lambda i, order, *_: (order[i], 0, 0)),
            scratch_shapes=[
                pltpu.VMEM((2, bp * page, width), store.dtype),
                pltpu.SemaphoreType.DMA((2,)),
                pltpu.VMEM((heads, 1), jnp.float32),
                pltpu.VMEM((heads, 1), jnp.float32),
                pltpu.VMEM((heads, kv_rank), jnp.float32),
                pltpu.SMEM((1,), jnp.int32)]),
        out_shape=jax.ShapeDtypeStruct((slots, heads, kv_rank), q.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",)),
        interpret=interpret, name="latent_paged_attn",
    )(order, n_live, lengths.astype(jnp.int32),
      table.reshape(-1).astype(jnp.int32),
      jnp.asarray(layer, jnp.int32).reshape(1), q, entry[:, None, :], store)


def latent_paged_attention(q, entry, store, table, lengths, layer, *,
                           scale: float, kv_rank: int, order=None,
                           n_live=None, interpret=None):
    """One query a slot over the slot's cached entries and its new one.

    ``q [slots, heads, width]``: the absorbed query, padded like an entry;
    ``entry [slots, width]``: the new token's entry (position
    ``lengths[slot]``, not in the store yet); ``store [cache layers,
    pages, page, width]`` of which layer ``layer`` (an int or a traced
    scalar) is read; ``table [slots, pages a slot]``; ``lengths [slots]``
    (-1 idle).  ``order``/``n_live``: :func:`live_first` of ``lengths``,
    for a caller that attends several layers at the same lengths.
    Returns ``[slots, heads, kv_rank]`` in ``q``'s type: ``softmax(q .
    rows * scale)`` over the rows at positions ``0..lengths[slot]``,
    times their first ``kv_rank`` values; an idle slot's rows are exact
    zeros.  Only pages ``table[slot, :ceil(length / page)]`` of the live
    slots are read; their rows past the length are masked, and must be
    finite."""
    if order is None:
        order, n_live = live_first(lengths)
    return _pallas_attend(q, entry, store, table, lengths, layer, order,
                          n_live, float(scale), int(kv_rank),
                          bool(interpret))
