"""Grouped-query attention over a paged key/value store where it lies: one
query a slot against the cached rows of the slots ALIVE, through a page
table whose row may be a RING, the softmax computed online.

    k_pages, v_pages [layers of the group, pages, page, kv_heads * hd]
    table            [slots, entries]    a slot's pages; a ring, see below
    lengths          [slots]             positions cached; -1 idle

Query head ``i`` of ``heads`` reads key/value head ``i // (heads /
kv_heads)``, unless the caller hands in another HEAD MAP (differential
attention's pairs, ``models/hybrid_ssm.py``: :func:`gqa_paged_attention`).
The arithmetic is the twins' a caller keeps off the TPU
(``models/mamba2_hybrid.py::attend_view``): scores times ``scale``, scores,
softmax and accumulation float32, the probabilities rounded to the store's
type as the second product's operand, the new token's own key and value
(the engine writes them to the store after the program) joining from their
operands as the softmax's first column.

**The table row is a ring** (the cache manager's window group): with
``cached`` positions in the store, the entries in use are the first
``min(ceil(cached / page), entries)``; entry ``e`` holds logical page ``top
- (top - e) % entries`` with ``top = (cached - 1) // page``; a row at
position ``pos`` is attended iff ``pos < cached`` and, where ``window`` is
given, ``pos > cached - window``.  A table that holds every page of a slot
in order is the ring that never wraps (``window`` 0): ``entries`` is the
width of the table handed in, nothing else is asked.  Softmax does not care
in which order a ring's entries come, so they are read in table order and
only the mask knows the ring: column ``c`` of a slot's entries laid end to
end lies at position ``base + c``, less the ring's length from the entry
after the newest one on, two comparisons against scalars and no division
on the vector unit.

The kernel (:func:`gqa_paged_attention`) is ``ops/latent_paged_attention.
py``'s walk of the page table with two stores: grid step ``i`` is the
``i``-th live slot (``live_first``, by scalar prefetch), the stores stay in
HBM, a slot's pages are copied, a key page and a value page a transfer,
into one of two pairs of VMEM blocks of :func:`block_pages` pages while the
other pair is computed on; the first block of the NEXT live slot is under
way before the last of this one is computed; entries past the ones in use
are never copied, an idle slot costs one grid step that writes its zeros.
It takes the WHOLE stores and a ``layer`` that may be traced.

**Block-diagonal, not per key/value head.**  The queries go in laid as
``hybrid_ssm.attend_view`` lays them, ``[heads, kv_width]`` with a head's
``hd`` values in its key/value head's lanes and zeros elsewhere, so a
block's scores are ONE product ``[heads, kv_width] x [kv_width, block]``
against the key block as it is stored, the output ONE product ``[heads,
block] x [block, kv_width]``, and a head keeps its own head's lanes at the
end (the zeros add nothing; eight times the multiplications, which the
matrix unit has to spare in a decode).  Which lanes a query head is laid
into and which it keeps is all the kernel knows of the heads: the head map
lives in the wrapper.  The other form, ``kv_heads`` products ``[queries a
head padded to 8 rows, hd] x [hd, block]`` each against its own lanes and
as many for the output, loads the same key and value tiles into the matrix
unit and streams a sixth of the rows through them, with an accumulator of 8
vector registers instead of 48: on the v5e, at the shapes of
`trinity-serve-mixed` (48 heads on 8 of 128, 8 of 64 slots alive, 131
thousand token-layers, five calls), it ran SLOWER at every block up to 512
tokens: 2.81 / 1.65 / 1.63 / 1.08 / 0.88 ms at blocks of 64 / 128 / 256 /
512 / 1024 against 1.64 / 1.23 / 1.02 / 0.90 / 0.84 for this one (0.65 ms
is the bytes' time at 819 GB/s; PERF.md section 6, PR 42): sixteen small
products a block, each with its own fill and drain of the unit and its own
softmax statistics, cost more than the rows saved.

It runs on the TPU, or wherever a test asks for it by name
(``interpret=True``); elsewhere a caller keeps the plain twin, a slot's
table row gathered whole under :func:`attended_rows`.  :func:`kernel_runs`
(``ops/flash_attention.py``) is the rule.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from .latent_paged_attention import live_first
from .flash_attention import kernel_runs   # the rule a caller asks

# The two pairs of blocks (keys and values, twice) a slot's pages are
# copied into may take this much VMEM.
BLOCK_VMEM_BYTES = 4 << 20
# A block's scores ``[heads, block]`` float32 stay within this many bytes.
# (At 48 heads on 8 of 128 in bfloat16 the blocks' budget binds first: 512
# tokens.  On the v5e five calls at `trinity-serve-mixed`'s shapes took 1.64
# / 1.23 / 1.02 / 0.90 / 0.84 ms at blocks of 64 / 128 / 256 / 512 / 1024
# tokens: a block's fixed cost outweighs the columns a longer block masks,
# and the last doubling buys 6% for twice the VMEM.)
SCORE_TILE_BYTES = 128 << 10


def block_pages(page_size: int, entries: int, heads: int, kv_width: int,
                itemsize: int) -> int:
    """Pages of one VMEM block, from the shapes alone: as many tokens as
    keep a block's float32 scores in ``SCORE_TILE_BYTES`` and the two
    pairs of blocks in ``BLOCK_VMEM_BYTES``, whole pages, no more than a
    table row has."""
    tokens = min(SCORE_TILE_BYTES // (4 * heads),
                 BLOCK_VMEM_BYTES // (4 * kv_width * itemsize))
    return max(1, min(tokens // page_size, entries))


def mapped_entries(cached, entries: int, page_size: int):
    """Table entries that hold a cached position of a slot with ``cached``
    positions: all its pages, and of a ring at most its length."""
    xp = np if isinstance(cached, np.ndarray) else jnp
    return xp.minimum((cached + page_size - 1) // page_size, entries)


def tokens_read(lengths, entries: int, page_size: int) -> int:
    """Tokens the kernel copies out of ONE layer's keys (and as many of
    its values) at these (host) lengths through a table of ``entries``:
    every live slot's entries in use, whole pages; idle slots (``< 0``)
    nothing."""
    lengths = np.asarray(lengths)
    live = lengths[lengths >= 0]
    return int(mapped_entries(live, entries, page_size).sum()) * page_size


def _kernel(order_ref, n_ref, len_ref, table_ref, layer_ref,   # scalars
            q_ref, ks_ref, vs_ref, k_store, v_store, o_ref,
            k_buf, v_buf, sem, m_scr, l_scr, acc_scr, par, *,
            scale: float, window: int, page: int, bp: int, entries: int,
            kv_heads: int):
    i = pl.program_id(0)
    n_live = n_ref[0]
    layer = layer_ref[0]
    block = bp * page
    od = k_buf.dtype

    def in_use(slot):
        return jnp.minimum((len_ref[slot] + page - 1) // page, entries)

    def pages_of(slot, j):
        """Entries of block ``j`` of ``slot`` that are in use."""
        return jnp.clip(in_use(slot) - j * bp, 0, bp)

    def copies(slot, j, b, k):
        at = table_ref[slot * entries + j * bp + k]
        rows = pl.ds(k * page, page)
        return (pltpu.make_async_copy(k_store.at[layer, at],
                                      k_buf.at[b, rows], sem.at[0, b]),
                pltpu.make_async_copy(v_store.at[layer, at],
                                      v_buf.at[b, rows], sem.at[1, b]))

    def each_page(slot, j, b, do):
        def one(k, carry):
            for page_copy in copies(slot, j, b, k):
                do(page_copy)
            return carry
        jax.lax.fori_loop(0, pages_of(slot, j), one, 0)

    def fetch(slot, j, b):
        each_page(slot, j, b, lambda page_copy: page_copy.start())

    def wait(slot, j, b):
        each_page(slot, j, b, lambda page_copy: page_copy.wait())

    def blocks_of(slot):
        return (in_use(slot) + bp - 1) // bp

    @pl.when(i == 0)
    def _():
        # Rows of a block past a slot's pages keep what was there before:
        # masked out of the scores, but a product's operand all the same.
        k_buf[...] = jnp.zeros_like(k_buf)
        v_buf[...] = jnp.zeros_like(v_buf)
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
        cached = len_ref[slot]
        nb = blocks_of(slot)
        has_next = i + 1 < n_live
        nxt = order_ref[jnp.minimum(i + 1, pl.num_programs(0) - 1)]
        # The ring, as scalars: the newest page lies in entry ``newest``;
        # columns up to that entry's last lie at ``base + column``, the
        # ones after it one ring's length earlier.
        top = jnp.maximum(cached - 1, 0) // page
        newest = top % entries
        base = (top - newest) * page
        edge = (newest + 1) * page
        held = in_use(slot) * page
        q = q_ref[0]                                   # [heads, kv_width]
        # The new token's own key and value are the first column: weight 1.
        m_scr[...] = jnp.sum(
            q.astype(jnp.float32) * ks_ref[0].astype(jnp.float32), axis=1,
            keepdims=True) * scale
        l_scr[...] = jnp.ones_like(l_scr)
        acc_scr[...] = jnp.broadcast_to(vs_ref[0].astype(jnp.float32),
                                        acc_scr.shape)

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

            s = jax.lax.dot_general(
                q, k_buf[b], (((1,), (1,)), ((), ())),
                preferred_element_type=jnp.float32) * scale    # [heads, block]
            column = j * block + jax.lax.broadcasted_iota(jnp.int32, s.shape,
                                                          1)
            pos = base + column - jnp.where(column >= edge, entries * page,
                                            0)
            seen = (column < held) & (pos < cached)
            if window:
                seen = seen & (pos > cached - window)
            s = jnp.where(seen, s, -jnp.inf)
            m_old = m_scr[...]
            m_new = jnp.maximum(m_old, jnp.max(s, axis=1, keepdims=True))
            alpha = jnp.exp(m_old - m_new)
            p = jnp.exp(s - m_new)
            l_scr[...] = alpha * l_scr[...] + jnp.sum(p, axis=1,
                                                      keepdims=True)
            acc_scr[...] = alpha * acc_scr[...] + jnp.dot(
                p.astype(od), v_buf[b], preferred_element_type=jnp.float32)
            m_scr[...] = m_new
            par[0] = 1 - b
            return carry

        jax.lax.fori_loop(0, nb, one, 0)
        # A head's probabilities met EVERY value head: it keeps its own.
        every = acc_scr[...] / l_scr[...]              # [heads, kv_width]
        heads, hd = o_ref.shape[1:]
        rep = heads // kv_heads
        head = jax.lax.broadcasted_iota(jnp.int32, (heads, hd), 0)
        own = jnp.zeros((heads, hd), jnp.float32)
        for g in range(kv_heads):
            own = jnp.where((head >= g * rep) & (head < (g + 1) * rep),
                            every[:, g * hd:(g + 1) * hd], own)
        o_ref[0] = own.astype(o_ref.dtype)


@functools.partial(jax.jit, static_argnames=("kv_heads", "scale", "window",
                                             "interpret", "out_dtype"),
                   inline=True)
def _pallas_attend(q, k_self, v_self, k_pages, v_pages, table, lengths,
                   layer, order, n_live, kv_heads: int, scale: float,
                   window: int, interpret: bool, out_dtype=None):
    slots, heads, kv_width = q.shape
    page = k_pages.shape[2]
    entries = table.shape[1]
    hd = kv_width // kv_heads
    bp = block_pages(page, entries, heads, kv_width, k_pages.dtype.itemsize)

    def live(i, order, n, *_):
        # A step past the live ones maps the last live slot's block again.
        return (order[jnp.minimum(i, jnp.maximum(n[0] - 1, 0))], 0, 0)

    return pl.pallas_call(
        functools.partial(_kernel, scale=scale, window=window, page=page,
                          bp=bp, entries=entries, kv_heads=kv_heads),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=5, grid=(slots,),
            in_specs=[pl.BlockSpec((1, heads, kv_width), live),
                      pl.BlockSpec((1, 1, kv_width), live),
                      pl.BlockSpec((1, 1, kv_width), live),
                      pl.BlockSpec(memory_space=pl.ANY),
                      pl.BlockSpec(memory_space=pl.ANY)],
            out_specs=pl.BlockSpec((1, heads, hd),
                                   lambda i, order, *_: (order[i], 0, 0)),
            scratch_shapes=[
                pltpu.VMEM((2, bp * page, kv_width), k_pages.dtype),
                pltpu.VMEM((2, bp * page, kv_width), v_pages.dtype),
                pltpu.SemaphoreType.DMA((2, 2)),
                pltpu.VMEM((heads, 1), jnp.float32),
                pltpu.VMEM((heads, 1), jnp.float32),
                pltpu.VMEM((heads, kv_width), jnp.float32),
                pltpu.SMEM((1,), jnp.int32)]),
        out_shape=jax.ShapeDtypeStruct((slots, heads, hd),
                                       out_dtype or q.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",)),
        interpret=interpret, name="gqa_paged_attn",
    )(order, n_live, lengths.astype(jnp.int32),
      table.reshape(-1).astype(jnp.int32),
      jnp.asarray(layer, jnp.int32).reshape(1), q, k_self[:, None, :],
      v_self[:, None, :], k_pages, v_pages)


def attended_rows(cached, entries: int, page_size: int, window: int = 0):
    """The kernel's mask as an array, for a twin that gathers: ``[slots,
    entries * page_size]`` bool, which rows of a slot's table row laid end
    to end (table order) the new token at position ``cached [slots]``
    attends (module docstring, "The table row is a ring")."""
    cached = cached[:, None]
    column = jnp.arange(entries * page_size)[None, :]
    top = jnp.maximum(cached - 1, 0) // page_size
    newest = top % entries
    pos = ((top - newest) * page_size + column
           - jnp.where(column >= (newest + 1) * page_size,
                       entries * page_size, 0))
    seen = ((column < mapped_entries(cached, entries, page_size) * page_size)
            & (pos < cached))
    if window:
        seen = seen & (pos > cached - window)
    return seen


def gathered_rows(cached, table, k_pages, v_pages, layer, window: int = 0):
    """For a twin that gathers: every slot's table row of paged layer
    ``layer`` laid end to end in table order, ``(k [slots, entries * page,
    kv_width], v, mask)``, ``mask`` :func:`attended_rows`."""
    b, entries = table.shape
    ps = k_pages.shape[2]
    k, v = (x[layer][table].reshape(b, entries * ps, -1)
            for x in (k_pages, v_pages))
    return k, v, attended_rows(cached, entries, ps, window)


def gqa_paged_attention(q, k_self, v_self, k_pages, v_pages, table, lengths,
                        layer, *, heads: int, scale: float, window: int = 0,
                        key_head=None, value_heads=None, out_dtype=None,
                        order=None, n_live=None, interpret=None):
    """One query a slot over the slot's cached keys and values and its new
    ones.

    ``q [slots, heads * hd]``; ``k_self``/``v_self`` ``[slots, kv_heads *
    hd]``: the new token's (position ``lengths[slot]``, not in the store
    yet); ``k_pages``/``v_pages`` ``[layers, pages, page, kv_heads * hd]``
    of which layer ``layer`` (an int or a traced scalar) is read; ``table
    [slots, entries]``, a ring (module docstring); ``lengths [slots]`` (-1
    idle); ``window``: rows fewer than ``window`` positions before the new
    token are attended (0: all).  ``order``/``n_live``:
    :func:`live_first` of ``lengths``, for a caller that attends several
    layers at the same lengths.

    **The head map** (left out: grouped-query attention's).  ``key_head
    [heads]`` (host integers): the key head whose lanes query head ``i``
    is laid into, ``i // (heads / kv_heads)`` by default.  ``value_heads``:
    into how many heads the value row divides, ``kv_heads`` by default;
    query head ``i`` keeps the lanes of value head ``i // (heads /
    value_heads)``.  Differential attention (``models/hybrid_ssm.py``)
    pairs its heads: query head ``i`` reads key head ``2 * (i // per) + i %
    2`` and keeps the double-width value of its PAIR (``value_heads =
    kv_heads / 2``).  ``out_dtype``: the type the output is handed on in
    (``q``'s by default; float32 for a caller that goes on in float32).

    Returns ``[slots, heads * kv_width / value_heads]``; an idle slot's
    rows are exact zeros.  Only the entries in use of the live slots are
    read; their rows outside the mask must be finite."""
    slots, hd = q.shape[0], q.shape[1] // heads
    kv_heads = k_self.shape[1] // hd
    if order is None:
        order, n_live = live_first(lengths)
    if key_head is None:
        key_head = np.arange(heads) // (heads // kv_heads)
    of_head = jnp.asarray(
        np.asarray(key_head)[:, None] == np.arange(kv_heads)[None, :],
        q.dtype)
    laid = jnp.einsum("bhd,hg->bhgd", q.reshape(slots, heads, hd),
                      of_head).reshape(slots, heads, kv_heads * hd)
    # The kernel keeps, of ``kv_heads`` equal parts of the value row, the
    # part of the head's group: the value heads are its ``kv_heads``.
    o = _pallas_attend(laid, k_self, v_self, k_pages, v_pages, table,
                       lengths, layer, order, n_live,
                       int(value_heads or kv_heads), float(scale),
                       int(window), bool(interpret),
                       None if out_dtype is None else jnp.dtype(out_dtype))
    return o.reshape(slots, -1)
