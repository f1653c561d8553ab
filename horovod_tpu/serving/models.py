"""What the serving engine asks of a model.

:class:`~horovod_tpu.serving.engine.InferenceEngine` knows no model.  It
builds its paged store and its executables from a small protocol, and
every model goes through the ONE ``_step`` / ``_admit`` / ``_prefill`` /
``_decode_iteration``, scheduler, page tables and ``LMServer``:

``identity() -> dict``
    What changes compiled programs or cache content: the prefix-cache
    fingerprint, the manifest's model field, the draft identity.
``cache_entry() -> dict``
    What a token leaves in the cache: ``n_layers``, ``n_heads``,
    ``head_dim`` and ``widths``, the minor width of each store
    (:class:`~horovod_tpu.serving.kv_cache.PagedKVCache` ``entry_widths``).
``cache_entry()["slot_stores"]`` (optional), ``slot_state``
    Per-slot stores beside the pages, ``{"name", "kind", "shape",
    "dtype"}`` each (``kind``: ``"window"`` a ring of the last positions,
    ``"state"`` recurrent state, ``"scratch"`` room a program keeps across
    iterations; ``shape`` led by the store's own layer count, a dimension
    a number or ``"capacity"``, the slot's positions): the cache manager
    holds one array ``[layers, max_slots, *shape]`` a store and tells the
    model their bytes by kind once (``observe_stores``).  ``pages`` below
    is then ``cache.arrays``: the page arrays, then these.  A model with
    ``slot_state = True`` is told which slot a prefill fills.
``decode(params, pages, table, lengths, tokens) -> (outs, pages)``
    One token a slot over the paged store; writes the new entries back.
    ``outs[0]`` is ``logits [slots, vocab]``, which stay on the device
    unless a slot samples (the engine's executable takes their argmax
    itself); anything after it is fetched and goes to ``observe_decode``
    inside ``serve.sample``.
``decode_view(lengths, page_size, pages_per_slot) -> tokens``
    What a slot the decode program attends of the store at these host
    lengths, from the store's geometry: the live tokens in whole pages
    where a kernel reads the pages in place; the rung about to be picked,
    by the program's own pure function, where a view is gathered (the
    dense decoder, ``mamba2_hybrid``).  ``serving.decode_view_tokens``
    counts it.
``prefill(params, pages, table_row, start, n_valid, tokens[, slot]) -> (outs, pages)``
    A padded prompt block from ``start`` cached positions; ``outs`` is
    ``(last,)``, the last real token's logits.  ``slot [1]`` only where
    ``slot_state``: the slot's rows of the per-slot stores are REPLACED
    by what the prompt leaves (recurrent state has no mask that could
    hide an evicted sequence's).
``observe_launch(lengths)`` (optional)
    Called once a retired decode iteration with the host lengths it was
    launched at: what the model itself counts of an iteration.
``verify`` / ``propose``
    The speculative programs, ``(outs, pages)`` like the others; a model
    with ``speculative = False`` has none and the engine refuses a draft
    for it.
``tensor_parallel`` (+ ``tensor_parallel_why``), ``prefix_cache`` (+ ``prefix_cache_why``)
    Whether the store may be sharded over a ``model`` axis, and whether
    a suffix prefill over cached prefix pages is exact for this model.

A config object that has a ``serving_model()`` method supplies its own
(``models/latent_moe.py``, ``models/hybrid_ssm.py``,
``models/mamba2_hybrid.py``, ``models/afmoe.py``,
``models/olmo_hybrid.py``); every other config is the dense multi-head
decoder of ``models/transformer.py``, :class:`DenseLM`, whose programs
are operation for operation the ones the engine built itself before.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

from ..models import transformer as _transformer


def serving_model(cfg):
    make = getattr(cfg, "serving_model", None)
    return make() if make is not None else DenseLM(cfg)


class DenseLM:
    """``models/transformer.py``'s decoder: two stores, keys and values
    of all heads."""

    speculative = True
    tensor_parallel = True
    prefix_cache = True
    prefix_cache_why = ""
    slot_state = False          # no per-slot store: pages are all it keeps

    def __init__(self, cfg) -> None:
        self.cfg = cfg

    def identity(self) -> dict:
        cfg = self.cfg
        return {
            "vocab_size": cfg.vocab_size,
            "d_model": cfg.d_model,
            "n_heads": cfg.n_heads,
            "n_layers": cfg.n_layers,
            "d_ff": cfg.d_ff,
            "max_seq_len": cfg.max_seq_len,
            "num_experts": cfg.num_experts,
            "dtype": jnp.dtype(cfg.dtype).name,
        }

    def decode_view(self, lengths, page_size, pages_per_slot) -> int:
        """One rung for every slot, holding the [token, dummy] block."""
        rungs = _transformer.view_rungs(page_size, pages_per_slot)
        return rungs[_transformer.view_rung(lengths, rungs)]

    def cache_entry(self) -> dict:
        cfg = self.cfg
        hd = cfg.d_model // cfg.n_heads
        return {"n_layers": cfg.n_layers, "n_heads": cfg.n_heads,
                "head_dim": hd, "widths": (cfg.n_heads * hd,) * 2}

    def decode(self, params, pages, table, lengths, tokens):
        cfg = self.cfg
        k_pages, v_pages = pages
        ps, L, B = k_pages.shape[2], cfg.n_layers, tokens.shape[0]
        # Width-2 block: [token, dummy]; the dummy column keeps the
        # gemms off XLA:CPU's bitwise-divergent single-row path and
        # is never sampled nor scattered.  The scheduler evicts at
        # prompt+generated == capacity, so the deepest decode here
        # runs at length == capacity-2 and the block always fits
        # the last rung; every other rung is picked to hold it.
        blk = jnp.stack([tokens, jnp.zeros_like(tokens)], axis=1)
        logits, k_new, v_new = _transformer.forward_step_paged(
            params, blk, lengths, k_pages, v_pages, table, cfg)
        # One row a slot, written where it lies: B in-place
        # dynamic-update-slices.  (A scatter over the flattened
        # store makes the TPU copy all of it into a layout of the
        # scatter's own, and back.)
        pos = jnp.clip(lengths, 0, None)
        page, off = table[jnp.arange(B), pos // ps], pos % ps
        zero = jnp.zeros((), jnp.int32)
        for slot in range(B):
            at = (zero, page[slot], off[slot], zero)
            k_pages = jax.lax.dynamic_update_slice(
                k_pages, k_new[:, slot, 0].reshape(L, 1, 1, -1), at)
            v_pages = jax.lax.dynamic_update_slice(
                v_pages, v_new[:, slot, 0].reshape(L, 1, 1, -1), at)
        return (logits[:, 0],), (k_pages, v_pages)

    def _views(self, pages, table):
        """Every slot's whole page-table row gathered into dense
        capacity-long views ``[layers, slots, capacity, heads,
        head_dim]``."""
        cfg = self.cfg
        L, H = cfg.n_layers, cfg.n_heads
        hd = cfg.d_model // H
        b, pps = table.shape
        ps = pages[0].shape[2]
        return tuple(p[:, table].reshape(L, b, pps * ps, H, hd)
                     for p in pages)

    def _scatter(self, pages, flat, new):
        """Rows ``new`` into the stores at flat token rows ``flat``."""
        cfg = self.cfg
        L, H = cfg.n_layers, cfg.n_heads
        hd = cfg.d_model // H
        n_pages, ps = pages[0].shape[1:3]
        out = []
        for store, rows in zip(pages, new):
            f = store.reshape(L, n_pages * ps, H, hd)
            out.append(f.at[:, flat].set(rows).reshape(store.shape))
        return tuple(out)

    def prefill(self, params, pages, table_row, start, n_valid, tokens):
        ps = pages[0].shape[2]
        pps = table_row.shape[1]
        cap, bucket = pps * ps, tokens.shape[1]
        k_view, v_view = self._views(pages, table_row)
        logits, k_new, v_new = _transformer.forward_step(
            params, tokens, start, k_view, v_view, self.cfg)
        idx = start[0] + jnp.arange(bucket, dtype=jnp.int32)
        # Positions past the capacity (a deep suffix's padding) and
        # pad positions whose page is unmapped both land in trash
        # page 0; real positions are mapped by construction.
        page = jnp.where(
            idx < cap,
            table_row[0, jnp.clip(idx // ps, 0, pps - 1)], 0)
        flat = page * ps + idx % ps
        return ((logits[0, n_valid[0] - 1],),
                self._scatter(pages, flat, (k_new[:, 0], v_new[:, 0])))

    def verify(self, params, pages, table, lengths, blocks):
        ps = pages[0].shape[2]
        pps = table.shape[1]
        cap, W = pps * ps, blocks.shape[1]
        k_view, v_view = self._views(pages, table)
        logits, k_new, v_new = _transformer.forward_step(
            params, blocks, lengths, k_view, v_view, self.cfg)
        pos = (jnp.clip(lengths, 0, None)[:, None]
               + jnp.arange(W, dtype=jnp.int32)[None, :])
        page = jnp.where(
            pos < cap,
            jnp.take_along_axis(table,
                                jnp.clip(pos // ps, 0, pps - 1),
                                axis=1), 0)
        flat = page * ps + pos % ps
        return (logits,), self._scatter(pages, flat, (k_new, v_new))

    def propose(self, params, pages, table, lengths, prev, pending, m):
        ps = pages[0].shape[2]
        pps = table.shape[1]
        cap = pps * ps
        k_view, v_view = self._views(pages, table)
        sp = lengths - 1
        proposals, kc, vc = _transformer.speculative_propose(
            params, prev, pending, sp, k_view, v_view, self.cfg, m)
        pos = sp[:, None] + jnp.arange(m + 1, dtype=jnp.int32)[None]
        page = jnp.where(
            (pos >= 0) & (pos < cap),
            jnp.take_along_axis(table,
                                jnp.clip(pos // ps, 0, pps - 1),
                                axis=1), 0)
        flat = page * ps + jnp.where(pos >= 0, pos % ps, 0)
        return (proposals,), self._scatter(pages, flat, (kc, vc))
