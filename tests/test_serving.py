"""hvd-serve: scheduler unit tests (no XLA), paged KV cache, the
incremental-decode bitwise contract, engine/executable behavior, the
HTTP front door on the shared exporter, and elastic drain/resume.

The load-bearing assertion (ISSUE 7 acceptance): prefill + N decode
steps through the cached donated executables reproduce the jitted
non-incremental ``serving_forward`` BITWISE — greedy completions are
therefore invariant to batch composition, slot assignment, scheduler
policy, and engine relaunches, which is what makes continuous batching
and elastic resize observably side-effect-free.
"""

import functools
import hashlib
import json
import os
import threading
import types
import urllib.request
from unittest import mock

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from horovod_tpu.models import transformer
from horovod_tpu.models.transformer import (TransformerConfig,
                                            forward_step,
                                            init_transformer,
                                            serving_forward, view_rung,
                                            view_rungs)
from horovod_tpu.serving import (ContinuousBatchingScheduler,
                                 FinishReason, InferenceEngine, LMServer,
                                 PagedKVCache, Request)

CFG = TransformerConfig(vocab_size=97, d_model=64, n_heads=4, n_layers=2,
                        d_ff=128, max_seq_len=64)
PARAMS = init_transformer(jax.random.PRNGKey(0), CFG)


def make_engine(**kw):
    kw.setdefault("max_slots", 3)
    kw.setdefault("page_size", 8)
    kw.setdefault("capacity", 32)
    return InferenceEngine(PARAMS, CFG, **kw)


def reference_rollout(prompt, n, capacity, params=PARAMS, cfg=CFG):
    """Greedy rollout through the jitted NON-incremental forward."""
    sf = jax.jit(serving_forward, static_argnums=(2, 3))
    seq = list(prompt)
    out = []
    for _ in range(n):
        logits = np.asarray(sf(params, jnp.asarray([seq], jnp.int32),
                               cfg, capacity))
        tok = int(np.argmax(logits[0, -1]))
        out.append(tok)
        seq.append(tok)
    return out


# ---------------------------------------------------------------------------
# Scheduler (pure unit — no XLA)
# ---------------------------------------------------------------------------

def _req(prompt=(1, 2, 3), **kw):
    kw.setdefault("max_new_tokens", 4)
    return Request(prompt=list(prompt), **kw)


def test_scheduler_admission_is_fifo_lowest_slot_first():
    s = ContinuousBatchingScheduler(max_slots=2, capacity=32)
    r1, r2, r3 = (s.submit(_req()) for _ in range(3))
    admitted = s.admit()
    assert [(slot, r.rid) for slot, r in admitted] == [(0, r1.rid),
                                                      (1, r2.rid)]
    assert s.queue_depth() == 1 and s.occupancy() == 2
    # r3 must wait; no later arrival can jump it.
    r4 = s.submit(_req())
    assert s.admit() == []
    # Evict slot 1 -> next admit takes THE HEAD (r3) into slot 1.
    for _ in range(4):
        s.feed(1, 9)
    assert r2.finish_reason == FinishReason.MAX_NEW_TOKENS
    admitted = s.admit()
    assert [(slot, r.rid) for slot, r in admitted] == [(1, r3.rid)]
    assert s.queue_depth() == 1 and r4.done.is_set() is False


def test_scheduler_eviction_reasons_and_slot_reuse():
    s = ContinuousBatchingScheduler(max_slots=1, capacity=8)
    r_eos = s.submit(_req(max_new_tokens=10, eos_id=42))
    s.admit()
    assert s.feed(0, 7) is None
    assert s.feed(0, 42) == FinishReason.EOS
    assert r_eos.result(0) == [7, 42]
    # Slot 0 reusable immediately (iteration-level eviction).
    r_cap = s.submit(_req(prompt=[1, 2, 3, 4, 5], max_new_tokens=10))
    assert s.admit()[0][0] == 0
    assert s.feed(0, 1) is None  # 5 + 2 < 8
    assert s.feed(0, 1) is None
    assert s.feed(0, 1) == FinishReason.CAPACITY
    r_max = s.submit(_req(max_new_tokens=1))
    s.admit()
    assert s.feed(0, 3) == FinishReason.MAX_NEW_TOKENS
    assert r_max.result(0) == [3]
    assert r_cap.finish_reason == FinishReason.CAPACITY


def test_scheduler_starvation_freedom_under_full_batch():
    """Adversarial: a stream of long jobs keeps the batch full; the
    head-of-queue short job is still admitted within a bounded number
    of iterations (FIFO — nothing can overtake it)."""
    s = ContinuousBatchingScheduler(max_slots=2, capacity=1000)
    long_reqs = [s.submit(_req(max_new_tokens=100)) for _ in range(2)]
    s.admit()
    victim = s.submit(_req(max_new_tokens=1))
    # Keep submitting fresh long jobs behind the victim every iteration.
    for it in range(200):
        s.submit(_req(max_new_tokens=100))
        for slot, r in s.active():
            s.feed(slot, 5)
        admitted = s.admit()
        if any(r is victim for _, r in admitted):
            break
    else:
        pytest.fail("victim request was starved")
    # Admitted as soon as the first long job finished (100 iterations).
    assert it <= 100


def test_scheduler_deterministic_composition_from_seeded_trace():
    def run():
        rng = np.random.default_rng(3)
        s = ContinuousBatchingScheduler(max_slots=3, capacity=64)
        log = []
        reqs = []
        for it in range(40):
            if rng.random() < 0.6:
                reqs.append(s.submit(_req(
                    max_new_tokens=int(rng.integers(1, 6)),
                    arrival=it)))
            for slot, r in s.active():
                s.feed(slot, int(rng.integers(0, 9)))
            log.append(tuple((slot, r.rid)
                             for slot, r in s.admit(now=it)))
            log.append(tuple(slot for slot, _ in s.active()))
        return log

    assert run() == run()


def test_scheduler_arrival_gating_and_drain():
    s = ContinuousBatchingScheduler(max_slots=2, capacity=32)
    r = s.submit(_req(arrival=5))
    assert s.admit(now=4) == []
    assert [x[1] for x in s.admit(now=5)] == [r]
    s.feed(0, 1)
    drained, pending = s.drain()
    assert drained == [r] and pending == []
    assert r.finish_reason == FinishReason.DRAINED
    assert r.result(0) == [1]
    with pytest.raises(RuntimeError):
        s.submit(_req())
    s.resume()
    s.submit(_req())
    assert len(s.admit()) == 1


def test_scheduler_snapshot_is_one_lock_hold_and_drain_reason():
    """snapshot() returns (active, pending) atomically — the export
    path's view; drain(reason) finishes in-flight sequences with the
    caller's reason (set BEFORE done, so a blocked handler can never
    read a stale one) and returns raced pending submissions too."""
    s = ContinuousBatchingScheduler(max_slots=1, capacity=32)
    r1 = s.submit(_req())
    s.admit()
    r2 = s.submit(_req())
    active, pending = s.snapshot()
    assert active == [(0, r1)] and pending == [r2]
    drained, pending = s.drain(FinishReason.ERROR)
    assert drained == [r1] and pending == [r2]
    assert r1.finish_reason == FinishReason.ERROR and r1.done.is_set()
    # Pending requests are returned for the CALLER to fail/requeue —
    # drain itself must not touch them (the elastic path resubmits).
    assert r2.finish_reason is None and not r2.done.is_set()


def test_scheduler_feed_expect_tolerates_concurrent_eviction():
    """feed(expect=req): when a concurrent drain evicted the slot (or
    another request now holds it), the token is discarded and the
    evicted request's finish reason is returned instead of raising —
    a drain landing mid-iteration must not poison the step."""
    s = ContinuousBatchingScheduler(max_slots=1, capacity=32)
    r1 = s.submit(_req())
    s.admit()
    s.drain()
    assert s.feed(0, 7, expect=r1) == FinishReason.DRAINED
    assert r1.generated == []  # the token was discarded
    with pytest.raises(ValueError):
        s.feed(0, 7)  # without expect the strict contract remains


def test_scheduler_rejects_bad_prompts():
    s = ContinuousBatchingScheduler(max_slots=1, capacity=8)
    with pytest.raises(ValueError):
        s.submit(_req(prompt=[]))
    with pytest.raises(ValueError):
        s.submit(_req(prompt=list(range(8))))  # no room to generate


# ---------------------------------------------------------------------------
# Paged KV cache
# ---------------------------------------------------------------------------

def test_kv_cache_page_lifecycle_and_reuse():
    c = PagedKVCache(n_layers=2, n_heads=4, head_dim=16, max_slots=2,
                     pages_per_slot=4, page_size=8)
    assert c.n_pages == 9 and c.free_pages() == 8  # page 0 reserved
    c.begin_slot(0, 10)  # 10 tokens -> 2 pages
    assert c.free_pages() == 6 and c.length(0) == 10
    first_pages = list(c._table[0][:2])
    assert 0 not in first_pages
    c.ensure(0, 16)  # 3rd page
    assert c.free_pages() == 5
    c.free_slot(0)
    assert c.free_pages() == 8 and c.length(0) == -1
    # Recycled pages serve the next sequence.
    c.begin_slot(1, 30)
    assert c.free_pages() == 4
    with pytest.raises(ValueError):
        c.ensure(1, 32)  # beyond per-slot capacity
    with pytest.raises(ValueError):
        c.begin_slot(1, 2)  # already active


def test_kv_cache_ensure_on_freed_slot_is_a_leakfree_noop():
    """Regression (drain-vs-serve-loop page leak): step() reads
    length(slot) and calls ensure(slot, n) as two lock holds, so a
    drain freeing the slot between them must make ensure a no-op —
    pages mapped into a freed slot are unreachable forever (free_slot
    early-returns on length < 0 and begin_slot zeroes the row)."""
    c = PagedKVCache(n_layers=1, n_heads=4, head_dim=8, max_slots=2,
                     pages_per_slot=4, page_size=8)
    c.begin_slot(0, 10)
    n = c.length(0)
    c.free_slot(0)  # the concurrent drain lands here
    free_before = c.free_pages()
    c.ensure(0, n)  # the loop's stale call: must not map pages
    assert c.free_pages() == free_before
    assert list(c._table[0]) == [0] * 4
    c.begin_slot(0, 10)  # slot stays reusable, no pages lost
    c.free_slot(0)
    assert c.free_pages() == c.n_pages - 1


def test_kv_cache_sharding_requires_model_axis():
    c = PagedKVCache(n_layers=1, n_heads=4, head_dim=8, max_slots=1,
                     pages_per_slot=2, page_size=4)
    assert c.page_sharding() is None  # no mesh


# ---------------------------------------------------------------------------
# Shared-prefix page cache (hvd-spec)
# ---------------------------------------------------------------------------

def _prefix_cache(**kw):
    kw.setdefault("max_slots", 3)
    kw.setdefault("pages_per_slot", 4)
    kw.setdefault("page_size", 8)
    kw.setdefault("prefix_cache", True)
    kw.setdefault("fingerprint", "test-model")
    return PagedKVCache(n_layers=1, n_heads=2, head_dim=8, **kw)


def test_prefix_publish_lookup_chain_semantics():
    c = _prefix_cache()
    prompt = list(range(20))  # 2 full pages + 4 tokens
    c.begin_slot(0, len(prompt))
    assert c.publish_prefix(0, prompt) == 2
    # Longest cached page-aligned STRICT prefix: the full 2 pages for
    # an extending prompt, 1 page when only the first page matches,
    # nothing for a diverging first page.
    assert len(c.lookup_prefix(prompt + [50, 51])) == 2
    assert len(c.lookup_prefix(prompt[:8] + [99] * 12)) == 1
    assert c.lookup_prefix([99] + prompt) == []
    # Strictness: a prompt that IS the cached prefix exactly keeps at
    # least one suffix token to prefill.
    assert len(c.lookup_prefix(prompt[:16])) == 1
    # The chain hash commits to every earlier token: same page-2
    # content after a different page 1 must miss.
    assert len(c.lookup_prefix([98] * 8 + prompt[8:16] + [1])) == 0


def test_prefix_refcount_lru_and_reclaim():
    c = _prefix_cache(max_slots=2, pages_per_slot=4)
    prompt = list(range(17))  # 2 full pages
    c.begin_slot(0, len(prompt))
    c.publish_prefix(0, prompt)
    pages = c.lookup_prefix(prompt + [1])
    stats = c.prefix_stats()
    assert stats["cached_pages"] == 2
    assert stats["referenced_pages"] == 2  # slot 0 holds them
    assert stats["reclaimable_pages"] == 0
    # A second slot maps them copy-free; refcounts go to 2.
    c.begin_slot(1, len(prompt) + 3, prefix_pages=pages)
    assert list(c._table[1][:2]) == pages
    assert c.prefix_stats()["referenced_pages"] == 2
    c.free_slot(0)
    assert c.prefix_stats()["referenced_pages"] == 2  # slot 1 remains
    c.free_slot(1)
    stats = c.prefix_stats()
    # Unreferenced but still cached: parked in the reclaimable LRU,
    # counted as free headroom.
    assert stats["referenced_pages"] == 0
    assert stats["reclaimable_pages"] == 2
    assert c.free_pages() == c.total_pages
    # Pressure reclaims LRU pages (and drops their index entries) but
    # NEVER a referenced one.
    c.begin_slot(0, 32)  # all 4 pages of slot 0
    c.begin_slot(1, 32)  # exhausts the free list + both LRU pages
    assert c.prefix_stats()["cached_pages"] == 0
    assert len(c.lookup_prefix(prompt + [1])) == 0


def test_prefix_referenced_pages_never_reclaimed():
    """Pressure reclaims only UNREFERENCED cached pages: with a ghost
    chain parked in the LRU and a referenced shared page live, filling
    the store consumes the LRU and leaves the referenced page (and
    slot 0's mapping of it) untouched."""
    c = _prefix_cache(max_slots=2, pages_per_slot=4)
    prompt = list(range(9))  # 1 full page
    c.begin_slot(0, len(prompt))
    c.publish_prefix(0, prompt)          # page referenced by slot 0
    c.ensure(0, 31)                      # slot 0 holds all 4 pages
    ghost_tokens = list(range(60, 76))
    c.publish_ghost(c.alloc_ghost(2), ghost_tokens)
    assert c.prefix_stats()["reclaimable_pages"] == 2
    assert c.free_pages() == 4           # 2 free-list + 2 reclaimable
    shared_page = int(c._table[0][0])
    c.begin_slot(1, 32)                  # needs 4 -> reclaims the LRU
    stats = c.prefix_stats()
    assert stats["reclaimable_pages"] == 0
    assert stats["cached_pages"] == 1    # the referenced page survives
    assert int(c._table[0][0]) == shared_page
    assert c.lookup_prefix(ghost_tokens + [1]) == []


def test_prefix_ghost_seed_roundtrip():
    c = _prefix_cache()
    tokens = list(range(16))  # exactly 2 pages
    row = c.alloc_ghost(2)
    assert c.publish_ghost(row, tokens) == 2
    stats = c.prefix_stats()
    assert stats["cached_pages"] == 2
    assert stats["reclaimable_pages"] == 2  # refcount zero, hittable
    assert len(c.lookup_prefix(tokens + [7])) == 2
    # Export returns the maximal chain only.
    assert c.export_prefixes() == [tokens]
    # Re-publishing the same chain frees the duplicate pages back.
    free_before = c.free_pages()
    row2 = c.alloc_ghost(2)
    assert c.publish_ghost(row2, tokens) == 0
    assert c.free_pages() == free_before


def test_prefix_disabled_cache_is_inert():
    c = _prefix_cache(prefix_cache=False)
    prompt = list(range(20))
    c.begin_slot(0, len(prompt))
    assert c.publish_prefix(0, prompt) == 0
    assert c.lookup_prefix(prompt + [1]) == []
    assert c.prefix_stats()["cached_pages"] == 0


# ---------------------------------------------------------------------------
# Incremental decode: the bitwise contract (model level)
# ---------------------------------------------------------------------------

def test_prefill_plus_decode_bitwise_equals_noncached_forward():
    """THE satellite contract: prefill + N width-2 decode steps through
    jitted forward_step reproduce the non-incremental forward bitwise
    (same jit, any split point)."""
    b, P, N, cap = 2, 7, 9, 32
    tokens = jax.random.randint(jax.random.PRNGKey(1), (b, P + N), 0,
                                CFG.vocab_size).astype(jnp.int32)
    hd = CFG.d_model // CFG.n_heads
    zeros = jnp.zeros((CFG.n_layers, b, cap, CFG.n_heads, hd), CFG.dtype)
    z = jnp.zeros((b,), jnp.int32)
    step = jax.jit(forward_step, static_argnums=(5,))
    ref, _, _ = step(PARAMS, tokens, z, zeros, zeros, CFG)
    ref = np.asarray(ref)

    def scatter(view, new, start):
        return jax.vmap(
            lambda vb, nb, s: jax.lax.dynamic_update_slice_in_dim(
                vb, nb, s, axis=1),
            in_axes=(1, 1, 0), out_axes=1)(view, new, start)

    k, v = zeros, zeros
    logits, kn, vn = step(PARAMS, tokens[:, :P], z, k, v, CFG)
    assert np.asarray(logits).tobytes() == ref[:, :P].tobytes()
    k, v = scatter(k, kn, z), scatter(v, vn, z)
    for t in range(N):
        pos = jnp.full((b,), P + t, jnp.int32)
        blk = jnp.concatenate(
            [tokens[:, P + t:P + t + 1],
             jnp.zeros((b, 1), jnp.int32)], axis=1)  # width-2 block
        logits, kn, vn = step(PARAMS, blk, pos, k, v, CFG)
        assert (np.asarray(logits)[:, :1].tobytes()
                == ref[:, P + t:P + t + 1].tobytes()), f"step {t}"
        k = scatter(k, kn[:, :, :1], pos)
        v = scatter(v, vn[:, :, :1], pos)


def test_decode_at_final_capacity_position_is_bitwise():
    """Regression (width-2 decode at the capacity boundary): a decode
    block [token, dummy] landing at start == capacity-1 used to go
    through a clamped slice-update that shifted the whole window back
    one position — overwriting the previous token's K/V with the
    current token's and leaving the dummy's K/V unmasked at
    capacity-1.  forward_step must instead keep the real token at its
    true index and drop the dummy column."""
    b, cap = 2, 16
    tokens = jax.random.randint(jax.random.PRNGKey(4), (b, cap), 0,
                                CFG.vocab_size).astype(jnp.int32)
    hd = CFG.d_model // CFG.n_heads
    zeros = jnp.zeros((CFG.n_layers, b, cap, CFG.n_heads, hd), CFG.dtype)
    z = jnp.zeros((b,), jnp.int32)
    step = jax.jit(forward_step, static_argnums=(5,))
    ref, _, _ = step(PARAMS, tokens, z, zeros, zeros, CFG)
    # Prefill the first cap-1 positions, then decode the final one.
    _, kn, vn = step(PARAMS, tokens[:, :cap - 1], z, zeros, zeros, CFG)
    k = zeros.at[:, :, :cap - 1].set(kn)
    v = zeros.at[:, :, :cap - 1].set(vn)
    pos = jnp.full((b,), cap - 1, jnp.int32)
    blk = jnp.concatenate([tokens[:, cap - 1:],
                           jnp.zeros((b, 1), jnp.int32)], axis=1)
    logits, kn2, _ = step(PARAMS, blk, pos, k, v, CFG)
    assert (np.asarray(logits)[:, 0].tobytes()
            == np.asarray(ref)[:, cap - 1].tobytes())
    # The returned new-token K is the real token's (scatter-back input).
    _, k_ref, _ = step(PARAMS, tokens, z, zeros, zeros, CFG)
    assert (np.asarray(kn2[:, :, 0]).tobytes()
            == np.asarray(k_ref[:, :, cap - 1]).tobytes())


def test_ragged_batch_masking_matches_per_sequence_runs():
    """Cache-aware causal masking for ragged batches: each row of a
    mixed-length decode batch is bitwise what it would be alone."""
    cap = 16
    hd = CFG.d_model // CFG.n_heads
    step = jax.jit(forward_step, static_argnums=(5,))

    def kv(b):
        return jnp.zeros((CFG.n_layers, b, cap, CFG.n_heads, hd),
                         CFG.dtype)

    t1 = jax.random.randint(jax.random.PRNGKey(2), (1, 5), 0,
                            CFG.vocab_size).astype(jnp.int32)
    t2 = jax.random.randint(jax.random.PRNGKey(3), (1, 9), 0,
                            CFG.vocab_size).astype(jnp.int32)
    z1 = jnp.zeros((1,), jnp.int32)
    _, k1, v1 = step(PARAMS, t1, z1, kv(1), kv(1), CFG)
    _, k2, v2 = step(PARAMS, t2, z1, kv(1), kv(1), CFG)

    def install(view, new, row):
        return view.at[:, row, :new.shape[2]].set(new[:, 0])

    # Batched ragged decode: row 0 at position 5, row 1 at position 9.
    kb = install(install(kv(2), k1, 0), k2, 1)
    vb = install(install(kv(2), v1, 0), v2, 1)
    toks = jnp.asarray([[7, 0], [11, 0]], jnp.int32)
    lengths = jnp.asarray([5, 9], jnp.int32)
    lb, _, _ = step(PARAMS, toks, lengths, kb, vb, CFG)
    # Per-sequence singles (batch independence is part of the contract).
    la, _, _ = step(PARAMS, jnp.asarray([[7, 0]], jnp.int32),
                    jnp.asarray([5], jnp.int32),
                    install(kv(1), k1, 0), install(kv(1), v1, 0), CFG)
    lc, _, _ = step(PARAMS, jnp.asarray([[11, 0]], jnp.int32),
                    jnp.asarray([9], jnp.int32),
                    install(kv(1), k2, 0), install(kv(1), v2, 0), CFG)
    assert (np.asarray(lb)[0, 0].tobytes()
            == np.asarray(la)[0, 0].tobytes())
    assert (np.asarray(lb)[1, 0].tobytes()
            == np.asarray(lc)[0, 0].tobytes())
    # Inactive rows (q_pos = -1) are finite, not NaN.
    linact, _, _ = step(PARAMS, toks, jnp.asarray([5, -1], jnp.int32),
                        kb, vb, CFG)
    assert bool(jnp.isfinite(linact).all())


# ---------------------------------------------------------------------------
# Engine: executables, bitwise acceptance, invariance, warm start
# ---------------------------------------------------------------------------

def test_engine_bitwise_vs_noncached_forward_through_executables():
    """Acceptance gate: the engine's paged, donated, AOT-compiled
    prefill/decode executables reproduce the non-incremental forward
    bitwise — captured logits compared position by position."""
    eng = make_engine()
    eng.warm_start()
    prompt = [3, 1, 4, 1, 5, 9, 2]
    N = 6
    req = eng.submit(prompt, max_new_tokens=N)
    rows, pf = [], []
    orig_dec, orig_pf = eng._decode_iteration, eng._prefill

    def wrapped_dec(active):
        logits = orig_dec(active)
        rows.append(logits[active[0][0]].copy())
        return logits

    def wrapped_pf(slot, r, *a, **kw):
        out = orig_pf(slot, r, *a, **kw)
        pf.append(np.asarray(out[2]))   # (token, tokens, last)
        return out

    eng._decode_iteration = wrapped_dec
    eng._prefill = wrapped_pf
    eng.run_until_idle()
    gen = req.result(0)
    full = prompt + gen
    sf = jax.jit(serving_forward, static_argnums=(2, 3))
    ref = np.asarray(sf(PARAMS, jnp.asarray([full], jnp.int32), CFG,
                        eng.capacity))
    P = len(prompt)
    assert pf[0].tobytes() == ref[0, P - 1].tobytes()
    for i, row in enumerate(rows[:N - 1]):
        assert row.tobytes() == ref[0, P + i].tobytes(), f"decode {i}"


def test_engine_greedy_matches_reference_and_batch_invariance():
    eng = make_engine()
    eng.warm_start()
    prompts = [[5, 3, 8], [1, 2, 3, 4, 5, 6], [9, 9, 2, 6]]
    ref = [reference_rollout(p, 7, eng.capacity) for p in prompts]
    # Sequential, one at a time.
    seq_out = [eng.generate(list(p), max_new_tokens=7) for p in prompts]
    assert seq_out == ref
    # Concurrent: all three share the decode batch (3 slots); the
    # completions must be identical — batch-composition invariance.
    eng2 = make_engine()
    eng2.warm_start()
    reqs = [eng2.submit(list(p), max_new_tokens=7) for p in prompts]
    eng2.run_until_idle()
    assert [r.result(0) for r in reqs] == ref


def test_engine_continuous_matches_static_batching_on_a_ragged_trace():
    """Admission policy changes WHEN a request runs, never what it
    generates: a seeded ragged-arrival trace admitted into any free
    slot every iteration (continuous: sequences join a batch that is
    mid-decode) and admitted only when every slot is empty (the static
    batcher, ``step(admit=False)`` between boundaries) completes with
    identical tokens."""
    rng = np.random.default_rng(7)
    trace, arrival = [], 0
    for _ in range(8):
        arrival += int(rng.integers(0, 2))
        trace.append((
            [int(t) for t in rng.integers(1, CFG.vocab_size,
                                          size=int(rng.integers(3, 9)))],
            int(rng.integers(2, 10)), arrival))

    def run(continuous):
        eng = make_engine()
        eng.warm_start()
        reqs = [eng.submit(list(p), max_new_tokens=n, arrival=a)
                for p, n, a in trace]
        it, joined = 0, 0
        while not eng.scheduler.idle():
            busy = eng.scheduler.occupancy()
            eng.step(now=it, admit=continuous or busy == 0)
            joined += bool(busy and eng.scheduler.occupancy() > busy)
            it += 1
        return [r.result(0) for r in reqs], it, joined

    cont, cont_iters, cont_joined = run(True)
    stat, stat_iters, stat_joined = run(False)
    assert cont == stat
    assert [len(g) for g in cont] == [n for _, n, _ in trace]
    # The two schedules really differed: continuous admitted into a
    # running batch and needed no more iterations than static.
    assert cont_joined > 0 and stat_joined == 0
    assert cont_iters <= stat_iters


@pytest.mark.parametrize("capacity", [32, 64])
def test_engine_capacity_finished_rollout_is_bitwise(capacity):
    """A CAPACITY-finished rollout (prompt + max_new_tokens over the
    KV capacity, no earlier EOS) must match the non-incremental
    forward bitwise — both schedulers produce the same tokens either
    way, so only a reference comparison can catch a boundary bug
    here.  The scheduler evicts the moment prompt+generated hits
    capacity, so the deepest decode runs at length == capacity-2 and
    writes [token, dummy] into the view's last two entries;
    forward_step staying exact at length == capacity-1 as well is
    gated by test_decode_at_final_capacity_position_is_bitwise.
    capacity == max_seq_len (64, the engine default) additionally
    exercises the decode block's final-position path end to end."""
    eng = make_engine(capacity=capacity)
    eng.warm_start()
    prompt = [int(t) for t in jax.random.randint(
        jax.random.PRNGKey(7), (capacity - 4,), 0, CFG.vocab_size)]
    req = eng.submit(list(prompt), max_new_tokens=99)
    eng.run_until_idle()
    out = req.result(0)
    assert req.finish_reason == FinishReason.CAPACITY
    assert len(prompt) + len(out) == eng.capacity
    assert out == reference_rollout(prompt, len(out), eng.capacity)


# ---------------------------------------------------------------------------
# Decode's view ladder: the program attends the smallest rung of pages
# that covers the iteration's longest live sequence
# ---------------------------------------------------------------------------

GEOMETRIES = [
    (16, 64, (128, 256, 512, 1024)),
    (8, 32, (64, 128, 256)),
    (16, 16, (128, 256)),
    (16, 24, (128, 256, 384)),  # pages_per_slot is always the last
    (8, 15, (120,)),            # fewer than 16 pages: the one full rung
    (16, 8, (128,)),
    (8, 4, (32,)),
]


@pytest.mark.parametrize("page_size,pages_per_slot,want", GEOMETRIES)
def test_view_rungs_follow_the_page_geometry(page_size, pages_per_slot,
                                             want):
    assert view_rungs(page_size, pages_per_slot) == want


@pytest.mark.parametrize("page_size,pages_per_slot,want", GEOMETRIES)
def test_the_dense_decoder_reads_its_ladder_off_the_shapes_it_holds(
        page_size, pages_per_slot, want):
    """Nobody hands the dense decoder a ladder: its decode program derives
    the rungs from the store's page size and the table's width, and the
    host's ``decode_view`` from the same two numbers, so what the counter
    says a launch rides is what the program picks."""
    from horovod_tpu.serving.models import DenseLM

    cfg = TransformerConfig(vocab_size=97, d_model=64, n_heads=4,
                            n_layers=2, d_ff=128, max_seq_len=1024)
    model = DenseLM(cfg)
    slots = 3
    store = jax.ShapeDtypeStruct(
        (2, 1 + slots * pages_per_slot, page_size, 64), jnp.float32)
    with mock.patch.object(transformer, "view_rung",
                           wraps=transformer.view_rung) as picked:
        jax.eval_shape(
            model.decode,
            jax.eval_shape(lambda: init_transformer(jax.random.PRNGKey(0),
                                                    cfg)),
            (store, store),
            jax.ShapeDtypeStruct((slots, pages_per_slot), jnp.int32),
            jax.ShapeDtypeStruct((slots,), jnp.int32),
            jax.ShapeDtypeStruct((slots,), jnp.int32))
    assert picked.call_args[0][1] == want
    # The host: a batch whose longest sequence just fits a rung rides it.
    for rung in want:
        lengths = np.asarray([rung - 2, 0, -1], np.int32)
        assert model.decode_view(lengths, page_size, pages_per_slot) == rung


@pytest.mark.parametrize("lengths,want", [
    ([126, 3, -1], 0),    # 126 + 2 == 128: the rung itself holds it
    ([127, 3, -1], 1),    # one more does not
    ([3, 254, 40], 1),    # one slot alone picks the rung
    ([255, -1, -1], 2),
    ([510, 510, 510], 2),
    ([-1, 511, -1], 3),
    ([1022, 0, 0], 3),    # the deepest decode the scheduler allows
    ([1023, 0, 0], 3),    # past every rung: the last one (rows drop)
    ([0, -1, -1], 0),
    ([-1, -1, -1], 0),    # all slots inactive
])
def test_view_rung_is_the_smallest_that_holds_the_block(lengths, want):
    rungs = (128, 256, 512, 1024)
    host = np.asarray(lengths, np.int32)
    assert int(view_rung(host, rungs)) == want
    # The traced function is the same function.
    traced = jax.jit(lambda x: view_rung(x, rungs))(jnp.asarray(host))
    assert traced.dtype == jnp.int32 and int(traced) == want
    assert int(view_rung(host, rungs[-1:])) == 0  # one rung: no choice


LCFG = TransformerConfig(vocab_size=97, d_model=64, n_heads=4,
                         n_layers=2, d_ff=128, max_seq_len=256)
LPARAMS = init_transformer(jax.random.PRNGKey(5), LCFG)
# The issue's three-rung engine, and a two-rung one whose last rung is
# the second.  (A 16-long view sums in another order than every longer
# one under XLA:CPU, so no rung here is shorter than 32 tokens.)
LADDERS = {
    "cap256-page8": dict(params=LPARAMS, cfg=LCFG, page_size=8,
                         capacity=256),
    "cap64-page4": dict(params=PARAMS, cfg=CFG, page_size=4,
                        capacity=64),
}


class Ladder:
    """An engine with its ladder, and the same engine forced to the
    full view; shared by the cases of one geometry (a finished request
    frees its slot; a prefix hit is bitwise invisible)."""

    def __init__(self, params, cfg, page_size, capacity):
        self.params, self.cfg = params, cfg
        self.kw = dict(max_slots=3, page_size=page_size,
                       capacity=capacity)
        self.rungs = view_rungs(page_size, capacity // page_size)
        assert len(self.rungs) >= 2 and self.rungs[-1] == capacity
        self.ladder = self.make()
        self.full = self.make(full=True)

    def make(self, full=False):
        """The engine as it is built; ``full``: its decode program traced
        under a ladder of the one full rung (the ladder is derived inside
        ``forward_step_paged``, so that is where it is cut) and the host
        told to count the same."""
        cut = (lambda page_size, pages_per_slot:
               (page_size * pages_per_slot,)) if full else view_rungs
        with mock.patch.object(transformer, "view_rungs", cut):
            eng = InferenceEngine(self.params, self.cfg, **self.kw)
            eng.warm_start()
        if full:
            eng.model.decode_view = (
                lambda lengths, page_size, pages_per_slot:
                page_size * pages_per_slot)
        return eng

    def prompt(self, seed, n):
        return [int(t) for t in jax.random.randint(
            jax.random.PRNGKey(seed), (n,), 0, self.cfg.vocab_size)]

    def view(self, longest):
        """What a decode whose longest sequence holds ``longest``
        tokens has to ride: room for the [token, dummy] block."""
        return next(r for r in self.rungs if r >= longest + 2)


@functools.lru_cache(maxsize=None)
def _ladder(name):
    return Ladder(**LADDERS[name])


# sha256 of the printed jaxpr of the engine's decode program on the CPU,
# taken on the tree BEFORE the engine stopped handing the model its ladder
# (commit 2fe4ef9, ``decode(..., rungs=eng._rungs)``): deriving the rungs
# from the shapes left the program what it was, operation for operation.
DECODE_JAXPR_SHA256 = {
    "cap256-page8":
        "665cdcc258faddfd0943ee1a45089f44bf8b77c24c76497017e4b964d2566e44",
    "cap64-page4":
        "87e9b060453c9dc4dc395388cb3c7a737166569de5f59f339423aaecdc7bc01e",
}


@pytest.mark.parametrize("name", sorted(LADDERS))
def test_the_dense_decode_program_is_the_one_it_was(name):
    eng = _ladder(name).ladder
    table, lengths = eng.cache.device_tables()
    n = len(eng.cache.arrays)

    def fn(params, *rest):
        outs, pages = eng._decode_step(params, rest[:n], *rest[n:])
        return (*outs, *pages)

    text = str(jax.make_jaxpr(fn)(
        eng.params, *eng.cache.arrays, table, lengths, eng._no_tokens,
        eng._no_override))
    assert ".py" not in text                    # no path, no line number
    assert (hashlib.sha256(text.encode()).hexdigest()
            == DECODE_JAXPR_SHA256[name])


@pytest.fixture(params=sorted(LADDERS))
def ladder(request):
    return _ladder(request.param)


def _view_counter():
    import horovod_tpu.telemetry as telemetry

    return telemetry.metrics().get("serving.decode_view_tokens",
                                   {}).get("value", 0)


def _rollout(eng, prompts, max_new):
    """Drive ``prompts`` together; returns the requests and, for each
    decode iteration, its ``{slot: logits row}``, the lengths it ran at
    and its advance of ``serving.decode_view_tokens``."""
    reqs = [eng.submit(list(p), max_new_tokens=n)
            for p, n in zip(prompts, max_new)]
    rows, lengths, views = [], [], []
    orig = eng._decode_iteration

    def wrapped(active):
        before = _view_counter()
        lengths.append(max(eng.cache.length(s) for s, _ in active))
        logits = orig(active)
        views.append(_view_counter() - before)
        rows.append({slot: logits[slot].copy() for slot, _ in active})
        return logits

    eng._decode_iteration = wrapped
    try:
        eng.run_until_idle()
    finally:
        eng._decode_iteration = orig
    return reqs, rows, lengths, views


def _assert_ladder_is_bitwise(lad, prompts, max_new):
    """Returns the requests and the view of every decode iteration."""
    reqs, rows, lengths, views = _rollout(lad.ladder, prompts, max_new)
    freqs, frows, _, fviews = _rollout(lad.full, prompts, max_new)
    # The counter advances by the rung the longest live sequence needs.
    assert views == [lad.view(n) for n in lengths]
    assert fviews == [lad.rungs[-1]] * len(views)
    # Rung by rung the logits are those of the full view...
    assert len(rows) == len(frows)
    for i, (a, b) in enumerate(zip(rows, frows)):
        assert a.keys() == b.keys()
        for slot in a:
            assert a[slot].tobytes() == b[slot].tobytes(), (i, slot)
    assert [r.result(0) for r in reqs] == [r.result(0) for r in freqs]
    # ... and the tokens are the non-incremental forward's greedy
    # ones.  (Its LOGITS are bitwise the engine's only while its own
    # gemms are small under XLA:CPU, a dozen tokens, with the ladder
    # as without it: test_engine_bitwise_vs_noncached_forward_through_
    # executables holds that end.)
    sf = jax.jit(serving_forward, static_argnums=(2, 3))
    for req, prompt in zip(reqs, prompts):
        gen = req.result(0)
        seq = list(prompt) + gen
        ref = np.asarray(sf(lad.params, jnp.asarray([seq], jnp.int32),
                            lad.cfg, lad.ladder.capacity))
        greedy = np.argmax(ref[0, len(prompt) - 1:-1], axis=-1)
        assert gen == [int(t) for t in greedy]
    return reqs, views


@pytest.mark.parametrize("name,across", [
    ("cap256-page8", "first"), ("cap256-page8", "second"),
    ("cap256-page8", "both"), ("cap64-page4", "first")])
def test_engine_rollout_across_a_rung_boundary_is_bitwise(name, across):
    lad = _ladder(name)
    lo, hi = {"first": (0, 1), "second": (1, 2), "both": (0, 2)}[across]
    # The first decode runs at length == len(prompt), and the last
    # length a rung r holds is r - 2: two iterations on the lowest
    # rung, all of those between, two past the last boundary.
    start = lad.rungs[lo] - 3
    want = ([lad.rungs[lo]] * 2
            + [r for r, below in zip(lad.rungs[lo + 1:hi],
                                     lad.rungs[lo:hi])
               for _ in range(r - below)]
            + [lad.rungs[hi]] * 2)
    _, views = _assert_ladder_is_bitwise(
        lad, [lad.prompt(start, start)], [len(want) + 1])
    assert views == want


def test_engine_one_long_slot_alone_forces_the_higher_rung(ladder):
    """Three slots, one of them past the first rung: every slot rides
    the second rung while it lives, and the short ones drop back to
    the first when it has finished."""
    r0, r1 = ladder.rungs[:2]
    prompts = [ladder.prompt(31, 5), ladder.prompt(32, r0 + 6),
               ladder.prompt(33, 9)]
    _, views = _assert_ladder_is_bitwise(ladder, prompts, [9, 4, 7])
    assert views == [r1] * 3 + [r0] * 5


def test_engine_ladder_rollout_that_finishes_at_capacity_is_bitwise(
        ladder):
    cap = ladder.rungs[-1]
    prompt = ladder.prompt(41, cap - 6)
    (req,), views = _assert_ladder_is_bitwise(ladder, [prompt], [99])
    assert req.finish_reason == FinishReason.CAPACITY
    assert len(prompt) + len(req.result(0)) == cap
    assert views == [cap] * 5


def test_engine_ladder_tensor_parallel_matches_single_device():
    """The store's heads sharded over a model axis: each rung's gather
    is on the page axis, inside the conditional, under GSPMD."""
    from horovod_tpu.core.topology import make_mesh

    lad = _ladder("cap256-page8")
    r0, r1 = lad.rungs[:2]
    mesh = make_mesh(data=1, model=2, devices=jax.devices()[:2])
    tp = InferenceEngine(lad.params, lad.cfg, mesh=mesh, **lad.kw)
    assert tp.cache.page_sharding() is not None
    tp.warm_start()
    prompt = lad.prompt(61, r0 - 3)
    (req,), _, _, views = _rollout(tp, [prompt], [6])
    (ref,), _, _, _ = _rollout(lad.ladder, [prompt], [6])
    assert views == [r0] * 2 + [r1] * 3
    assert req.result(0) == ref.result(0)


def test_engine_ladder_is_one_decode_executable(ladder, tmp_path,
                                                monkeypatch):
    """The rung is picked inside the program: one decode executable
    and one manifest entry, whatever rungs the traffic rode."""
    assert ([k for k in ladder.ladder._exec if k[0] == "decode"]
            == [("decode",)])
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path))
    eng = ladder.make()
    assert eng.health()[1]["executables"] == 1
    r0, r1 = ladder.rungs[:2]
    _, _, _, views = _rollout(eng, [ladder.prompt(51, r0 - 2)], [4])
    assert views == [r0, r1, r1]
    bucket = eng._bucket_for(r0 - 2)
    assert sorted(eng._exec) == [("decode",), ("prefill", bucket)]
    assert eng.health()[1]["executables"] == 2
    man = json.loads(
        (tmp_path / "megakernel_manifest.json").read_text())
    kinds = [(e["kind"], e.get("bucket")) for e in man["entries"]
             if e["variant"] == "serving"]
    assert sorted(kinds, key=str) == [("decode", None),
                                      ("prefill", bucket)]


def test_engine_eos_and_sampling_determinism():
    eng = make_engine()
    eng.warm_start()
    ref = reference_rollout([5, 3, 8], 12, eng.capacity)
    # EOS at the first reference token stops generation immediately.
    out = eng.generate([5, 3, 8], max_new_tokens=12, eos_id=ref[0])
    assert out == ref[:1]
    # Temperature sampling is deterministic given (seed, rid, step).
    a = eng.generate([5, 3, 8], max_new_tokens=6, temperature=0.8,
                     seed=11)
    eng3 = make_engine()
    eng3.warm_start()
    b = eng3.generate([5, 3, 8], max_new_tokens=6, temperature=0.8,
                      seed=11)
    assert a == b


def test_engine_one_dispatch_per_decode_iteration():
    """Megakernel-style contract, in two halves: a steady-state decode
    iteration invokes the donated decode executable EXACTLY once
    (gather → forward → scatter is one program), and issues ZERO eager
    XLA launches outside it (eager ops dispatch through the patched
    pjit path and would show up in the record scope; the AOT
    executable's own launch does not)."""
    from horovod_tpu.utils import xla_dispatch

    eng = make_engine()
    eng.warm_start()
    for p in ([1, 2, 3], [4, 5, 6, 7]):
        eng.submit(list(p), max_new_tokens=5)
    eng.step()  # admissions + prefills + decode
    calls = []
    compiled = eng._exec[("decode",)]
    eng._exec[("decode",)] = (
        lambda *a: (calls.append(1) or compiled(*a)))
    with xla_dispatch.exact_scope():
        with xla_dispatch.record(all_threads=True) as scope:
            eng.step()  # steady state: decode only
    assert len(calls) == 1, f"{len(calls)} decode executable calls"
    assert scope.count == 0, (
        f"{scope.count} eager dispatches leaked out of the decode "
        f"executable")
    eng._exec[("decode",)] = compiled
    eng.run_until_idle()


# ---------------------------------------------------------------------------
# The decode loop runs one iteration ahead (ISSUE 30): the token is chosen
# in the program and stays on the device, the host feeds one pass behind
# ---------------------------------------------------------------------------

def _counters(*names):
    import horovod_tpu.telemetry as telemetry

    snap = telemetry.metrics()
    return [snap.get("serving." + n, {}).get("value", 0) for n in names]


def _hold_at_depth_0(eng):
    """The loop as it was: every pass fetches what it launched."""
    eng._runs_ahead = lambda active: False
    return eng


RAGGED = [  # (prompt, max_new_tokens, arrival): joins and leaves mid-flight
    ([5, 3, 8], 9, 0), ([1, 2, 3, 4, 5, 6], 2, 0), ([9, 9, 2, 6], 6, 1),
    ([7, 1], 1, 2), ([4, 4, 4, 4, 4], 7, 3), ([2, 7, 1, 8, 2, 8], 4, 3),
    ([6, 6], 5, 9), ([3, 1, 4, 1, 5, 9, 2, 6, 5, 3], 8, 9)]


def _replay(eng, trace=RAGGED):
    """Drive a trace by its arrival stamps; also holds step()'s
    contract on the way: a request that was decoding before a pass
    has exactly one more token after it."""
    reqs = [eng.submit(list(p), max_new_tokens=n, arrival=a)
            for p, n, a in trace]
    it = 0
    while not eng.scheduler.idle():
        before = {id(r): (r, len(r.generated))
                  for _, r in eng.scheduler.active()}
        eng.step(now=it)
        for r, had in before.values():
            assert len(r.generated) == had + 1, (it, r.rid)
        it += 1
    return [r.result(0) for r in reqs], it


def test_run_ahead_loop_equals_the_loop_held_at_depth_0():
    """Staggered admissions and finishes (by count, at a prefill, into a
    slot another request just left): the same tokens, pass for pass, as
    the loop that fetches before it launches, and as the
    non-incremental forward; and ``serving.decode_ahead`` counts the
    launches made with the previous tokens unfetched — none at depth
    0."""
    names = ("decode_ahead", "decode_iterations", "tokens_generated",
             "prefills")
    eng = make_engine()
    eng.warm_start()
    c0 = _counters(*names)
    ahead, ahead_passes = _replay(eng)
    c1 = _counters(*names)
    held, held_passes = _replay(_hold_at_depth_0(make_engine()))
    c2 = _counters(*names)
    assert ahead == held
    assert ahead == [reference_rollout(p, n, eng.capacity)
                     for p, n, _ in RAGGED]
    d_ahead, d_iter, d_tok, d_pre = (b - a for a, b in zip(c0, c1))
    h_ahead, h_iter, h_tok, h_pre = (b - a for a, b in zip(c1, c2))
    assert (d_tok, d_pre) == (h_tok, h_pre) == (
        sum(n for _, n, _ in RAGGED), len(RAGGED))
    # No ``eos_id`` here, so the host knew every last iteration by
    # count: nothing was launched in vain.  A request admitted behind an
    # iteration in flight decodes one pass later than at depth 0, so
    # the two loops may differ by a pass or an iteration, not by more.
    assert h_ahead == 0 and h_iter == held_passes
    assert d_iter <= h_iter + 3 and ahead_passes <= held_passes + 3
    assert 0.6 * d_iter < d_ahead < d_iter
    assert eng.cache.free_pages() == eng.cache.total_pages
    assert eng._inflight is None


def test_run_ahead_counts_one_start_a_pipeline():
    """One request alone, n tokens: the prefill's, then n - 1 decode
    iterations of which all but the first were launched ahead; the
    first pass launches two and feeds one, the last launches none."""
    eng = make_engine()
    eng.warm_start()
    calls = []
    compiled = eng._exec[("decode",)]
    eng._exec[("decode",)] = lambda *a: (calls.append(1) or compiled(*a))
    a0, i0 = _counters("decode_ahead", "decode_iterations")
    req = eng.submit([5, 3, 8], max_new_tokens=6)
    per_pass = []
    while not eng.scheduler.idle():
        n = len(calls)
        eng.step()
        per_pass.append(len(calls) - n)
    a1, i1 = _counters("decode_ahead", "decode_iterations")
    assert per_pass == [2, 1, 1, 1, 0]
    assert (i1 - i0, a1 - a0) == (5, 4)
    assert req.result(0) == reference_rollout([5, 3, 8], 6, eng.capacity)


def test_eos_under_an_iteration_in_flight_drops_its_token():
    """``eos_id`` is what the host cannot count: the request has ridden
    the next iteration when its last token arrives.  That token is
    dropped, the slot freed, and the next admission takes the SAME
    pages (one slot, four pages: it needs them all) while the stale
    program may still be writing into one; its completion is a fresh
    engine's."""
    first, long_prompt = [1, 2, 3, 4, 5, 6], list(range(1, 27))
    ref = reference_rollout(first, 12, 32)
    k = next(i for i in range(2, 12) if ref[i] not in ref[:i])
    eng = make_engine(max_slots=1, prefix_cache=False)
    eng.warm_start()
    i0, = _counters("decode_iterations")
    a = eng.submit(list(first), max_new_tokens=12, eos_id=ref[k])
    b = eng.submit(list(long_prompt), max_new_tokens=5)
    held = set()
    while not a.done.is_set():
        eng.step()
        held = {int(p) for p in eng.cache.table_row(0)[0] if p} or held
    assert held
    assert a.result(0) == ref[:k + 1]
    assert a.finish_reason == FinishReason.EOS
    # It rode the iteration still in flight; nothing else does.
    assert list(eng._inflight.riders.values()) == [a]
    assert eng.cache.free_pages() == eng.cache.total_pages
    eng.step()      # admits b behind the stale iteration
    assert held < {int(p) for p in eng.cache.table_row(0)[0]}
    eng.run_until_idle()
    i1, = _counters("decode_iterations")
    # a's k fed iterations and the dropped one, then b's.
    assert i1 - i0 == (k + 1) + 4
    fresh = make_engine(max_slots=1, prefix_cache=False)
    fresh.warm_start()
    assert b.result(0) == fresh.generate(list(long_prompt),
                                         max_new_tokens=5)
    assert b.result(0) == reference_rollout(long_prompt, 5, 32)
    assert eng.cache.free_pages() == eng.cache.total_pages


def _sampled_reference(prompt, n, temperature, seed, capacity):
    """The sampled rollout of ``InferenceEngine._sample`` over the
    non-incremental forward: the draw keyed ``(seed, position)``."""
    sf = jax.jit(serving_forward, static_argnums=(2, 3))
    seq, out = list(prompt), []
    for i in range(n):
        row = np.asarray(sf(PARAMS, jnp.asarray([seq], jnp.int32), CFG,
                            capacity))[0, -1]
        z = (row - row.max()) / temperature
        p = np.exp(z)
        p /= p.sum()
        tok = int(np.random.default_rng((seed, i)).choice(len(p), p=p))
        out.append(tok)
        seq.append(tok)
    return out


def test_a_sampled_request_alive_holds_the_pass_synchronous():
    """A ``temperature > 0`` token is a host draw from the logits row:
    while such a request is alive no iteration is launched ahead
    (``serving.decode_ahead`` stands still), its rollout is the one
    its ``(seed, position)`` gives, and the greedy request beside it
    is untouched.  Admitted behind an iteration in flight, it makes
    that pass retire without launching."""
    eng = make_engine()
    eng.warm_start()
    greedy = eng.submit([5, 3, 8], max_new_tokens=12)
    eng.step()
    eng.step()
    assert eng._inflight is not None
    a0, = _counters("decode_ahead")
    hot = eng.submit([1, 2, 3, 4], max_new_tokens=4, temperature=0.8,
                     seed=11)
    n = len(greedy.generated)
    eng.step()                      # prefill; the flight retires
    assert eng._inflight is None
    assert (len(greedy.generated), len(hot.generated)) == (n + 1, 1)
    while not hot.done.is_set():
        eng.step()
        assert eng._inflight is None
    assert _counters("decode_ahead") == [a0]
    eng.run_until_idle()            # alone again, the loop runs ahead
    assert _counters("decode_ahead")[0] > a0
    assert hot.result(0) == _sampled_reference([1, 2, 3, 4], 4, 0.8, 11,
                                               eng.capacity)
    assert greedy.result(0) == reference_rollout([5, 3, 8], 12,
                                                 eng.capacity)
    held = _hold_at_depth_0(make_engine())
    assert hot.result(0) == held.generate(
        [1, 2, 3, 4], max_new_tokens=4, temperature=0.8, seed=11)


@pytest.mark.parametrize("how", ["cancel", "drain", "abort_all"])
def test_evictions_under_an_iteration_in_flight(how):
    """Slots freed at a pass boundary while the device still runs the
    iteration they rode (the donation sanitizer is armed suite-wide):
    the cache comes back whole, the survivors' tokens are the
    reference's, and the freed pages serve the next requests."""
    prompts = [[5, 3, 8], [1, 2, 3, 4, 5, 6], [9, 9, 2, 6]]
    ref = [reference_rollout(p, 10, 32) for p in prompts]
    eng = make_engine()
    eng.warm_start()
    reqs = [eng.submit(list(p), max_new_tokens=10) for p in prompts]
    eng.step()
    eng.step()
    assert [r.rid for r in eng._inflight.riders.values()] == [
        r.rid for r in reqs]
    if how == "cancel":
        assert eng.abort_request(reqs[1]) == "active"
        eng.step()
        assert reqs[1].finish_reason == FinishReason.CLIENT_DISCONNECT
        assert len(reqs[1].generated) == 3      # its token was dropped
        eng.run_until_idle()
        assert [reqs[0].result(0), reqs[2].result(0)] == [ref[0], ref[2]]
    elif how == "drain":
        exported = eng.drain()
        assert eng.cache.free_pages() == eng.cache.total_pages
        assert [len(d["generated_prefix"]) for d in exported] == [3] * 3
        again = eng.import_requests(exported)
        eng.run_until_idle()
        assert [r.result(0) for r in again] == ref
    else:
        assert {r.rid for r in eng.abort_all()} == {r.rid for r in reqs}
        assert eng._inflight is None
        assert eng.cache.free_pages() == eng.cache.total_pages
    assert [eng.generate(list(p), max_new_tokens=10)
            for p in prompts] == ref
    assert eng.cache.free_pages() == eng.cache.total_pages
    assert eng._inflight is None


# ---------------------------------------------------------------------------
# An admission joins the run-ahead pipeline (ISSUE 34): the prefill
# program takes the first token itself and sets it in the token vector the
# next decode launch reads, so that decode is queued before the host has
# seen the token
# ---------------------------------------------------------------------------

@functools.lru_cache(maxsize=None)
def _hybrid():
    """A ``slot_state`` model at toy size (models/hybrid_ssm.py: its
    prefill is told its slot and replaces per-slot stores)."""
    from benchmark import cells
    from benchmark.builders.hybrid_ssm import config_of

    with open(os.path.join(cells.HERE, "tests", "fixtures", "configs",
                           "tiny-phi4flash.json")) as f:
        model = json.load(f)["model"]
    ref = cells.load_module("refs", "phi4-mini-flash")
    return ref.init_params(model, 11), config_of(model)


@functools.lru_cache(maxsize=None)
def _admission_engine(model):
    """One engine a model for the cases below: a case runs on it twice,
    as it is and held synchronous."""
    if model == "dense":
        eng = make_engine()
    else:
        eng = InferenceEngine(*_hybrid(), max_slots=3, page_size=4,
                              capacity=32)
        assert eng.model.slot_state
    eng.warm_start()
    return eng


def _tokens(seed, n):
    return [int(t) for t in
            np.random.default_rng(seed).integers(1, 96, size=n)]


P0, P1, P2 = _tokens(340, 5), _tokens(341, 9), _tokens(342, 3)

# case -> (trace of (prompt, max_new_tokens, arrival), prefills that ride)
ADMISSIONS = {
    # (a) behind an iteration in flight
    "in_flight": ([(P0, 8, 0), (P1, 5, 2)], 2),
    # (b) a start: nothing in flight, nobody alive
    "start": ([(P1, 5, 0)], 1),
    # (c) two in one step, behind an iteration and at a start
    "two_in_flight": ([(P0, 8, 0), (P1, 5, 2), (P2, 4, 2)], 3),
    "two_at_a_start": ([(P1, 5, 0), (P2, 4, 0)], 2),
    # (d) the first token is the last, by count: it does not ride
    "one_token": ([(P0, 6, 0), (P1, 1, 2), (P2, 1, 12)], 1),
    # (e) the first token is ``eos_id``: it has ridden, and is dropped;
    # the next admission takes its slot
    "eos_first": ([(P0, 8, 0), (P1, 6, 2), (P2, 4, 4)], 3),
    "eos_first_at_a_start": ([(P1, 6, 0), (P2, 4, 3)], 2),
    # (f) cancelled between its prefill's enqueue and its fetch (it
    # never rides), and between the launch behind it and the fetch (it
    # has ridden)
    "cancel_at_enqueue": ([(P0, 8, 0), (P1, 6, 2), (P2, 4, 5)], 2),
    "cancel_after_launch": ([(P0, 8, 0), (P1, 6, 2), (P2, 4, 5)], 3),
}


def _drive(eng, trace, eos=None, cancel=None):
    """Replay ``trace``; P1's request ends by ``eos`` where given, and is
    cancelled where ``cancel`` says.  Returns the requests and the
    counters' deltas (prefills, prefill_ahead, tokens)."""
    names = ("prefills", "prefill_ahead", "tokens_generated")
    c0 = _counters(*names)
    reqs = [eng.submit(list(p), max_new_tokens=n, arrival=a,
                       eos_id=eos if p is P1 else None)
            for p, n, a in trace]
    victim = next((r for r in reqs if r.prompt == P1), None)
    orig_prefill, orig_launch = eng._prefill, eng._launch

    def prefill(slot, req, *a, **kw):
        out = orig_prefill(slot, req, *a, **kw)
        if req is victim:
            assert eng.abort_request(req) == "active"
        return out

    def launch(flight, prev):
        out = orig_launch(flight, prev)
        if victim in flight.riders.values() and not victim.cancel_reason:
            assert eng.abort_request(victim) == "active"
        return out

    if cancel == "cancel_at_enqueue":
        eng._prefill = prefill
    elif cancel == "cancel_after_launch":
        eng._launch = launch
    try:
        it = 0
        while not eng.scheduler.idle():
            eng.step(now=it)
            # Every first token is the host's when a pass ends.
            assert eng._fresh == [] and eng._carry is None
            it += 1
        eng.step()      # a stale iteration in flight is dropped
    finally:
        eng._prefill, eng._launch = orig_prefill, orig_launch
    assert eng._inflight is None
    assert eng.cache.free_pages() == eng.cache.total_pages
    return reqs, [b - a for a, b in zip(c0, _counters(*names))]


@pytest.mark.parametrize("case", sorted(ADMISSIONS))
@pytest.mark.parametrize("model", ["dense", "hybrid"])
def test_admission_ahead_equals_the_synchronous_admission(model, case,
                                                          monkeypatch):
    """Greedy completions token for token those of the admission that
    waits for its logits row, ``serving.prefill_ahead`` counting the
    prefills whose successor decode was launched with their token
    unfetched (all of them on an all-greedy trace where none ends at
    its first token by count), the cache whole afterwards."""
    eng = _admission_engine(model)
    trace, rode = ADMISSIONS[case]
    cancel = case if case.startswith("cancel") else None
    eos = None
    if case.startswith("eos"):
        eos = eng.generate(list(P1), max_new_tokens=1)[0]
    ahead, (n_pre, n_ahead, n_tok) = _drive(eng, trace, eos, cancel)
    monkeypatch.setattr(eng, "_runs_ahead", lambda active: False)
    held, (h_pre, h_ahead, h_tok) = _drive(eng, trace, eos, cancel)
    assert (n_pre, h_pre) == (len(trace),) * 2
    assert (n_ahead, h_ahead) == (rode, 0)
    for a, h, (p, n, _) in zip(ahead, held, trace):
        if cancel and p is P1:
            # Cancelled under its prefill, its first token is dropped
            # where the synchronous admission had fed it already;
            # cancelled under the decode behind it, that decode's is
            # (held at depth 0, the pass had fed it too).
            fed = 0 if cancel == "cancel_at_enqueue" else 1
            assert a.generated == h.generated[:fed]
            assert len(h.generated) == 1 + fed
            assert a.finish_reason == h.finish_reason \
                == FinishReason.CLIENT_DISCONNECT
        elif eos is not None and p is P1:
            assert a.generated == h.generated == [eos]
            assert a.finish_reason == h.finish_reason == FinishReason.EOS
        else:
            assert a.result(0) == h.result(0) and len(a.generated) == n
            if model == "dense":
                assert a.result(0) == reference_rollout(p, n, eng.capacity)
    if not cancel:
        assert n_tok == h_tok == sum(len(r.generated) for r in held)
    if case in ("in_flight", "start", "two_in_flight", "two_at_a_start"):
        assert n_ahead == n_pre


def test_a_sampled_request_alive_holds_the_admission_synchronous():
    """``_runs_ahead``'s rule over the admitted request and everyone
    alive: a sampled request (its token is a host draw from the row) is
    admitted synchronously, and so is a greedy one admitted while it is
    alive; ``serving.prefill_ahead`` stands still.  Alone again, the
    next admission rides."""
    eng = make_engine()
    eng.warm_start()
    greedy = eng.submit([5, 3, 8], max_new_tokens=14)
    eng.step()
    eng.step()
    p0, a0 = _counters("prefills", "prefill_ahead")
    hot = eng.submit([1, 2, 3, 4], max_new_tokens=5, temperature=0.8,
                     seed=11)
    eng.step()
    assert len(hot.generated) == 1 and eng._inflight is None
    late = eng.submit([9, 9, 2, 6], max_new_tokens=3)
    eng.step()
    assert not hot.done.is_set() and len(late.generated) == 2
    assert _counters("prefills", "prefill_ahead") == [p0 + 2, a0]
    while not (hot.done.is_set() and late.done.is_set()):
        eng.step()
    last = eng.submit([7, 1], max_new_tokens=3)
    eng.run_until_idle()
    assert _counters("prefills", "prefill_ahead") == [p0 + 3, a0 + 1]
    assert hot.result(0) == _sampled_reference([1, 2, 3, 4], 5, 0.8, 11,
                                               eng.capacity)
    for req, (p, n) in ((greedy, ([5, 3, 8], 14)), (late, ([9, 9, 2, 6], 3)),
                        (last, ([7, 1], 3))):
        assert req.result(0) == reference_rollout(p, n, eng.capacity)


def test_a_prefix_hits_suffix_prefill_rides_as_a_cold_one_does():
    """The suffix prefill over cached prefix pages is the same program
    at another ``start``: its token too goes to the decode behind it on
    the device."""
    header = list(range(1, 18))  # 17 tokens -> 2 full pages published
    ext = header + [40, 41, 42]
    eng = make_engine(prefix_cache=True)
    eng.warm_start()
    names = ("prefills", "prefill_ahead", "prefill_tokens", "prefix_hits")
    c0 = _counters(*names)
    assert eng.generate(list(header), max_new_tokens=5) \
        == reference_rollout(header, 5, 32)
    c1 = _counters(*names)
    assert [b - a for a, b in zip(c0, c1)] == [1, 1, 17, 0]
    assert eng.generate(list(ext), max_new_tokens=5) \
        == reference_rollout(ext, 5, 32)
    assert [b - a for a, b in zip(c1, _counters(*names))] == [1, 1, 4, 1]


@pytest.mark.parametrize("n,bucket", [(17, 32), (4, 4), (5, 8)])
def test_a_model_that_says_nothing_of_its_rows_counts_its_bucket(n, bucket):
    """``serving.prefill_rows`` beside ``serving.prefill_tokens``: the rows
    the prompt program computed are the bucket's, padding and all, unless
    the model has a ``prefill_rows(bucket, n_valid)`` to say otherwise
    (``models/latent_moe.py`` has: tests/test_sparse_latent.py)."""
    eng = make_engine()
    assert not hasattr(eng.model, "prefill_rows")
    c0 = _counters("prefill_tokens", "prefill_rows")
    eng.generate(list(range(1, n + 1)), max_new_tokens=2)
    c1 = _counters("prefill_tokens", "prefill_rows")
    assert [b - a for a, b in zip(c0, c1)] == [n, bucket]


def test_a_prefill_error_surfaces_at_the_fetch_under_its_name(monkeypatch):
    """The prefill is only enqueued at admission, so what its program
    raises arrives with its token: still under ``serving/prefill/
    <bucket>``, and ``abort_all`` still leaves an engine that serves."""
    from horovod_tpu.memory import oom

    named = []
    monkeypatch.setattr(oom, "oom_event",
                        lambda name, exc, predicted=None: named.append(name))

    class Poisoned:
        def __array__(self, *a, **kw):
            raise RuntimeError("RESOURCE_EXHAUSTED: Out of memory while "
                               "trying to allocate 123 bytes")

    eng = make_engine()
    eng.warm_start()
    a = eng.submit([5, 3, 8], max_new_tokens=8)
    eng.step()
    eng.step()
    orig = eng._prefill

    def prefill(slot, req, *args, **kw):
        _, tokens, last = orig(slot, req, *args, **kw)
        return Poisoned(), tokens, last

    eng._prefill = prefill
    b = eng.submit([1, 2, 3, 4], max_new_tokens=4)
    n = len(a.generated)
    with pytest.raises(RuntimeError, match="RESOURCE_EXHAUSTED"):
        eng.step()
    eng._prefill = orig
    assert named == ["serving/prefill/4"]
    # The iteration in flight was retired before the fetch.
    assert len(a.generated) == n + 1 and b.generated == []
    assert {r.rid for r in eng.abort_all()} == {a.rid, b.rid}
    assert eng._fresh == [] and eng._carry is None
    assert eng._inflight is None
    assert eng.cache.free_pages() == eng.cache.total_pages
    assert eng.generate([1, 2, 3, 4], max_new_tokens=4) \
        == reference_rollout([1, 2, 3, 4], 4, eng.capacity)


def test_engine_tensor_parallel_matches_single_device():
    from horovod_tpu.core.topology import make_mesh

    single = make_engine()
    single.warm_start()
    ref = single.generate([2, 7, 1, 8, 2, 8], max_new_tokens=8)
    mesh = make_mesh(data=1, model=2, devices=jax.devices()[:2])
    tp = make_engine(mesh=mesh)
    assert tp.cache.page_sharding() is not None
    tp.warm_start()
    out = tp.generate([2, 7, 1, 8, 2, 8], max_new_tokens=8)
    assert out == ref


def test_engine_warm_start_from_manifest(tmp_path, monkeypatch):
    """Relaunch: the manifest records the serving executables; a fresh
    engine's warm_start rebuilds them BEFORE any request arrives and
    flips readiness, and the rebuilt executables replay bitwise."""
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path))
    e1 = make_engine()
    e1.warm_start()
    out1 = e1.generate([1, 2, 3, 4, 5], max_new_tokens=6)
    man = json.loads(
        (tmp_path / "megakernel_manifest.json").read_text())
    kinds = {(e["kind"], e.get("bucket")) for e in man["entries"]
             if e["variant"] == "serving"}
    assert ("decode", None) in kinds and ("prefill", 8) in kinds

    e2 = make_engine()
    assert not e2.ready
    warmed = e2.warm_start(str(tmp_path))
    assert warmed >= 2 and e2.ready
    assert ("prefill", 8) in e2._exec  # present before any request
    assert e2.generate([1, 2, 3, 4, 5], max_new_tokens=6) == out1


def test_engine_foreign_manifest_entries_are_skipped(tmp_path):
    from horovod_tpu.ops import megakernel as mk

    entry = dict(make_engine()._manifest_identity())
    entry.update(kind="decode", bucket=None)
    entry["model"] = dict(entry["model"], d_model=999)
    mk.record_manifest_entry(entry, str(tmp_path))
    e = make_engine()
    assert e.warm_start(str(tmp_path)) == 0 and e.ready


def test_engine_serving_metrics_flow():
    import horovod_tpu.telemetry as telemetry

    eng = make_engine()
    eng.warm_start()
    before = telemetry.metrics().get("serving.tokens_generated",
                                     {}).get("value", 0)
    eng.generate([4, 4, 4], max_new_tokens=5)
    snap = telemetry.metrics()
    assert snap["serving.tokens_generated"]["value"] == before + 5
    assert snap["serving.ttft_seconds"]["count"] >= 1
    assert snap["serving.token_seconds"]["count"] >= 1


def _iteration_spans(it):
    import horovod_tpu.trace as trace

    return [e for e in trace.export_events()
            if e.get("ph") == "X" and e["args"].get("iter") == it]


def test_engine_iteration_emits_serve_regions_with_one_iter():
    """One engine iteration: serve.iteration > serve.admit,
    serve.prefill, serve.ensure, serve.tables, serve.launch,
    serve.logits_wait, serve.sample — all with the engine's own
    ``iter`` (ISSUE 24).  ``serve.prefill`` opens twice an admission
    that rides ahead (ISSUE 34): around its enqueue and around the
    fetch of its token, which comes after the launch of the decode
    behind it, and after the retirement of the iteration the prefill
    was queued behind."""
    import horovod_tpu.telemetry as telemetry
    import horovod_tpu.trace as trace

    trace.set_enabled(True)
    eng = make_engine()
    eng.warm_start()
    eng.generate([1, 2, 3], max_new_tokens=2)       # compiles
    reqs = [eng.submit([i + 1, 2, 3], max_new_tokens=4)
            for i in range(2)]
    trace.clear()       # ``iter`` is per engine: earlier engines' spans
    before = telemetry.metrics()
    eng.step()
    after = telemetry.metrics()
    spans = _iteration_spans(eng._iter)
    by_name = {}
    for e in spans:
        by_name.setdefault(e["name"], []).append(e)
    assert sorted(by_name) == sorted(
        ["serve.iteration", "serve.admit", "serve.prefill",
         "serve.ensure", "serve.tables", "serve.launch",
         "serve.logits_wait", "serve.sample"])
    (whole,) = by_name["serve.iteration"]
    assert "parent" not in whole["args"] and whole["cat"] == "serve"
    for name, evs in by_name.items():
        if name == "serve.iteration":
            continue
        assert len(evs) == (4 if name == "serve.prefill" else 1), name
        for e in evs:
            assert e["args"]["parent"] == "serve.iteration", name
            assert whole["ts"] <= e["ts"] and (
                whole["ts"] + whole["dur"] >= e["ts"] + e["dur"]), name
    end = lambda e: e["ts"] + e["dur"]      # noqa: E731
    prefills = sorted(by_name["serve.prefill"], key=lambda e: e["ts"])
    assert [e["args"]["rid"] for e in prefills] \
        == [r.rid for r in reqs] * 2
    assert all(e["args"]["prompt_tokens"] == 3 and e["args"]["bucket"] >= 3
               for e in prefills)
    # A start: both prefills enqueued, the two decodes launched behind
    # them, THEN the first tokens fetched, then the first decode's.
    (tables,), (launch,) = by_name["serve.tables"], by_name["serve.launch"]
    (wait,), (sample,) = by_name["serve.logits_wait"], by_name["serve.sample"]
    assert end(prefills[1]) <= tables["ts"] and end(tables) <= launch["ts"]
    assert end(launch) <= prefills[2]["ts"]
    assert end(prefills[3]) <= wait["ts"] and end(wait) <= sample["ts"]
    assert [len(r.generated) for r in reqs] == [2, 2]
    # The decode iteration's regions tile it: serving.token_seconds is
    # fed from the first one's start and the last one's end; the tables
    # and the launch of a pass that fed first tokens ran under the
    # prefills, so it is timed from its wait.
    took = after["serving.token_seconds"]["sum"] \
        - before["serving.token_seconds"]["sum"]
    assert took == pytest.approx((end(sample) - wait["ts"]) / 1e6)
    assert after["trace.span_seconds.serve.iteration"]["count"] \
        - before["trace.span_seconds.serve.iteration"]["count"] == 1
    # The next iteration carries the next iter.  Admitted behind the
    # iteration in flight, a request's token is fetched after that
    # iteration's retirement, and the pass is timed from its tables.
    late = eng.submit([7, 2, 3], max_new_tokens=4)
    before = telemetry.metrics()
    eng.step()
    after = telemetry.metrics()
    by_name = {}
    for e in _iteration_spans(eng._iter):
        by_name.setdefault(e["name"], []).append(e)
    enqueue, fetch = sorted(by_name["serve.prefill"], key=lambda e: e["ts"])
    assert enqueue["args"]["rid"] == fetch["args"]["rid"] == late.rid
    (tables,), (launch,) = by_name["serve.tables"], by_name["serve.launch"]
    (sample,) = by_name["serve.sample"]
    assert end(enqueue) <= tables["ts"] and end(launch) <= fetch["ts"]
    assert end(sample) <= fetch["ts"]
    assert [len(r.generated) for r in reqs + [late]] == [3, 3, 1]
    took = after["serving.token_seconds"]["sum"] \
        - before["serving.token_seconds"]["sum"]
    assert took == pytest.approx((end(sample) - tables["ts"]) / 1e6)
    eng.step()
    assert {e["name"] for e in _iteration_spans(eng._iter)} >= {
        "serve.iteration", "serve.sample"}
    assert not [e for e in _iteration_spans(eng._iter)
                if e["name"] == "serve.prefill"]
    eng.run_until_idle()


# ---------------------------------------------------------------------------
# What each pass was and what each token waited for (ISSUE 36)
# ---------------------------------------------------------------------------

NOTED = {"kind", "admitted", "h2d_bytes", "pages_mapped", "copy_ms"}
# One pass a line: what is submitted before it, and the kind it must be.
SCRIPT = [
    ("a", "start"),         # nothing in flight: two launches, a's 2 tokens
    (None, "steady"),
    (None, "steady"),
    ("b", "admission"),     # b's prefill behind the iteration in flight
    (None, "steady"),
    (None, "steady"),       # b's third token: done
    ("hot", "sync"),        # sampled: admitted synchronously, a retired
    (None, "sync"),
    (None, "sync"),         # hot's third token: done
    (None, "start"),        # a alone again, nothing in flight
    (None, "retire"),       # a's twelfth token
]
SUBMIT = {"a": dict(prompt=[5, 3, 8], max_new_tokens=12),
          "b": dict(prompt=[1, 2, 3, 4], max_new_tokens=3),
          "hot": dict(prompt=[9, 9, 2], max_new_tokens=3, temperature=0.8,
                      seed=11)}


@functools.lru_cache(maxsize=None)
def _moe():
    """A model whose decode program returns counts beside the logits
    (``observe_decode``: models/latent_moe.py) at toy size."""
    from benchmark import cells
    from benchmark.builders.latent_moe import config_of

    with open(os.path.join(cells.HERE, "tests", "fixtures", "configs",
                           "tiny-axk1.json")) as f:
        model = json.load(f)["model"]
    ref = cells.load_module("refs", "axk1-ep16")
    return ref.init_params(model, 7), config_of(model)


def _added(before, after):
    """What a run added: the observations of each kind's histogram, and
    the ``serve.iteration`` regions closed."""
    count = lambda snap, name: snap.get(name, {}).get("count", 0)  # noqa: E731
    names = ["serving.pass_seconds." + k
             for k in ("start", "admission", "steady", "retire", "sync")]
    iterations = "trace.span_seconds.serve.iteration"
    return ({n: count(after, n) - count(before, n) for n in names},
            count(after, iterations) - count(before, iterations))


def _play(eng):
    """SCRIPT on ``eng``: the requests by name, and the registry before
    and after."""
    import horovod_tpu.telemetry as telemetry

    before = telemetry.metrics()
    reqs = {}
    for who, _ in SCRIPT:
        if who:
            reqs[who] = eng.submit(**SUBMIT[who])
        eng.step()
    assert eng.scheduler.idle() and eng._inflight is None
    return reqs, before, telemetry.metrics()


@functools.lru_cache(maxsize=None)
def _scripted(model):
    """SCRIPT traced, then the same with tracing off, on one engine."""
    import horovod_tpu.trace as trace

    if model == "dense":
        eng = make_engine()
    else:
        eng = InferenceEngine(*_moe(), max_slots=3, page_size=8,
                              capacity=64)
        assert hasattr(eng.model, "observe_decode")
    eng.warm_start()
    for kw in SUBMIT.values():      # compiles
        eng.generate(kw["prompt"], max_new_tokens=2)
    trace.set_enabled(True)
    trace.clear()
    first_iter = eng._iter + 1
    reqs, before, after = _play(eng)
    counts, iterations = _added(before, after)
    events = [e for e in trace.export_events() if e.get("ph") == "X"]
    noted = []
    orig = trace._Off.note
    trace._Off.note = lambda self, **kw: noted.append(set(kw))
    trace.set_enabled(False)
    trace.clear()
    try:
        off_reqs, *off = _play(eng)
        off_events = trace.export_events()
    finally:
        trace._Off.note = orig
        trace.set_enabled(True)
    off_counts, off_iterations = _added(*off)
    return types.SimpleNamespace(
        eng=eng, reqs=reqs, counts=counts, iterations=iterations,
        events=events, first_iter=first_iter, before=before, after=after,
        off_reqs=off_reqs, off_counts=off_counts, off_events=off_events,
        off_iterations=off_iterations, noted=noted)


def _named(events, name):
    return sorted((e for e in events if e["name"] == name),
                  key=lambda e: e["ts"])


PASS_MODELS = pytest.mark.parametrize("model", ["dense", "moe"])


@PASS_MODELS
def test_every_pass_has_a_kind_on_its_span_and_in_one_histogram(model):
    """The kinds of SCRIPT, pass for pass, on the ``serve.iteration``
    spans with the requests each admitted; one observation a pass in
    the kind's member of ``serving.pass_seconds``, of the region's own
    seconds; the counts add up to ``serve.iteration``'s."""
    run = _scripted(model)
    kinds = [k for _, k in SCRIPT]
    passes = _named(run.events, "serve.iteration")
    assert [e["args"]["kind"] for e in passes] == kinds
    assert [e["args"]["admitted"] for e in passes] == [
        (run.reqs[who].rid,) if who else () for who, _ in SCRIPT]
    assert [e["args"]["iter"] for e in passes] == list(
        range(run.first_iter, run.first_iter + len(SCRIPT)))
    tables = {e["args"]["iter"]: e for e in _named(run.events,
                                                   "serve.tables")}
    # A pass that only retires plans nothing, and neither does the one
    # that admits synchronously behind an iteration in flight.
    assert [e["args"]["iter"] in tables for e in passes] == [
        k != "retire" and who != "hot" for who, k in SCRIPT]
    for kind in set(kinds):
        assert run.counts["serving.pass_seconds." + kind] \
            == kinds.count(kind), kind
    assert sum(v for k, v in run.counts.items()
               if k.startswith("serving.pass_seconds.")) \
        == run.iterations == len(SCRIPT)
    # The region's own clock reads: the one admission pass.
    (adm,) = [e for e in passes if e["args"]["kind"] == "admission"]
    name = "serving.pass_seconds.admission"
    took = run.after[name]["sum"] - run.before.get(name, {}).get("sum", 0.0)
    assert took == pytest.approx(adm["dur"] / 1e6)


@PASS_MODELS
def test_every_gap_counts_the_prefills_it_waited_out(model):
    """``itl_admissions`` beside ``itl_ms``: a prefill enqueued behind
    the iteration in flight is fetched after that iteration's feed, so
    it lies in the gap that ENDS in the pass after the admission's; a
    synchronous one in the gap that ends in its own pass.  Over a
    request they add up to the first tokens other requests got between
    its first and its last."""
    run = _scripted(model)
    spans = {e["args"]["rid"]: e["args"]
             for e in _named(run.events, "serving.request")}
    reqs = list(run.reqs.values())
    assert sorted(spans) == sorted(r.rid for r in reqs)
    for r in reqs:
        args = spans[r.rid]
        held = args["itl_admissions"]
        assert len(held) == len(args["itl_ms"]) == len(r.generated) - 1
        assert sum(held) == sum(
            r.t_first_token < o.t_first_token <= r.t_done for o in reqs)
        assert len(r.token_prefills) == len(r.token_times)
    a = spans[run.reqs["a"].rid]["itl_admissions"]
    # a's tokens: 2 in the start, 1 a pass; b's prefill is fetched at
    # the end of pass 4 (gap 5 -> 6), hot's inside pass 7 (gap 7 -> 8).
    assert a == [0, 0, 0, 0, 1, 0, 1, 0, 0, 0, 0]


@PASS_MODELS
def test_a_request_joins_its_prefill_regions_and_its_passes(model):
    """``rid`` with ``admit_iter``, ``first_iter`` and ``last_iter``:
    the request's ``serve.prefill`` regions lie in the iterations from
    its admission to its first token, and each of those iterations
    names it under ``admitted`` or served it."""
    run = _scripted(model)
    passes = {e["args"]["iter"]: e["args"]
              for e in _named(run.events, "serve.iteration")}
    for who, r in run.reqs.items():
        (args,) = [e["args"] for e in _named(run.events, "serving.request")
                   if e["args"]["rid"] == r.rid]
        assert args["admit_iter"] <= args["first_iter"] <= args["last_iter"]
        assert (args["admit_iter"], args["first_iter"]) == (
            r.admit_iter, r.first_iter)
        assert r.rid in passes[args["admit_iter"]]["admitted"]
        assert args["last_iter"] in passes
        prefills = [e["args"]["iter"]
                    for e in _named(run.events, "serve.prefill")
                    if e["args"]["rid"] == r.rid]
        assert len(prefills) == (1 if who == "hot" else 2)
        assert all(args["admit_iter"] <= it <= args["first_iter"]
                   for it in prefills)


@PASS_MODELS
def test_the_tables_span_says_what_it_copied(model):
    """A steady pass sends the page table and the lengths; a start
    plans twice; a rider whose token only the host holds adds the
    override.  The counter takes what the spans say."""
    run = _scripted(model)
    kind_of = {e["args"]["iter"]: e["args"]["kind"]
               for e in _named(run.events, "serve.iteration")}
    table, lengths = run.eng.cache.host_tables()
    two = table.nbytes + lengths.nbytes
    tables = _named(run.events, "serve.tables")
    for e in tables:
        args = e["args"]
        assert NOTED - {"kind", "admitted"} <= set(args)
        kind = kind_of[args["iter"]]
        if kind in ("steady", "admission"):
            assert args["h2d_bytes"] == two
        elif kind == "start":
            assert args["h2d_bytes"] in (2 * two, 2 * two + lengths.nbytes)
        else:   # depth 0: every rider's token goes through the override
            assert args["h2d_bytes"] == two + lengths.nbytes
        assert 0.0 <= args["copy_ms"] <= e["dur"] / 1e3 + 1e-3
    # a passes a page boundary (3 prompt tokens, 12 more, pages of 8),
    # mapped by the plan that runs ahead of it.
    assert sum(e["args"]["pages_mapped"] for e in tables) >= 1
    counter = "serving.tables_h2d_bytes"
    assert run.after[counter]["value"] - run.before.get(
        counter, {}).get("value", 0) == sum(e["args"]["h2d_bytes"]
                                            for e in tables)


@PASS_MODELS
def test_tracing_off_builds_no_argument_and_serves_the_same_tokens(model):
    """``HVD_TPU_TRACE=0`` (here its runtime switch): no span, none of
    this issue's arguments built, the served tokens the same; the
    histograms a kind are the registry's and count on, from the two
    clock reads a ``timed`` region still takes."""
    run = _scripted(model)
    assert run.off_events == []
    assert not [kw & NOTED for kw in run.noted if kw & NOTED]
    for who, r in run.reqs.items():
        assert run.off_reqs[who].result(0) == r.result(0)
        assert np.diff(run.off_reqs[who].token_prefills).tolist() \
            == np.diff(r.token_prefills).tolist()
    assert run.off_counts == run.counts
    assert run.off_iterations == 0


@PASS_MODELS
def test_the_readers_read_what_the_loop_wrote(model):
    """benchmark/metrics' readers of the kinds on the scripted run's own
    counters and spans (CPU: the arithmetic, never a speed)."""
    from benchmark import cells

    run = _scripted(model)
    rec = types.SimpleNamespace(
        counters_before=run.before, counters_after=run.after,
        requests=list(run.reqs.values()), peaks=None)
    rec.counter_delta = lambda name, field="value": (
        run.after.get(name, {}).get(field, 0)
        - run.before.get(name, {}).get(field, 0))
    read = lambda name, **kw: cells.load_module(  # noqa: E731
        "metrics", name).read(rec, **kw)
    passes = _named(run.events, "serve.iteration")
    ms = lambda kind: [e["dur"] / 1e3 for e in passes  # noqa: E731
                       if e["args"]["kind"] == kind]
    assert read("steady_pass_ms") == pytest.approx(np.mean(ms("steady")))
    assert read("admission_pass_ms") == pytest.approx(
        np.mean(ms("admission")))
    assert read("admission_time_pct") == pytest.approx(
        100.0 * sum(ms("admission") + ms("start"))
        / sum(e["dur"] / 1e3 for e in passes))
    # The passes that admitted and planned: a's start and b's admission
    # (hot's synchronous one planned nothing; the second start admitted
    # nobody).
    carried = {e["args"]["iter"] for e in passes if e["args"]["admitted"]}
    tables = _named(run.events, "serve.tables")
    after = [e["dur"] / 1e3 for e in tables if e["args"]["iter"] in carried]
    assert len(after) == 2
    assert read("tables_after_admission_ms", events=run.events) \
        == pytest.approx(np.mean(after))
    assert read("tables_h2d_kb_per_pass") == pytest.approx(
        sum(e["args"]["h2d_bytes"] for e in tables) / 1e3 / len(tables))
    spans = _named(run.events, "serving.request")
    owed = [sum(g for g, n in zip(e["args"]["itl_ms"],
                                  e["args"]["itl_admissions"]) if n)
            / (e["args"]["tokens"] - 1) for e in spans]
    assert max(owed) > 0.0 and min(owed) == 0.0
    from benchmark import loadgen

    assert read("tpot_admission_p90_ms", spans=spans) == pytest.approx(
        loadgen.percentile(owed, 90))
    # No peak table on the CPU: the roofline reads nothing, and says so.
    assert read("steady_decode_hbm_roofline") is None


def test_request_token_times_one_stamp_per_token_on_one_clock():
    import horovod_tpu.telemetry as telemetry
    import horovod_tpu.trace as trace

    trace.set_enabled(True)
    eng = make_engine(max_slots=2)
    eng.warm_start()
    trace.clear()       # ``rid`` is per scheduler
    q0 = telemetry.metrics().get("serving.queue_wait_seconds",
                                 {}).get("count", 0)
    reqs = [eng.submit([i + 1, 2, 3], max_new_tokens=5) for i in range(3)]
    eng.run_until_idle()
    for r in reqs:
        assert len(r.token_times) == len(r.generated) == 5
        assert r.token_times == sorted(r.token_times)
        assert r.token_times[0] == r.t_first_token
        assert r.token_times[-1] == r.t_done
        assert r.t_submit <= r.t_admit <= r.t_first_token
    # The third request waited for a slot: its wait is in its stamps,
    # and every admitted request was observed exactly once.
    assert reqs[2].t_admit > reqs[0].t_admit
    assert telemetry.metrics()["serving.queue_wait_seconds"]["count"] \
        == q0 + 3
    # The request's span sits on the stamps' clock and carries the
    # gaps: no second clock, no arithmetic between two.
    spans = {e["args"]["rid"]: e for e in trace.export_events()
             if e["name"] == "serving.request"}
    for r in reqs:
        ev = spans[r.rid]
        assert ev["ts"] == pytest.approx(r.t_submit * 1e6)
        assert ev["dur"] == pytest.approx((r.t_done - r.t_submit) * 1e6)
        assert len(ev["args"]["itl_ms"]) == 4
        assert ev["args"]["itl_ms"] == pytest.approx(
            [(b - a) * 1e3 for a, b in
             zip(r.token_times, r.token_times[1:])], abs=1e-3)
        assert ev["args"]["queue_ms"] == pytest.approx(
            (r.t_admit - r.t_submit) * 1e3, abs=1e-3)


def test_generate_returns_token_ms_one_offset_per_token():
    cfg = TransformerConfig(vocab_size=256, d_model=64, n_heads=4,
                            n_layers=2, d_ff=128, max_seq_len=64)
    params = init_transformer(jax.random.PRNGKey(5), cfg)
    engine = InferenceEngine(params, cfg, max_slots=2, page_size=8,
                             capacity=32)
    with LMServer(engine, port=0) as srv:
        srv.start()
        base = f"http://127.0.0.1:{srv.port}"
        status, resp = _post(base + "/generate",
                             {"tokens": [1, 2, 3], "max_tokens": 6})
    assert status == 200
    assert len(resp["token_ms"]) == len(resp["tokens"]) == 6
    assert resp["token_ms"] == sorted(resp["token_ms"])
    assert resp["token_ms"][0] == resp["ttft_ms"]
    assert resp["token_ms"][-1] <= resp["total_ms"]


# ---------------------------------------------------------------------------
# HTTP front door on the shared exporter (route registry)
# ---------------------------------------------------------------------------

def _get(url, timeout=10):
    with urllib.request.urlopen(url, timeout=timeout) as r:
        return r.status, json.loads(r.read())


def _post(url, payload, timeout=60):
    req = urllib.request.Request(
        url, data=json.dumps(payload).encode(),
        headers={"Content-Type": "application/json"}, method="POST")
    with urllib.request.urlopen(req, timeout=timeout) as r:
        return r.status, json.loads(r.read())


def test_route_registry_dispatch_and_health_contributors():
    from horovod_tpu.telemetry import exporter as tel_exporter
    from horovod_tpu.telemetry.registry import MetricsRegistry

    routes = tel_exporter.routes()
    calls = []

    def handler(query, body):
        calls.append((query, body))
        return 200, b'{"pong": true}', "application/json"

    routes.register("/ping", handler, methods=("GET", "POST"))
    routes.register_health("unit", lambda: (False, {"why": "testing"}))
    exp = tel_exporter.start_exporter(MetricsRegistry(), 0,
                                      host="127.0.0.1")
    try:
        base = f"http://127.0.0.1:{exp.port}"
        status, body = _get(base + "/ping?x=1")
        assert status == 200 and body["pong"] is True
        assert calls[0][0] == "x=1"
        # A not-ready contributor makes /healthz NOT_READY with 503.
        try:
            _get(base + "/healthz")
            pytest.fail("expected 503")
        except urllib.error.HTTPError as e:
            assert e.code == 503
            payload = json.loads(e.read())
            assert payload["status"] == "NOT_READY"
            assert payload["unit"] == {"why": "testing"}
        routes.register_health("unit", lambda: (True, {"ok": 1}))
        status, payload = _get(base + "/healthz")
        assert status == 200 and payload["status"] == "ok"
    finally:
        exp.close()
        routes.unregister("/ping")
        routes.unregister_health("unit")


def test_lmserver_generate_http_and_readiness():
    """/healthz NOT_READY before warm start; /generate answers with the
    engine's exact completion plus latency fields; /metrics shares the
    same listener (route registry, not a second port)."""
    from horovod_tpu.telemetry import exporter as tel_exporter

    cfg = TransformerConfig(vocab_size=256, d_model=64, n_heads=4,
                            n_layers=2, d_ff=128, max_seq_len=64)
    params = init_transformer(jax.random.PRNGKey(5), cfg)
    ref_engine = InferenceEngine(params, cfg, max_slots=2, page_size=8,
                                 capacity=32)
    ref_engine.warm_start()
    prompt = list(b"hi")
    ref = ref_engine.generate(prompt, max_new_tokens=6)

    engine = InferenceEngine(params, cfg, max_slots=2, page_size=8,
                             capacity=32)
    # Readiness before warm start: register health only, probe, then
    # start (LMServer.start warm-starts synchronously).
    routes = tel_exporter.routes()
    routes.register_health("serving", engine.health)
    exp = tel_exporter.start_exporter(
        __import__("horovod_tpu.telemetry", fromlist=["x"]).registry(),
        0, host="127.0.0.1")
    base = f"http://127.0.0.1:{exp.port}"
    try:
        try:
            _get(base + "/healthz")
            pytest.fail("expected NOT_READY before warm_start")
        except urllib.error.HTTPError as e:
            assert e.code == 503
            assert json.loads(e.read())["serving"]["ready"] is False

        with LMServer(engine) as srv:
            srv.start()
            status, health = _get(base + "/healthz")
            assert status == 200 and health["serving"]["ready"] is True
            status, resp = _post(base + "/generate",
                                 {"text": "hi", "max_tokens": 6})
            assert status == 200
            assert resp["tokens"] == ref
            assert resp["finish_reason"] == "max_new_tokens"
            assert resp["ttft_ms"] is not None and resp["total_ms"] > 0
            assert isinstance(resp.get("text"), str)
            # Token-id prompts hit the same path.
            status, resp2 = _post(base + "/generate",
                                  {"tokens": prompt, "max_tokens": 6})
            assert resp2["tokens"] == ref
            # Error paths: bad JSON / no prompt / out-of-vocab ids.
            for payload in ({}, {"tokens": [999999]},):
                try:
                    _post(base + "/generate", payload)
                    pytest.fail("expected 400")
                except urllib.error.HTTPError as e:
                    assert e.code == 400
            # Drained admission is a retryable 503, not a client 400.
            engine.scheduler.drain()
            try:
                _post(base + "/generate", {"tokens": prompt})
                pytest.fail("expected 503 while draining")
            except urllib.error.HTTPError as e:
                assert e.code == 503
            engine.scheduler.resume()
            status, resp3 = _post(base + "/generate",
                                  {"tokens": prompt, "max_tokens": 6})
            assert resp3["tokens"] == ref
            # /metrics still served by the same listener.
            status, snap = _get(base + "/metrics?format=json")
            assert status == 200
            assert "serving.tokens_generated" in snap
    finally:
        exp.close()
        routes.unregister_health("serving")


def test_engine_abort_all_fails_everything_and_reopens():
    """abort_all (the serve loop's recovery): every queued AND
    in-flight request is failed with finish_reason='error' and done
    set, the KV pages are recycled, and admission re-opens — the
    returned list is exactly what the drain removed, so a submission
    racing the recovery is failed fast instead of silently lost."""
    eng = make_engine()
    eng.warm_start()
    reqs = [eng.submit([i + 1, 2, 3], max_new_tokens=8)
            for i in range(4)]  # 3 slots -> one stays queued
    eng.step()
    assert eng.scheduler.occupancy() == 3
    failed = eng.abort_all()
    assert {r.rid for r in failed} == {r.rid for r in reqs}
    for r in reqs:
        assert r.done.is_set() and r.finish_reason == FinishReason.ERROR
    assert eng.cache.free_pages() == eng.cache.n_pages - 1
    assert eng.generate([1, 2], max_new_tokens=2)  # admission re-open


def test_follow_applies_abort_marker_and_abort_all_broadcasts_it():
    """Multi-host recovery: abort_all broadcasts an abort marker, and a
    follower receiving it (here scripted as the post-prefill sync of a
    step that died on rank 0) frees its whole cache mirror — without
    this the fleet's caches diverge after a poisoned step and every
    later decode breaks the bitwise contract."""
    eng = make_engine()
    msgs = [{"stop": False, "admit": [(0, [1, 2, 3])]}, {"abort": True}]
    eng._bcast = lambda obj: msgs.pop(0)
    assert eng.follow() is True
    assert msgs == [] and eng.cache.length(0) < 0
    assert eng.cache.free_pages() == eng.cache.n_pages - 1
    # Rank-0 side: abort_all under a live control plane broadcasts the
    # marker so blocked followers unblock into the same recovery.
    eng2 = make_engine()
    sent = []
    eng2._multiprocess = lambda: True
    eng2._bcast = lambda obj: sent.append(obj)
    eng2.submit([1, 2, 3], max_new_tokens=4)
    eng2.abort_all()
    assert {"abort": True} in sent


def test_lmserver_survives_engine_exception_and_keeps_serving():
    """Error recovery: one poisoned step fails every caught-up request
    FAST as an HTTP 500 with finish_reason='error' (not 'drained', not
    a timeout, not a 200 masquerading as success), frees the KV slots,
    and the server keeps serving new requests — slot 0 must be reusable
    (regression: a recovery that drained only the scheduler left the
    cache slots mapped and bricked admission)."""
    engine = make_engine()
    with LMServer(engine, port=0) as srv:
        srv.start()
        base = f"http://127.0.0.1:{srv.port}"
        boom = {"armed": True}
        orig = engine._decode_iteration

        def poisoned(active):
            if boom["armed"]:
                boom["armed"] = False
                raise RuntimeError("injected decode failure")
            return orig(active)

        engine._decode_iteration = poisoned
        try:
            _post(base + "/generate",
                  {"tokens": [1, 2, 3], "max_tokens": 6,
                   "timeout": 30})
            pytest.fail("expected HTTP 500 for the failed request")
        except urllib.error.HTTPError as e:
            assert e.code == 500
            resp = json.loads(e.read())
        assert resp["finish_reason"] == "error", resp
        # The server is healthy again: same slot serves a new request.
        status, resp2 = _post(base + "/generate",
                              {"tokens": [1, 2, 3], "max_tokens": 6})
        assert status == 200
        assert resp2["finish_reason"] == "max_new_tokens"
        ref = make_engine()
        ref.warm_start()
        assert resp2["tokens"] == ref.generate([1, 2, 3],
                                               max_new_tokens=6)


def test_lmserver_midflight_drain_returns_retryable_503():
    """An elastic drain evicting an in-flight request must surface to
    its blocked /generate handler as a retryable 503 with the partial
    tokens and finish_reason='drained' — never a 200 that only
    finish_reason distinguishes from success (the docs/inference.md
    failure-status contract)."""
    engine = make_engine()
    with LMServer(engine, port=0) as srv:
        srv.start()
        base = f"http://127.0.0.1:{srv.port}"
        orig = engine._decode_iteration

        def draining(active):
            engine._decode_iteration = orig
            engine.drain()  # mid-flight eviction, continuation exported

        engine._decode_iteration = draining
        try:
            _post(base + "/generate",
                  {"tokens": [1, 2, 3], "max_tokens": 6, "timeout": 30})
            pytest.fail("expected HTTP 503 for the drained request")
        except urllib.error.HTTPError as e:
            assert e.code == 503
            resp = json.loads(e.read())
        assert resp["finish_reason"] == "drained", resp
        # Resume (the relaunch path) and the same server serves again.
        engine.import_requests([])
        status, resp2 = _post(base + "/generate",
                              {"tokens": [1, 2, 3], "max_tokens": 6})
        assert status == 200
        assert resp2["finish_reason"] == "max_new_tokens"


def test_lmserver_client_disconnect_releases_slot(monkeypatch):
    """hvd-chaos satellite (ISSUE 9): a client that vanishes
    mid-generation is detected by the handler's ClientProbe (the
    serving.disconnect injection site), the slot is released through
    the abort path, serving.client_disconnects counts it, and the SAME
    slot serves the next request normally."""
    import horovod_tpu.chaos as chaos
    import horovod_tpu.telemetry as tel

    engine = make_engine(max_slots=1)
    with LMServer(engine, port=0) as srv:
        srv.start()
        base = f"http://127.0.0.1:{srv.port}"
        before = tel.metrics().get("serving.client_disconnects",
                                   {}).get("value", 0)
        monkeypatch.setenv("HVD_TPU_FAULTS",
                           "serving.disconnect:count=1@7")
        chaos.reload()
        try:
            try:
                _post(base + "/generate",
                      {"tokens": [1, 2, 3], "max_tokens": 400,
                       "timeout": 30})
                pytest.fail("expected HTTP 499 for the gone client")
            except urllib.error.HTTPError as e:
                assert e.code == 499
                resp = json.loads(e.read())
            assert "disconnected" in resp["error"]
        finally:
            monkeypatch.delenv("HVD_TPU_FAULTS", raising=False)
            chaos.reload()
        after = tel.metrics().get("serving.client_disconnects",
                                  {}).get("value", 0)
        assert after - before >= 1
        # The slot was released at the loop boundary: the one-slot
        # engine admits (and completes) a fresh request.
        status, resp2 = _post(base + "/generate",
                              {"tokens": [1, 2, 3], "max_tokens": 6,
                               "timeout": 30})
        assert status == 200
        assert resp2["finish_reason"] == "max_new_tokens"
        deadline = _time_monotonic_deadline(5.0)
        while engine.scheduler.occupancy() and not deadline():
            pass
        assert engine.scheduler.occupancy() == 0


def _time_monotonic_deadline(seconds):
    import time as _t

    end = _t.monotonic() + seconds
    return lambda: _t.monotonic() > end


def test_lmserver_concurrent_http_requests():
    cfg = TransformerConfig(vocab_size=256, d_model=64, n_heads=4,
                            n_layers=2, d_ff=128, max_seq_len=64)
    params = init_transformer(jax.random.PRNGKey(5), cfg)
    engine = InferenceEngine(params, cfg, max_slots=2, page_size=8,
                             capacity=32)
    with LMServer(engine, port=0) as srv:
        srv.start()
        base = f"http://127.0.0.1:{srv.port}"
        results = {}

        def hit(i):
            results[i] = _post(base + "/generate",
                               {"tokens": [i + 1, 2, 3],
                                "max_tokens": 5})[1]["tokens"]

        threads = [threading.Thread(target=hit, args=(i,))
                   for i in range(4)]
        for t in threads:
            t.start()
        for t in threads:
            t.join(timeout=60)
        ref_engine = InferenceEngine(params, cfg, max_slots=2,
                                     page_size=8, capacity=32)
        ref_engine.warm_start()
        for i in range(4):
            assert results[i] == ref_engine.generate(
                [i + 1, 2, 3], max_new_tokens=5), i


# ---------------------------------------------------------------------------
# Elastic drain / resume
# ---------------------------------------------------------------------------

def test_elastic_serving_state_drain_commit_resume(tmp_path, monkeypatch):
    """Fleet resize: drain mid-generation, commit, 'relaunch' a fresh
    engine, resume — completions equal the uninterrupted run exactly
    (greedy continuations ride the bitwise contract)."""
    from horovod_tpu import elastic

    monkeypatch.setenv("HVD_TPU_ELASTIC_DIR", str(tmp_path))
    prompts = [[3, 1, 4, 1, 5], [9, 2, 6, 5, 3, 5], [2, 7, 1, 8]]
    e0 = make_engine()
    e0.warm_start()
    ref = [e0.generate(list(p), max_new_tokens=10) for p in prompts]

    e1 = make_engine()
    e1.warm_start()
    for p in prompts:
        e1.submit(list(p), max_new_tokens=10)
    state = elastic.ServingState(e1)
    for _ in range(4):  # some in flight, queue possibly nonempty
        e1.step()
    exported = state.drain_commit()
    assert state.wait_committed()
    assert len(exported) == 3
    assert any(x["generated_prefix"] for x in exported)  # mid-flight

    e2 = make_engine()
    e2.warm_start()
    state2 = elastic.ServingState(e2)
    state2.sync()  # loads the disk commit and resubmits
    pend = e2.scheduler.pending()
    assert len(pend) == 3
    e2.run_until_idle()
    results = sorted(tuple(r.result(0)) for r in pend)
    assert results == sorted(map(tuple, ref))


def test_engine_drain_with_nothing_in_flight_is_empty():
    eng = make_engine()
    eng.warm_start()
    assert eng.drain() == []
    eng.import_requests([])  # resume with nothing
    assert eng.generate([1, 2], max_new_tokens=2)  # still serves


def test_import_requests_attaches_prefix_before_admissible():
    """A relaunched continuation's generated_prefix must be on the
    Request BEFORE it enters the queue: a live serve loop can admit
    and sample it immediately, and the sampling rng keys on
    len(prefix) + len(generated) — a late prefix assignment would draw
    from the wrong rng position and break continuation determinism."""
    eng = make_engine()
    eng.warm_start()
    seen = []
    orig_submit = eng.scheduler.submit

    def spy(req):
        seen.append(list(req.prefix))
        return orig_submit(req)

    eng.scheduler.submit = spy
    eng.import_requests([{"prompt": [1, 2, 3, 9],
                          "generated_prefix": [9],
                          "max_new_tokens": 4, "seed": 1,
                          "temperature": 0.7}])
    assert seen == [[9]]


def test_import_requests_skips_unresumable_continuations():
    """A resize can SHRINK capacity; a drained continuation whose
    prompt no longer fits must be skipped (flight-recorder event), not
    abort the import loop and silently drop the rest of the committed
    export behind it."""
    eng = make_engine()  # capacity 64
    oversized = {"prompt": list(range(eng.capacity + 4)),
                 "generated_prefix": [], "max_new_tokens": 8}
    ok = {"prompt": [1, 2, 3], "generated_prefix": [9],
          "max_new_tokens": 4}
    out = eng.import_requests([oversized, ok])
    assert len(out) == 1 and out[0].prompt == [1, 2, 3]
    assert out[0].prefix == [9]


def test_engine_drain_finishes_pending_requests_fast():
    """engine.drain() must also finish queued-but-unadmitted requests
    (finish_reason='drained', done set): the relaunch resubmits NEW
    Request objects from the export, so a /generate handler blocked on
    the original would otherwise hang to its client timeout instead of
    failing fast as a retryable 503."""
    eng = make_engine()
    eng.warm_start()
    reqs = [eng.submit([i + 1, 2, 3], max_new_tokens=8)
            for i in range(4)]  # 3 slots -> one stays queued
    eng.step()
    exported = eng.drain()
    assert len(exported) == 4  # pending still exported for relaunch
    for r in reqs:
        assert r.done.is_set(), r.rid
        assert r.finish_reason == FinishReason.DRAINED


def test_engine_abort_all_survives_dead_control_plane():
    """A control-plane fault that poisoned the step must not also kill
    the recovery: abort_all's abort broadcast failing is swallowed and
    the LOCAL drain/fail/reopen still completes."""
    eng = make_engine()
    eng.warm_start()
    eng._multiprocess = lambda: True

    def dead_bcast(obj):
        raise ConnectionError("control plane down")

    eng._bcast = dead_bcast
    req = eng.submit([1, 2, 3], max_new_tokens=4)
    failed = eng.abort_all()
    assert req in failed and req.finish_reason == FinishReason.ERROR
    eng._multiprocess = lambda: False
    assert eng.generate([1, 2], max_new_tokens=2)  # admission re-open


def test_engine_warm_start_none_keeps_chosen_manifest_dir(tmp_path):
    """warm_start(None) after warm_start(dir) must keep recording to
    dir (a later default-argument call — e.g. LMServer.start() with no
    warm_start_dir — must not silently revert to the env default)."""
    eng = make_engine()
    eng.warm_start(str(tmp_path))
    eng.warm_start()
    assert eng._manifest_dir == str(tmp_path)
    eng.generate([1, 2, 3], max_new_tokens=2)
    man = json.loads((tmp_path / "megakernel_manifest.json").read_text())
    assert any(e["variant"] == "serving" for e in man["entries"])


# ---------------------------------------------------------------------------
# Serving checkpoint export/load
# ---------------------------------------------------------------------------

def test_serving_checkpoint_roundtrip(tmp_path):
    from horovod_tpu.utils.checkpoint import (load_serving_checkpoint,
                                              save_serving_checkpoint)

    save_serving_checkpoint(str(tmp_path), PARAMS, CFG, block=True)
    params, cfg, meta = load_serving_checkpoint(str(tmp_path))
    assert cfg.vocab_size == CFG.vocab_size
    assert cfg.n_layers == CFG.n_layers
    assert meta["tokenizer"]["kind"] == "byte"
    same = all(
        np.asarray(a).tobytes() == np.asarray(b).tobytes()
        for a, b in zip(jax.tree_util.tree_leaves(PARAMS),
                        jax.tree_util.tree_leaves(params)))
    assert same
    # And the loaded checkpoint actually serves.
    eng = InferenceEngine(params, cfg, max_slots=2, page_size=8,
                          capacity=32)
    eng.warm_start()
    ref_eng = make_engine()
    ref_eng.warm_start()
    assert (eng.generate([1, 2, 3], max_new_tokens=4)
            == ref_eng.generate([1, 2, 3], max_new_tokens=4))


# ---------------------------------------------------------------------------
# Shared-prefix cache at the engine level (hvd-spec)
# ---------------------------------------------------------------------------

def test_engine_prefix_hit_is_bitwise_and_saves_prefill():
    """The tentpole gate: a prompt extending a cached prefix maps the
    shared pages copy-free, prefills ONLY the suffix, and the
    completion is bitwise-equal to the cache-off engine's (and the
    non-incremental reference)."""
    from horovod_tpu import telemetry as _telemetry

    def counter(name):
        return _telemetry.metrics().get(name, {}).get("value", 0)

    header = list(range(1, 18))  # 17 tokens -> 2 full pages published
    ext = header + [40, 41, 42]
    # Ground truth: the non-incremental reference — a cache-off engine
    # equals it by the standing contract.
    a_off = reference_rollout(header, 5, 32)
    b_off = reference_rollout(ext, 5, 32)

    on = make_engine(prefix_cache=True)
    on.warm_start()
    assert on.generate(list(header), max_new_tokens=5) == a_off
    assert on.cache.prefix_stats()["cached_pages"] == 2
    hits0 = counter("serving.prefix_hits")
    pages0 = counter("serving.prefix_pages_shared")
    # Capture the suffix prefill's width: with 16 tokens shared, the
    # 4-token suffix rides the 4-bucket, not the 32-bucket.
    widths = []
    orig = on._prefill_exec

    def spy(bucket, draft=False):
        widths.append(bucket)
        return orig(bucket, draft)

    on._prefill_exec = spy
    assert on.generate(list(ext), max_new_tokens=5) == b_off
    on._prefill_exec = orig
    assert counter("serving.prefix_hits") - hits0 == 1
    assert counter("serving.prefix_pages_shared") - pages0 == 2
    assert max(widths) <= 4  # 20-token prompt, 16 shared -> suffix 4


def test_engine_prefix_refcounts_follow_slot_lifecycle():
    eng = make_engine(prefix_cache=True)
    eng.warm_start()
    header = list(range(1, 18))
    eng.generate(list(header), max_new_tokens=3)
    assert eng.cache.prefix_stats()["referenced_pages"] == 0
    req = eng.submit(header + [50], max_new_tokens=30)
    eng.step()  # admitted: the shared pages are referenced
    assert eng.cache.prefix_stats()["referenced_pages"] == 2
    eng.run_until_idle()
    req.result(0)
    stats = eng.cache.prefix_stats()
    assert stats["referenced_pages"] == 0
    assert stats["cached_pages"] >= 2
    assert eng.cache.free_pages() == eng.cache.total_pages


def test_engine_prefix_cache_off_env(monkeypatch):
    monkeypatch.setenv("HVD_TPU_PREFIX_CACHE", "0")
    eng = make_engine()
    assert not eng.cache.prefix_enabled
    monkeypatch.delenv("HVD_TPU_PREFIX_CACHE")
    assert make_engine().cache.prefix_enabled


def test_engine_seed_prefixes_rebuilds_bitwise_pages():
    """seed_prefixes (the elastic rebuild path) produces pages a later
    request hits copy-free — and the hit is bitwise-identical to a
    cold engine's completion."""
    header = list(range(1, 17))  # exactly 2 pages
    ref = reference_rollout(header + [7, 8], 6, 32)

    eng = make_engine(prefix_cache=True)
    eng.warm_start()
    assert eng.seed_prefixes([header]) == 2
    assert eng.cache.prefix_stats()["cached_pages"] == 2
    assert eng.generate(header + [7, 8], max_new_tokens=6) == ref
    # Seeding an already-covered chain is a no-op.
    assert eng.seed_prefixes([header]) == 0


def test_scheduler_admit_defers_on_page_budget():
    """Admission headroom (hvd-spec satellite): a head-of-queue request
    whose prefill does not fit the page budget defers — strictly FIFO
    (nothing behind it admits first), the slot is not burned, and
    serving.admission_deferred counts it."""
    from horovod_tpu import telemetry as _telemetry

    def deferred():
        return _telemetry.metrics().get(
            "serving.admission_deferred", {}).get("value", 0)

    s = ContinuousBatchingScheduler(max_slots=2, capacity=64)
    big = s.submit(_req(prompt=list(range(40))))     # 5 pages @ 8
    small = s.submit(_req(prompt=[1, 2, 3]))         # 1 page
    need = {big.rid: 5, small.rid: 1}
    before = deferred()
    admitted = s.admit(page_budget=4,
                       pages_needed=lambda r: need[r.rid])
    assert admitted == []                 # head blocked => FIFO holds
    assert deferred() - before == 1
    assert s.queue_depth() == 2
    # With headroom back, the original order admits.
    admitted = s.admit(page_budget=8,
                       pages_needed=lambda r: need[r.rid])
    assert [r.rid for _, r in admitted] == [big.rid, small.rid]


# ---------------------------------------------------------------------------
# Elastic: prefix-index export/rebuild roundtrip
# ---------------------------------------------------------------------------

@pytest.mark.slow
def test_elastic_serving_state_prefix_roundtrip(tmp_path, monkeypatch):
    """drain_commit exports the prefix index next to the
    continuations; a relaunched fleet's sync() rebuilds the shared
    pages (ghost prefills), so the FIRST post-relaunch request already
    hits copy-free — and everything stays bitwise.  (slow: four warm
    engines; the CI serving-bench job runs it unfiltered — tier-1
    keeps the cheap seed_prefixes leg.)"""
    from horovod_tpu import elastic
    from horovod_tpu import telemetry as _telemetry

    def hits():
        return _telemetry.metrics().get(
            "serving.prefix_hits", {}).get("value", 0)

    monkeypatch.setenv("HVD_TPU_ELASTIC_DIR", str(tmp_path))
    header = list(range(1, 18))  # 2 full pages published
    eng = make_engine(prefix_cache=True)
    eng.warm_start()
    ref_a = eng.generate(list(header), max_new_tokens=4)
    assert eng.cache.prefix_stats()["cached_pages"] == 2
    state = elastic.ServingState(eng)
    mid = eng.submit(header + [60], max_new_tokens=6)
    exported = state.drain_commit()
    assert state.wait_committed()
    assert exported and mid.finish_reason == FinishReason.DRAINED

    fresh = make_engine(prefix_cache=True)
    fresh.warm_start()
    state2 = elastic.ServingState(fresh)
    state2.sync()  # rebuilds pages AND resubmits the continuation
    assert fresh.cache.prefix_stats()["cached_pages"] >= 2
    pend = fresh.scheduler.pending()
    assert len(pend) == 1
    fresh.run_until_idle()
    # The continuation finished exactly as the uninterrupted run.
    uninterrupted = make_engine(prefix_cache=False)
    uninterrupted.warm_start()
    assert pend[0].result(0) == uninterrupted.generate(
        header + [60], max_new_tokens=6)
    # Replaying the original header is a copy-free hit, bitwise.
    h0 = hits()
    assert fresh.generate(list(header), max_new_tokens=4) == ref_a
    assert hits() > h0
