"""Learned sparse attention over the latent store (models/latent_moe.py with
an indexer, ops/sparse_latent_attention.py, ``deepseek_v32``) against the
plain float32 reference the benchmark keeps
(benchmark/refs/deepseek-v32-exp-ep16.py, which imports nothing of the
program).  Tiny widths, seeded weights, ``index_topk`` 16 well under every
context so that the selection BINDS; logits and selected sets, not
tokens."""

import functools
import json
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import test_latent_moe as tl
import test_latent_paged_attention as tp
from benchmark import cells
from benchmark.builders.sparse_latent_moe import config_of
from horovod_tpu.memory import planner
from horovod_tpu.models import latent_moe as lm
from horovod_tpu.ops import sparse_latent_attention as sla
from horovod_tpu.parallel.expert import (moe_layer_held,
                                         route_sigmoid_bias_group_top_k,
                                         swiglu)
from horovod_tpu.serving import InferenceEngine

REF = cells.load_module("refs", "deepseek-v32-exp-ep16")
with open(os.path.join(cells.HERE, "tests", "fixtures", "configs",
                       "tiny-dsv32.json")) as f:
    MODEL = json.load(f)["model"]      # float32, 4 of 16 experts held
CFG = config_of(MODEL)
UNCUT = dict(MODEL, n_routed_experts=16)
TOP = MODEL["index_topk"]

# float32 on both sides (see tests/test_latent_moe.py): the order of sums.
TOL = 5e-5
counter = tl.counter


@functools.lru_cache(maxsize=None)
def params():
    return REF.init_params(MODEL, 7)


def prompt(seed, n):
    return [int(t) for t in np.random.default_rng(seed).integers(
        0, MODEL["vocab_size"], size=n)]


@functools.lru_cache(maxsize=None)
def engine(kernel=False, chunk=2048):
    """``chunk``: an engine of its own for the prompt programs a test
    builds under ``short_stretches`` (they are compiled when first asked
    for and kept)."""
    lm.PAGED_INTERPRET = True if kernel else None
    try:
        eng = InferenceEngine(params(), CFG, max_slots=6, page_size=8,
                              capacity=256)
        eng.warm_start()
    finally:
        lm.PAGED_INTERPRET = None
    return eng


# -- the full forward, and the selection itself --------------------------------

@pytest.mark.parametrize("absorbed", [False, True])
def test_program_and_reference_agree_on_whole_sequences(absorbed):
    toks = jnp.asarray([prompt(11, 48), prompt(12, 48)], jnp.int32)
    logits, (entries, keys), counts = jax.jit(
        lambda p, t: lm.forward_full(p, t, CFG, absorbed))(params(), toks)
    want = np.stack(REF.served_logits(MODEL, params(),
                                      np.asarray(toks).tolist()))
    assert np.abs(np.asarray(logits) - want).max() < TOL
    assert entries.shape == (3, 2, 48, CFG.entry_width)
    assert keys.shape == (3, 2, 48, MODEL["index_head_dim"])
    assert counts.shape == (2, 4)
    # The selection is no bystander: without it, or with the last 16 in
    # its place, the logits move by two thousand tolerances.
    for rule in ("all", "recent"):
        other = np.stack(REF.served_logits(
            MODEL, params(), np.asarray(toks).tolist(), select=rule))
        assert np.abs(other - want).max() > 1000 * TOL


def short_stretches(monkeypatch, q_block, chunk):
    """Query blocks of ``q_block`` in stretches of ``chunk`` keys, heads
    two at a time: a toy bucket then holds several stretches."""
    monkeypatch.setattr(lm, "PREFILL_Q_BLOCK", q_block)
    monkeypatch.setattr(lm, "PREFILL_KEY_CHUNK", chunk)
    monkeypatch.setattr(lm, "PREFILL_HEAD_GROUP", 2)
    monkeypatch.setattr(lm, "INDEX_HEAD_GROUP", 2)


@pytest.mark.parametrize("q_block,chunk", [(8, 16), (16, 16), (256, 2048)])
def test_blocks_and_stretches_change_nothing(q_block, chunk, monkeypatch):
    """The prompt's attention cut into query blocks of 8 in stretches of
    16 keys (at the shipped sizes a toy sequence is one block)."""
    short_stretches(monkeypatch, q_block, chunk)
    toks = jnp.asarray([prompt(21, 48)], jnp.int32)
    logits = jax.jit(lambda p, t: lm.forward_full(p, t, CFG))(
        params(), toks)[0]
    want = REF.served_logits(MODEL, params(), np.asarray(toks).tolist())
    assert np.abs(np.asarray(logits) - np.stack(want)).max() < TOL


@pytest.mark.parametrize("bucket,n_valid", [
    (64, 32), (64, 33), (64, 34), (64, 64),     # a stretch's last row, the
    (128, 1), (128, 49), (128, 113),            # next one's first, one past
    (128, 128)])                                # it, the whole bucket
def test_a_walked_prompt_is_the_unpadded_sequence(bucket, n_valid,
                                                  monkeypatch):
    """``prefill_step`` of a bucket of 4 or 8 stretches of 16 rows: the
    last valid row's logits, every valid row's entries and index keys and
    the experts' counts are ``forward_full``'s on the unpadded sequence;
    the rows of the stretches past ``n_valid`` are the carried buffers'
    zeros: they were not run."""
    short_stretches(monkeypatch, 8, 16)
    assert lm.walked_stretch(CFG, 1, bucket) == 16
    toks = np.zeros((1, bucket), np.int32)
    toks[0, :n_valid] = prompt(81, n_valid)
    last, (entries, keys), counts = jax.jit(
        lambda p, t, n: lm.prefill_step(p, t, n, CFG))(
            params(), jnp.asarray(toks), jnp.asarray([n_valid], jnp.int32))
    every, (want_entries, want_keys), want_counts = jax.jit(
        lambda p, t: lm.forward_full(p, t, CFG))(
            params(), jnp.asarray(toks[:, :n_valid]))
    assert np.abs(np.asarray(last[0] - every[0, -1])).max() < TOL
    assert entries.shape == (3, 1, bucket, CFG.entry_width)
    assert keys.shape == (3, 1, bucket, MODEL["index_head_dim"])
    for got, want in ((entries, want_entries), (keys, want_keys)):
        assert np.abs(np.asarray(got[:, :, :n_valid] - want)).max() < TOL
    np.testing.assert_array_equal(np.asarray(counts),
                                  np.asarray(want_counts))
    ran = -(-n_valid // 16) * 16
    assert CFG.serving_model().prefill_rows(bucket, n_valid) == ran
    # Padding inside the last stretch that ran is computed, as it was.
    if ran > n_valid:
        assert np.asarray(entries[:, :, n_valid:ran]).any()
    assert not np.asarray(entries[:, :, ran:]).any()
    assert not np.asarray(keys[:, :, ran:]).any()


@pytest.mark.parametrize("b,bucket,chunk", [(1, 16, 16), (1, 8, 16),
                                            (1, 40, 16), (2, 64, 16),
                                            (1, 2048, 2048)])
def test_a_bucket_of_one_stretch_never_enters_the_walk(b, bucket, chunk,
                                                       monkeypatch):
    """One stretch or less, stretches that are not whole, more than one
    sequence: the layer-major code, as before the walk; and at the
    shipped sizes a bucket of 2048."""
    short_stretches(monkeypatch, 8, chunk)

    def walk(*a, **kw):
        raise AssertionError("the walk was entered")

    monkeypatch.setattr(lm, "walked_prefill", walk)
    assert lm.walked_stretch(CFG, b, bucket) == 0
    if b == 1:                     # (the engine's prompts are one a pass)
        assert CFG.serving_model().prefill_rows(bucket, 3) == bucket
    if bucket > 64:
        return                     # the rule alone: no toy runs 2048 rows
    toks = jnp.asarray([prompt(82 + i, bucket) for i in range(b)], jnp.int32)
    n_valid = jnp.full((b,), bucket - 3, jnp.int32)
    last, (entries, _), _ = jax.jit(
        lambda p, t, n: lm.prefill_step(p, t, n, CFG))(params(), toks,
                                                       n_valid)
    every = jax.jit(lambda p, t: lm.forward_full(p, t, CFG))(params(),
                                                             toks)[0]
    assert np.abs(np.asarray(last - every[:, bucket - 4])).max() < TOL
    assert np.asarray(entries[:, :, bucket - 3:]).any()
    # Without an indexer no shape walks.
    assert lm.walked_stretch(tl.CFG, 1, 64) == 0
    # (And the trap is a trap: a bucket of two stretches springs it.)
    with pytest.raises(AssertionError, match="was entered"):
        lm.prefill_step(params(), jnp.zeros((1, 32), jnp.int32),
                        n_valid[:1], CFG)


def _layer_inputs(seed, s):
    """``(h, c_q)`` of layer 0 for one seeded sequence, float32."""
    ap = params()["layers"][0]["attn"]
    x = params()["embed"][jnp.asarray(prompt(seed, s))]
    h = REF._rms(x, ap["norm"], MODEL["rms_norm_eps"])
    c_q = REF._rms(jnp.dot(h, ap["w_dq"]), ap["q_norm"],
                   MODEL["rms_norm_eps"])
    return ap, h, c_q


def test_index_scores_agree_without_the_hadamard_matrix():
    """The reference multiplies the indexer's queries and keys by the
    orthonormal Hadamard matrix as the source does; the program leaves it
    out (configuration ``departures``): the scores are the same."""
    ap, h, c_q = _layer_inputs(31, 40)
    pos = jnp.arange(40)[None]
    q = REF.index_queries(MODEL, ap, c_q, pos[0], "f32")
    k, w = REF.index_keys(MODEL, ap, h, "f32")
    want = REF.index_scores(MODEL, q, k, w, "f32")
    q_i, k_i, w_i = lm.index_project(h[None], c_q[None], ap, CFG, pos)
    got = lm.index_scores(q_i[0], k_i[0], w_i[0])
    assert np.abs(np.asarray(got) - np.asarray(want)).max() < 1e-6
    had = REF.hadamard(16)
    np.testing.assert_allclose(had @ had.T, np.eye(16), atol=1e-6)


def test_the_prompts_selection_is_the_references():
    ap, h, c_q = _layer_inputs(32, 48)
    pos = jnp.arange(48)[None]
    want = np.asarray(REF.selection_mask(MODEL, ap, h, c_q, "f32", "topk"))
    k_i, w_i = lm.index_keys(h[None], ap, CFG, pos)
    got = np.asarray(lm.prefill_selection(c_q, k_i[0], w_i[0], ap, CFG,
                                          pos[0]))
    assert (got == want).all()
    assert got.sum(axis=1).tolist() == [min(t + 1, TOP) for t in range(48)]
    assert lm.prefill_selection(c_q[:16], k_i[0, :16], w_i[0, :16], ap,
                                CFG, pos[0, :16]) is None


@pytest.mark.parametrize("k", [1, 5, 16, 40])
def test_the_threshold_chooses_what_top_k_chooses(k):
    """Ties at the k-th place (scores drawn from eight values), -inf among
    the valid, rows with fewer valid entries than k."""
    rng = np.random.default_rng(k)
    scores = rng.choice([-np.inf, -2.5, -1.0, -0.0, 0.0, 0.5, 1.0, 3.0],
                        size=(64, 40)).astype(np.float32)
    scores[:8] = rng.normal(size=(8, 40))
    n_valid = rng.integers(0, 41, size=64)
    valid = np.arange(40)[None, :] < n_valid[:, None]
    got = np.asarray(jax.jit(lambda s, v: sla.topk_mask(s, v, k))(
        scores, valid))
    _, idx = jax.lax.top_k(jnp.where(valid, scores, -jnp.inf), min(k, 40))
    want = np.zeros((64, 40), bool)
    want[np.arange(64)[:, None], np.asarray(idx)] = True
    # (The ordered bits keep -0.0 under 0.0, as top_k's total order
    # does.)
    assert (got == (want & valid)).all()
    assert (got.sum(axis=1) == np.minimum(n_valid, k)).all()
    # The decode's kernel: the same rows, valid up to a length.
    lengths = jnp.asarray(n_valid - 1, jnp.int32)
    paged = np.asarray(sla.select_paged(jnp.asarray(scores), lengths, k,
                                        interpret=True))
    assert (paged == got).all()


# -- prefill, then decode through the two stores --------------------------------

@pytest.mark.parametrize("kernel", [False, True])
@pytest.mark.parametrize("lengths,chunk", [
    ((40,), 2048), ((9, 70, 140), 2048), ((70, 24, 33, 130), 2048),
    ((70, 33, 140), 32)])
def test_prefill_then_decode_equals_the_reference(lengths, chunk, kernel,
                                                  monkeypatch):
    """Every prompt but the 9-token one is past ``index_topk``, so the
    selection binds from the first decoded token; ``kernel``: the two
    Pallas kernels and the sortless threshold in the interpreter.  In
    stretches of 32 the prompts of 70 and 140 tokens are WALKED: three of
    their bucket's four stretches, five of eight."""
    if chunk != 2048:
        short_stretches(monkeypatch, 8, chunk)
    eng = engine(kernel, chunk)
    prompts = [prompt(100 + n, n) for n in lengths]
    new = [4 + i for i in range(len(lengths))]
    scored = counter("serving.dsa_scored_tokens")
    selected = counter("serving.dsa_selected_tokens")
    got = tl.rollout(eng, prompts, new)
    seqs = [p + toks for p, (_, toks) in zip(prompts, got)]
    want = REF.served_logits(MODEL, params(), seqs, "f32")
    for p, n, (rows, toks), ref in zip(prompts, new, got, want):
        assert len(toks) == n and rows.shape[0] == n
        assert np.abs(rows - ref[len(p) - 1:len(p) - 1 + n]).max() < TOL
    # Iteration i scores a live slot's prompt + i cached tokens and its
    # new one, in each of the three layers.
    seen = [n + i + 1 for n, k in zip(lengths, new) for i in range(k - 1)]
    assert counter("serving.dsa_scored_tokens") - scored == 3 * sum(seen)
    assert (counter("serving.dsa_selected_tokens") - selected
            == 3 * sum(min(s, TOP) for s in seen))
    assert eng.cache.free_pages() == eng.cache.total_pages


def test_the_engine_counts_the_rows_a_walked_prompt_computed(monkeypatch):
    """One token over a stretch's boundary in a longer bucket: the rows
    of the stretches up to that token's, the prompt's own tokens, no page
    mapped past the prompt, and the reference's logits."""
    short_stretches(monkeypatch, 8, 32)
    eng = engine(False, 32)
    names = ("serving.prefill_rows", "serving.prefill_tokens")
    before = [counter(n) for n in names]
    mapped = []
    orig = eng._prefill

    def prefill(slot, req, *a, **kw):
        out = orig(slot, req, *a, **kw)
        mapped.append(np.asarray(eng.cache.table_row(slot))[0].copy())
        return out

    monkeypatch.setattr(eng, "_prefill", prefill)
    p = prompt(91, 65)                       # bucket 128: stretches 0-2
    ((rows, toks),) = tl.rollout(eng, [p], [3])
    assert [counter(n) - b for n, b in zip(names, before)] == [96, 65]
    want = REF.served_logits(MODEL, params(), [p + toks], "f32")[0]
    assert np.abs(rows - want[64:67]).max() < TOL
    # 65 tokens and 3 to come: nine pages of 8, the rest of the row is the
    # trash page.
    (row,) = mapped
    assert (row[:9] > 0).all() and not row[9:].any()
    assert eng.cache.free_pages() == eng.cache.total_pages


def test_scored_tokens_are_counted_apart_while_a_profiler_records(
        monkeypatch):
    """``serving.dsa_scored_tokens_traced`` moves with
    ``serving.dsa_scored_tokens`` while a profiler session records and not
    otherwise: what the benchmark's two rooflines hold a trace's kernel
    seconds against."""
    model = CFG.serving_model()
    names = ("serving.dsa_scored_tokens", "serving.dsa_scored_tokens_traced")
    before = [counter(n) for n in names]
    model.observe_launch(np.asarray([30, -1, 7]))
    assert [counter(n) - b for n, b in zip(names, before)] == [3 * 39, 0]
    monkeypatch.setattr(lm.TraceAnnotation, "is_enabled",
                        staticmethod(lambda: True))
    model.observe_launch(np.asarray([30, -1, 7]))
    assert [counter(n) - b for n, b in zip(names, before)] == [6 * 39,
                                                               3 * 39]
    tl.CFG.serving_model().observe_launch(np.asarray([5]))   # no indexer
    assert counter(names[0]) - before[0] == 6 * 39


@pytest.mark.parametrize("kernel", [False, True])
def test_the_decodes_selected_set_is_the_references(kernel):
    """One decode step over a cache the prefill left: what the decode's
    indexer selects for the new token in layer 0 is row ``t`` of the
    reference's mask, float32 on both sides."""
    n = 90
    toks = prompt(55, n + 1)
    ap, h, c_q = _layer_inputs(55, n + 1)
    want = np.asarray(REF.selection_mask(MODEL, ap, h, c_q, "f32",
                                         "topk"))[n]
    # The paged stores as a prefill of the first n tokens leaves them.
    pos = jnp.arange(n + 1)[None]
    _, entry = lm.mla_latents(h[None], ap, CFG, pos)
    _, k_i, _ = lm.index_project(h[None], c_q[None], ap, CFG, pos)
    ps, pps = 8, 16
    table = jnp.asarray(np.random.default_rng(1).permutation(
        np.arange(1, 2 * pps + 1)).reshape(2, pps), jnp.int32)

    def paged(rows):
        store = jnp.zeros((1, 2 * pps + 1, ps, rows.shape[-1]))
        padded = jnp.pad(rows[0, :n], ((0, pps * ps - n), (0, 0)))
        return store.at[0, table[0]].set(padded.reshape(pps, ps, -1))

    lengths = jnp.asarray([n, -1], jnp.int32)
    seen = {}
    real = lm.mla_absorbed_attention if not kernel else None

    def spy_twin(q_nope, q_rope, view, q_pos, ap, cfg, allowed=None):
        seen["selected"] = allowed[:, 0]
        return real(q_nope, q_rope, view, q_pos, ap, cfg, allowed)

    def spy_kernel(q, entry, store, table, lengths, layer, selected, **kw):
        seen["selected"] = selected
        return jnp.zeros(q.shape[:2] + (CFG.kv_lora_rank,), q.dtype)

    old = (lm.PAGED_INTERPRET, lm.mla_absorbed_attention,
           sla.sparse_paged_attention)
    lm.PAGED_INTERPRET = True if kernel else None
    lm.mla_absorbed_attention = spy_twin if not kernel else old[1]
    if kernel:
        sla.sparse_paged_attention = spy_kernel
    try:
        attend, _ = lm.selected_decode_attend(
            lengths, (paged(entry), paged(k_i)), table, CFG)
        attend(0, jnp.stack([h[n], h[n]])[:, None], ap)
    finally:
        (lm.PAGED_INTERPRET, lm.mla_absorbed_attention,
         sla.sparse_paged_attention) = old
    got = np.asarray(seen["selected"]) > 0
    assert (got[0, :n + 1] == want[:n + 1]).all()
    assert got[0, :n + 1].sum() == TOP
    if kernel:
        assert not got[1].any()            # the idle slot selects nothing


# -- the kernels against their twins ---------------------------------------------

PAGE, PPS, LAYERS, HEADS, DIM = 16, 80, 2, 64, 128


def _paged_case(lengths, seed):
    lengths = np.asarray(lengths, np.int32)
    slots = len(lengths)
    n_pages = slots * PPS + 1
    ks = jax.random.split(jax.random.PRNGKey(seed), 4)
    keys = jax.random.normal(ks[0], (LAYERS, n_pages, PAGE, DIM))
    table = np.random.RandomState(seed).permutation(
        np.arange(1, n_pages)).reshape(slots, PPS).astype(np.int32)
    owned = np.zeros(n_pages, bool)
    for s, n in enumerate(lengths):
        if n >= 0:
            owned[table[s, :-(-int(n) // PAGE)]] = True
    # Pages no live slot owns are NaN: nothing of them may be read.
    keys = jnp.where(owned[None, :, None, None], keys, jnp.nan)
    return dict(lengths=jnp.asarray(lengths), keys=keys,
                table=jnp.asarray(table),
                q=jax.random.normal(ks[1], (slots, HEADS, DIM)),
                w=jax.random.normal(ks[2], (slots, HEADS)))


B = sla.INDEX_BLOCK_TOKENS


@pytest.mark.parametrize("lengths", [
    (300, -1, 37, -1, 600, 5), (PAGE - 1, PAGE, PAGE + 1),
    (B - 1, B, B + 1, -1, 2 * B + 1), (0, -1, 1), (PAGE * PPS - 1, -1, 3)])
def test_the_score_kernel_equals_scores_over_a_gathered_row(lengths):
    c = _paged_case(lengths, len(lengths))
    got = np.asarray(jax.jit(lambda c: sla.index_paged_scores(
        c["q"], c["w"], c["keys"], c["table"], c["lengths"], 1,
        interpret=True))(c))
    slots = len(lengths)
    rows = jnp.nan_to_num(c["keys"][1][c["table"]].reshape(
        slots, PPS * PAGE, DIM))
    want = np.asarray(jax.vmap(lm.index_scores)(
        c["q"][:, None], rows, c["w"][:, None])[:, 0])
    for s, n in enumerate(lengths):
        n = max(n, 0)
        assert np.abs(got[s, :n] - want[s, :n]).max(initial=0) < 2e-4
        assert np.isneginf(got[s, n:]).all()


@pytest.mark.parametrize("lengths", [
    (300, -1, 37, -1, 600, 5), (0, -1, 1, 0), (PAGE * 80 - 1, -1, 3),
    (255, 256, 257, 511, 513)])
def test_the_sparse_kernel_equals_masked_attention_over_a_gathered_view(
        lengths):
    c = tp.case(lengths, seed=len(lengths), nan_elsewhere=True)
    slots = len(lengths)
    cap = tp.PPS * tp.PAGE
    rng = np.random.default_rng(slots)
    selected = rng.random((slots, cap)) < 0.3
    for s, n in enumerate(lengths):       # one slot selects its new token
        if n >= 0:                        # alone, one everything, one not
            selected[s, n] = s % 3 != 1   # its new token
            if not selected[s, :n + 1].any():
                selected[s, 0] = True
    if lengths[0] >= 0:
        selected[0] = False
        selected[0, lengths[0]] = True
    selected = jnp.asarray(selected)

    def kernel(c):
        q = lm._absorbed_query(c["q_nope"], c["q_rope"],
                               c["store"].shape[-1], c["ap"], tp.CFG)
        o = sla.sparse_paged_attention(
            q[:, 0], c["entry"][:, 0], c["store"], c["table"],
            c["lengths"], 1, selected, scale=lm.softmax_scale(tp.CFG),
            kv_rank=tp.CFG.kv_lora_rank, interpret=True)
        return lm._absorbed_output(o[:, None], c["ap"], tp.CFG)[:, 0]

    def twin(c):
        view = jnp.nan_to_num(c["store"][1][c["table"]].reshape(
            slots, cap, -1))
        pos = jnp.clip(c["lengths"], 0, None)
        view = view.at[jnp.arange(slots), pos].set(c["entry"][:, 0])
        return lm.mla_absorbed_attention(
            c["q_nope"], c["q_rope"], view, c["lengths"][:, None], c["ap"],
            tp.CFG, selected[:, None])[:, 0]

    got, want = jax.jit(kernel)(c), jax.jit(twin)(c)
    on = np.asarray(lengths) >= 0
    assert tp.gap(got[on], want[on]) < tp.TOL
    assert not np.asarray(got)[~on].any()


# -- the second store --------------------------------------------------------------

def test_two_unequal_stores_share_one_table_and_one_pool():
    """The latent entry (128 here) and the index key (16) on one page
    table: one array a width, a page priced at the SUM of the widths by
    the cache, the planner's pools and admission's reservation; written,
    freed and reused together."""
    eng = engine()
    store, keys = eng.cache.pages
    assert store.shape == (3, 1 + 6 * 32, 8, 128)
    assert keys.shape == (3, 1 + 6 * 32, 8, 16)
    assert eng.cache.entry_widths == (128, 16)
    assert eng.cache.page_global_bytes == 3 * 8 * (128 + 16) * 4
    assert counter("serving.cache_entry_bytes") == (128 + 16) * 4
    assert json.loads(eng.fingerprint)["indexer"] == [4, 16, 16]
    # A byte budget: the pool holds what the budget buys at the summed
    # width, the trash page out of it too.
    token = (128 + 16) * 4
    (pages,) = planner.size_page_pools(
        ({"name": "full", "n_layers": 3},), token, 8, 32, 6,
        40 * 3 * 8 * token, expected_tokens=64)
    assert pages == 39
    pooled = InferenceEngine(params(), CFG, max_slots=6, page_size=8,
                             capacity=256, kv_pool_bytes=40 * 3 * 8 * token,
                             kv_expected_tokens=64)
    assert [p.shape[1] for p in pooled.cache.pages] == [40, 40]
    assert pooled.cache.total_pages == 39
    # Written together: after a rollout the pages a request held carry
    # both kinds of rows; freed together: every page is back.
    first = tl.rollout(eng, [prompt(61, 30)], [3])
    touched = [np.asarray(jnp.any(p != 0, axis=(0, 2, 3)))
               for p in eng.cache.pages]
    assert (touched[0][1:] == touched[1][1:]).all() and touched[0].sum() >= 4
    assert eng.cache.free_pages() == eng.cache.total_pages
    # Reused: the same prompt again, over pages that hold other requests'
    # stale rows of both kinds, serves the same logits.
    tl.rollout(eng, [prompt(62, 100), prompt(63, 77)], [2, 2])
    again = tl.rollout(eng, [prompt(61, 30)], [3])
    np.testing.assert_array_equal(first[0][0], again[0][0])


def test_without_the_indexers_fields_the_family_is_what_it_was():
    """``axk1``'s tiny configuration: ONE width, no indexer parameter,
    and no indexer operation in its decode and prefill programs."""
    cfg = tl.CFG
    assert not cfg.indexed and cfg.entry_widths == (cfg.entry_width,)
    assert cfg.serving_model().cache_entry()["widths"] == (128,)
    shapes = jax.eval_shape(
        lambda: lm.init_latent_moe(jax.random.PRNGKey(0), cfg))
    names = {jax.tree_util.keystr(p) for p, _ in
             jax.tree_util.tree_flatten_with_path(shapes)[0]}
    assert not any(n in name for name in names
                   for n in ("w_qi", "w_ki", "ki_", "w_w", "router_bias"))
    eng = tl.engine()
    model, c = eng.model, eng.cache
    lengths = jnp.zeros((8,), jnp.int32)
    table = jnp.zeros((8, c.pages_per_slot), jnp.int32)
    decode = jax.jit(model.decode).lower(
        tl.params(), c.pages, table, lengths, lengths).as_text()
    prefill = jax.jit(model.prefill).lower(
        tl.params(), c.pages, table[:1], lengths[:1], lengths[:1] + 5,
        jnp.zeros((1, 32), jnp.int32)).as_text()
    for text in (decode, prefill):
        # The router's top_k (4 of 16) is the only one; no threshold
        # built from a float's bits, no kernel of the indexer's.
        assert "dsa_" not in text and f"k = {TOP}" not in text
        assert "k = 4" in text and "bitcast_convert" not in text
    # The same tiny model WITH the fields names them.
    eng = engine()
    c = eng.cache
    text = jax.jit(eng.model.decode).lower(
        params(), c.pages, jnp.zeros((6, c.pages_per_slot), jnp.int32),
        jnp.zeros((6,), jnp.int32), jnp.zeros((6,), jnp.int32)).as_text()
    assert f"k = {TOP}" in text


def test_the_prompts_head_sees_one_row():
    """``_layers`` applies the head to the last valid row alone: the
    prompt's returned logits are what the full head gave there, for
    ``axk1``'s tiny configuration."""
    toks = jnp.asarray([prompt(71, 32)], jnp.int32)
    n_valid = jnp.asarray([27], jnp.int32)
    last, entries, _ = jax.jit(
        lambda p, t, n: lm.prefill_step(p, t, n, tl.CFG))(
            tl.params(), toks, n_valid)
    assert last.shape == (1, MODEL["vocab_size"])
    every, full_entries, _ = jax.jit(
        lambda p, t: lm.forward_full(p, t, tl.CFG))(tl.params(), toks)
    # Padding follows the valid rows and is causally inert.
    np.testing.assert_allclose(np.asarray(last[0]),
                               np.asarray(every[0, 26]), atol=1e-6)
    np.testing.assert_allclose(np.asarray(entries[:, :, :27]),
                               np.asarray(full_entries[:, :, :27]),
                               atol=1e-6)


# -- group-limited expert choice -----------------------------------------------------

def _plain_choice(scores, bias, k, n_group, topk_group):
    """Plain Python: the groups by the sum of their two best biased
    scores, then the experts among the kept groups."""
    out = []
    for row in scores:
        biased = row + bias
        per = len(row) // n_group
        groups = sorted(range(n_group), key=lambda g: (-sum(sorted(
            biased[g * per:(g + 1) * per])[-2:]), g))[:topk_group]
        pool = [e for g in groups for e in range(g * per, (g + 1) * per)]
        out.append(sorted(pool, key=lambda e: (-biased[e], e))[:k])
    return out


def test_group_limited_choice_against_a_plain_python_choice():
    rng = np.random.default_rng(5)
    x = rng.normal(size=(50, 64)).astype(np.float32)
    router = (rng.normal(size=(64, 32)) * 0.2).astype(np.float32)
    bias = (rng.normal(size=32) * 0.3).astype(np.float32)
    idx, gate = route_sigmoid_bias_group_top_k(
        x, router, bias, top_k=6, n_group=8, topk_group=3,
        routed_scale=2.5)
    scores = 1 / (1 + np.exp(-(x.astype(np.float64) @ router)))
    want = _plain_choice(scores, bias, 6, 8, 3)
    assert [sorted(r) for r in np.asarray(idx).tolist()] == [
        sorted(r) for r in want]
    # Weights: the UNBIASED scores of the chosen, normalised, scaled.
    chosen = np.take_along_axis(scores, np.asarray(idx), axis=1)
    np.testing.assert_allclose(
        np.asarray(gate), 2.5 * chosen / chosen.sum(1, keepdims=True),
        rtol=1e-5)
    # The bias moves choices here (else the test shows nothing of it).
    plain = _plain_choice(scores, 0 * bias, 6, 8, 3)
    assert sum(sorted(a) != sorted(b) for a, b in zip(want, plain)) > 5
    ref_idx, ref_gate = REF.route(
        dict(MODEL, n_group=8, topk_group=3, num_experts_per_tok=6),
        jnp.asarray(x), jnp.asarray(router), jnp.asarray(bias), "f32")
    np.testing.assert_array_equal(np.asarray(ref_idx), np.asarray(idx))
    np.testing.assert_allclose(np.asarray(ref_gate), np.asarray(gate),
                               rtol=1e-5)


def test_the_sixteen_shares_add_up_to_the_uncut_layer():
    """16 experts over 16 members, ONE each: the parts all shares give,
    the shared expert counted once, are the uncut reference's layer."""
    lp = REF.init_params(UNCUT, 3)["layers"][1]
    h = jax.random.normal(jax.random.PRNGKey(4), (24, 64), jnp.float32)
    whole = REF.expert_ffn(dict(UNCUT, expert_offset=0), lp, h, "f32")
    sh = lp["shared"]
    shared = swiglu(h, sh["w_gate"], sh["w_up"], sh["w_down"])
    routing = functools.partial(
        route_sigmoid_bias_group_top_k, router=lp["router"],
        bias=lp["router_bias"], top_k=4, n_group=4, topk_group=2,
        routed_scale=2.5)
    total, assigned = shared, 0
    for offset in range(16):
        share = dict(lp, **{k: lp[k][offset:offset + 1]
                            for k in ("w_gate", "w_up", "w_down")})
        out = moe_layer_held(h, share, num_experts=16,
                             expert_offset=offset, top_k=4,
                             routing=routing)
        total = total + (out.out - shared)
        assigned += int(out.counts.sum())
    assert np.abs(np.asarray(total) - np.asarray(whole)).max() < 1e-5
    assert assigned == 24 * 4
