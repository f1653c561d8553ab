"""The latent-attention mixture-of-experts decoder (models/latent_moe.py)
through the serving engine, against the plain float32 reference the
benchmark keeps (benchmark/refs/axk1-ep16.py, which imports nothing of
the program).  Tiny widths, seeded weights, logits and not tokens."""

import functools
import json
import math
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import horovod_tpu.telemetry as telemetry
from benchmark import cells
from benchmark.builders.latent_moe import config_of
from horovod_tpu.models import latent_moe as lm
from horovod_tpu.models.transformer import (TransformerConfig,
                                            init_transformer)
from horovod_tpu.ops.latent_paged_attention import tokens_read
from horovod_tpu.parallel.expert import (held_chunk_rows, moe_layer_held,
                                         route_sigmoid_top_k, swiglu)
from horovod_tpu.serving import InferenceEngine
from horovod_tpu.serving.kv_cache import PagedKVCache

REF = cells.load_module("refs", "axk1-ep16")
with open(os.path.join(cells.HERE, "tests", "fixtures", "configs",
                       "tiny-axk1.json")) as f:
    MODEL = json.load(f)["model"]          # float32, 4 of 16 experts held
CFG = config_of(MODEL)
UNCUT = dict(MODEL, n_routed_experts=16)   # every expert held

# float32 on both sides: what is left is the order of sums (the gathered
# rows, the absorbed form against dense masked products).
# bfloat16 operands in the reference's place move the logits by 1e-3 and
# more (test_the_tolerance_would_catch_bfloat16), so the tolerance sits
# between the two with a decade on each side.
TOL = 5e-5


@functools.lru_cache(maxsize=None)
def params():
    return REF.init_params(MODEL, 7)


def prompt(seed, n):
    return [int(t) for t in np.random.default_rng(seed).integers(
        0, MODEL["vocab_size"], size=n)]


def counter(name):
    return telemetry.metrics().get(name, {}).get("value", 0)


# -- (a) prefill then decode through the latent paged cache -------------------

@functools.lru_cache(maxsize=None)
def engine():
    eng = InferenceEngine(params(), CFG, max_slots=8, page_size=8,
                          capacity=256)
    eng.warm_start()
    return eng


def rollout(eng, prompts, max_new):
    """Drive ``prompts`` together; returns, for each, the logits rows the
    engine's own executables produced (the prefill's last row, then one
    row a decode iteration) and the tokens it served."""
    reqs = [eng.submit(list(p), max_new_tokens=n)
            for p, n in zip(prompts, max_new)]
    rows = {r.rid: [] for r in reqs}
    slot_of = {}
    orig_prefill, orig_decode = eng._prefill, eng._decode_iteration

    def prefill(slot, req, *a, **kw):
        out = orig_prefill(slot, req, *a, **kw)
        slot_of[slot] = req.rid
        rows[req.rid].append(np.asarray(out[2]))  # (token, tokens, last)
        return out

    def decode(active):
        owners = {slot: req.rid for slot, req in active}
        logits = orig_decode(active)
        for slot, rid in owners.items():
            rows[rid].append(logits[slot].copy())
        return logits

    eng._prefill, eng._decode_iteration = prefill, decode
    try:
        eng.run_until_idle()
    finally:
        eng._prefill, eng._decode_iteration = orig_prefill, orig_decode
    return [(np.stack(rows[r.rid]), r.result(0)) for r in reqs]


def view_tokens_of(lengths, new, page_size=8, slots=8):
    """What ``serving.decode_view_tokens`` moves by over a rollout of
    prompts of ``lengths`` admitted together, ``new`` tokens each:
    iteration ``i`` (0-based) attends the slots with more than ``i + 1``
    tokens to give, each at its prompt's length plus ``i`` cached entries
    rounded up to the page, the mean over the slots."""
    return sum(tokens_read([n + i if i + 1 < k else -1
                            for n, k in zip(lengths, new)], page_size)
               for i in range(max(new) - 1)) / slots


# Ragged slots, one to six of the eight alive, from under a page to more
# than half the capacity.
@pytest.mark.parametrize("lengths", [
    (20,), (20, 70), (9, 70, 140), (70, 9, 140, 30, 66, 12)])
def test_prefill_then_decode_equals_the_reference(lengths):
    eng = engine()
    prompts = [prompt(100 + n, n) for n in lengths]
    new = [6 + i for i in range(len(lengths))]
    views = counter("serving.decode_view_tokens")
    iters = counter("serving.decode_iterations")
    got = rollout(eng, prompts, new)
    d_iter = counter("serving.decode_iterations") - iters
    assert d_iter == max(new) - 1
    # The counter moved by the live lengths, whole pages, and not by a
    # view's size: the same on every backend.
    assert (counter("serving.decode_view_tokens") - views
            == view_tokens_of(lengths, new))
    seqs = [p + toks for p, (_, toks) in zip(prompts, got)]
    want = REF.served_logits(MODEL, params(), seqs, "f32")
    for p, n, (rows, toks), ref in zip(prompts, new, got, want):
        assert len(toks) == n and rows.shape[0] == n
        # Row i was computed after len(p) + i tokens: the reference's
        # logits at position len(p) - 1 + i.
        ref_rows = ref[len(p) - 1:len(p) - 1 + n]
        assert np.abs(rows - ref_rows).max() < TOL
    assert eng.cache.free_pages() == eng.cache.total_pages


def test_one_sequence_alive_counts_its_own_pages_alone():
    """One sequence alive: the counter moves by its length rounded up to
    the page, the seven idle slots nothing, the mean over the slots; no
    rung of any view is in it."""
    eng = engine()
    seen = []
    for n in (20, 70, 140):
        before = counter("serving.decode_view_tokens")
        rollout(eng, [prompt(300 + n, n)], [2])
        seen.append(counter("serving.decode_view_tokens") - before)
    assert seen == [24 / 8, 72 / 8, 144 / 8]


def test_run_ahead_loop_equals_the_loop_held_at_depth_0(monkeypatch):
    """The decode loop one iteration ahead (ISSUE 30) over this model's
    program (one store, the expert counts beside the logits):
    staggered admissions and finishes serve the same tokens and feed
    the same expert counters as the loop that fetches before it
    launches; ``serving.decode_ahead`` moves only when it runs
    ahead."""
    eng = engine()
    trace = [(prompt(400 + i, n), new, at) for i, (n, new, at) in enumerate(
        [(20, 7, 0), (70, 2, 0), (9, 5, 1), (33, 1, 2), (140, 6, 2),
         (12, 4, 6), (66, 3, 6)])]
    names = ("serving.decode_ahead", "serving.decode_iterations",
             "serving.moe_assignments", "serving.tokens_generated")

    def replay():
        before = [counter(n) for n in names]
        reqs = [eng.submit(list(p), max_new_tokens=n, arrival=a)
                for p, n, a in trace]
        it = 0
        while not eng.scheduler.idle():
            eng.step(now=it)
            it += 1
        assert eng.cache.free_pages() == eng.cache.total_pages
        return ([r.result(0) for r in reqs],
                [counter(n) - b for n, b in zip(names, before)])

    ahead, (n_ahead, n_iter, assigned, tokens) = replay()
    monkeypatch.setattr(eng, "_runs_ahead", lambda active: False)
    held, (h_ahead, h_iter, h_assigned, h_tokens) = replay()
    assert ahead == held and [len(t) for t in ahead] == [
        n for _, n, _ in trace]
    assert h_ahead == 0 and 0.5 * n_iter < n_ahead < n_iter
    # Every token but a prefill's came from one slot of one decode
    # iteration, through top_k experts of each expert layer, of which
    # this share holds some: the counters saw the same slots.
    assert tokens == h_tokens == sum(n for _, n, _ in trace)
    assert assigned == h_assigned > 0


def test_the_tolerance_would_catch_bfloat16():
    seqs = [prompt(5, 40)]
    f32 = REF.served_logits(MODEL, params(), seqs, "f32")[0]
    b16 = REF.served_logits(MODEL, params(), seqs, "bf16")[0]
    assert np.abs(f32 - b16).max() > 10 * TOL


def test_program_and_reference_agree_on_whole_sequences():
    toks = jnp.asarray([prompt(11, 48), prompt(12, 48)], jnp.int32)
    logits, entries, counts = jax.jit(
        lambda p, t: lm.forward_full(p, t, CFG))(params(), toks)
    want = REF.served_logits(MODEL, params(), np.asarray(toks).tolist())
    assert np.abs(np.asarray(logits) - np.stack(want)).max() < TOL
    assert entries.shape == (3, 2, 48, CFG.entry_width)
    assert counts.shape == (2, 4) and counts.dtype == jnp.int32


# -- (b) the absorbed decode form equals the rebuilt-keys form ----------------

@pytest.mark.parametrize("q_block", [8, 256])
def test_absorbed_attention_equals_rebuilt(q_block, monkeypatch):
    """Also with the prefill's attention cut into five blocks of queries
    (at the shipped 256 a toy sequence is one block)."""
    monkeypatch.setattr(lm, "PREFILL_Q_BLOCK", q_block)
    toks = jnp.asarray([prompt(21, 40)], jnp.int32)
    rebuilt = jax.jit(lambda p, t: lm.forward_full(p, t, CFG))(
        params(), toks)
    absorbed = jax.jit(lambda p, t: lm.forward_full(
        p, t, CFG, absorbed=True))(params(), toks)
    assert np.abs(np.asarray(rebuilt[0]) - np.asarray(absorbed[0])
                  ).max() < 1e-5
    np.testing.assert_array_equal(np.asarray(rebuilt[2]),
                                  np.asarray(absorbed[2]))


# -- (c) the share ties to the model ------------------------------------------

def _layer_params(seed, held, offset):
    """One expert layer of the UNCUT tiny model, and the share ``offset
    .. offset + held`` of its routed experts."""
    lp = REF.init_params(UNCUT, seed)["layers"][1]
    share = dict(lp, **{k: lp[k][offset:offset + held]
                        for k in ("w_gate", "w_up", "w_down")})
    return lp, share


def test_all_shares_add_up_to_the_uncut_layer():
    lp, _ = _layer_params(3, 16, 0)
    h = jax.random.normal(jax.random.PRNGKey(4), (24, 64), jnp.float32)
    whole = REF.expert_ffn(dict(UNCUT, expert_offset=0), lp, h, "f32")
    sh = lp["shared"]
    shared = swiglu(h, sh["w_gate"], sh["w_up"], sh["w_down"])
    total, assigned = shared, 0
    for offset in range(0, 16, 4):
        _, share = _layer_params(3, 4, offset)
        out = moe_layer_held(h, share, num_experts=16,
                             expert_offset=offset, top_k=4,
                             routed_scale=2.5)
        # The same share in the reference.
        ref = REF.expert_ffn(dict(MODEL, expert_offset=offset), share, h,
                             "f32")
        assert np.abs(np.asarray(out.out) - np.asarray(ref)).max() < 1e-5
        total = total + (out.out - shared)      # the shared expert once
        assigned += int(out.counts.sum())
    assert np.abs(np.asarray(total) - np.asarray(whole)).max() < 1e-5
    assert assigned == 24 * 4                   # every pair, exactly once


# -- (d) no token is dropped ---------------------------------------------------

@pytest.mark.parametrize("chunk_rows", [8, 16, None])
def test_a_skewed_router_drops_no_token(chunk_rows):
    lp, share = _layer_params(5, 4, 4)
    # Every token's best expert is held expert 1 (number 5 of 16).
    h = jnp.abs(jax.random.normal(jax.random.PRNGKey(6), (40, 64),
                                  jnp.float32))
    router = share["router"].at[:, 5].set(1.0)
    share = dict(share, router=router)
    mask = jnp.arange(40) < 37                  # three rows are padding
    out = moe_layer_held(h, share, num_experts=16, expert_offset=4,
                         top_k=4, routed_scale=2.5, token_mask=mask,
                         chunk_rows=chunk_rows)
    idx, _ = route_sigmoid_top_k(h, router, 4, 2.5)
    idx = np.asarray(idx)[:37]
    want = [(idx == 4 + e).sum() for e in range(4)]
    assert want[1] == 37 and sum(want) > 37
    assert np.asarray(out.counts).tolist() == want
    ref = REF.expert_ffn(dict(MODEL, expert_offset=4), share, h, "f32")
    assert np.abs(np.asarray(out.out)[:37] - np.asarray(ref)[:37]
                  ).max() < 1e-5
    # Masked rows reach no routed expert: the shared expert alone.
    sh = share["shared"]
    alone = swiglu(h[37:], sh["w_gate"], sh["w_up"], sh["w_down"])
    assert np.abs(np.asarray(out.out)[37:] - np.asarray(alone)).max() < 1e-6


@pytest.mark.parametrize("tokens,rows", [(64, 128), (2048, 2048), (2, 16),
                                         (16, 128), (512, 512)])
def test_chunk_rows_follow_the_expected_load(tokens, rows):
    assert held_chunk_rows(tokens, 8, 12, 192) == rows


def test_experts_outside_the_router_are_refused():
    _, share = _layer_params(5, 4, 4)
    with pytest.raises(ValueError, match="not among the router"):
        moe_layer_held(jnp.zeros((2, 64)), share, num_experts=16,
                       expert_offset=13, top_k=4)


# -- (e) YaRN -------------------------------------------------------------------

def test_yarn_frequencies_and_scale_at_the_published_sizes():
    """Hand-computed for rope 64, theta 10000, factor 32 over 4096,
    beta_fast 32, beta_slow 1: the correction dims are 10.47 and 22.51,
    so pairs 0..10 extrapolate, 23.. interpolate (1/32), and pair 16 sits
    6/13 of the way."""
    cfg = lm.LatentMoEConfig()
    f = lm.yarn_inv_freq(cfg)
    assert f.shape == (32,)
    np.testing.assert_allclose(
        f[[0, 10, 16, 23, 31]],
        [1.0, 0.05623413251903491, 0.005528846153846153,
         4.167254475510388e-05, 4.167254475510387e-06], rtol=1e-6)
    assert math.isclose(lm.yarn_mscale(32, 1), 1.3465735902799727)
    assert math.isclose(lm.softmax_scale(cfg), 0.13086079996295005)
    np.testing.assert_allclose(
        REF.yarn_inv_freq(lm_model()), f, rtol=1e-6)
    assert math.isclose(REF.softmax_scale(lm_model()),
                        lm.softmax_scale(cfg))


def lm_model():
    with open(os.path.join(cells.HERE, "configs", "axk1-ep16.json")) as f:
        return json.load(f)["model"]


def test_rope_rotates_pairs_by_the_blended_frequencies():
    cfg = lm.LatentMoEConfig(qk_rope_head_dim=4, rope_factor=1.0)
    x = jnp.asarray([[1.0, 0.0, 0.0, 1.0]])
    got = np.asarray(lm.rope(x, jnp.asarray([3]), cfg))[0]
    a0, a1 = 3 * 1.0, 3 * 10000.0 ** -0.5
    np.testing.assert_allclose(
        got, [math.cos(a0), -math.sin(a1), math.sin(a0), math.cos(a1)],
        atol=1e-6)


# -- the protocol: cache entry, refusals, counters ----------------------------

def test_the_store_is_one_latent_entry_a_token():
    eng = engine()
    (store,) = eng.cache.pages
    assert store.shape == (3, 1 + 8 * 32, 8, 128)   # 32 + 8, to a lane row
    assert eng.cache.entry_widths == (128,)
    assert eng.cache.page_global_bytes == 3 * 8 * 128 * 4
    assert counter("serving.cache_entry_bytes") == 128 * 4
    assert json.loads(eng.fingerprint)["family"] == "latent_moe"
    assert not eng.cache.prefix_enabled       # off, with its reason
    assert lm.LatentMoEServing.prefix_cache_why


def test_a_dense_model_keeps_its_two_stores():
    c = PagedKVCache(n_layers=2, n_heads=4, head_dim=8, max_slots=2,
                     pages_per_slot=4, page_size=4)
    assert c.entry_widths == (32, 32) and len(c.pages) == 2
    assert c.k_pages is c.pages[0] and c.v_pages is c.pages[1]
    assert c.page_global_bytes == 2 * 2 * 4 * 32 * 4
    with pytest.raises(ValueError, match="page arrays"):
        c.replace_pages(c.k_pages)


def test_draft_and_tensor_parallel_are_refused_clearly():
    dcfg = TransformerConfig(vocab_size=128, d_model=32, n_heads=2,
                             n_layers=1, d_ff=64, max_seq_len=512)
    draft = (init_transformer(jax.random.PRNGKey(0), dcfg), dcfg)
    with pytest.raises(ValueError, match="speculative decoding"):
        InferenceEngine(params(), CFG, max_slots=2, page_size=8,
                        capacity=64, draft=draft)
    from horovod_tpu.core.topology import make_mesh

    mesh = make_mesh(data=jax.device_count() // 2, model=2)
    with pytest.raises(ValueError, match="cannot be sharded"):
        InferenceEngine(params(), CFG, mesh=mesh, max_slots=2,
                        page_size=8, capacity=64)


def test_decode_feeds_the_expert_counters():
    eng = engine()
    names = ("serving.moe_assignments", "serving.moe_expert_load_max",
             "serving.moe_experts_touched", "serving.decode_iterations")
    before = {n: counter(n) for n in names}
    seen = []
    orig = eng.model.observe_decode

    def spy(extras):
        seen.append(np.asarray(extras[0]))
        orig(extras)

    eng.model.observe_decode = spy
    try:
        rollout(eng, [prompt(41, 12), prompt(42, 30)], [5, 3])
    finally:
        del eng.model.observe_decode
    d = {n: counter(n) - before[n] for n in names}
    assert d["serving.decode_iterations"] == len(seen) == 4
    assert all(c.shape == (2, 4) and c.dtype == np.int32 for c in seen)
    assert d["serving.moe_assignments"] == sum(int(c.sum()) for c in seen)
    assert d["serving.moe_expert_load_max"] == sum(
        int(c.max(axis=1).sum()) for c in seen)
    assert d["serving.moe_experts_touched"] == sum(
        int((c > 0).sum()) for c in seen)
    # Two slots alive then one: an iteration's pairs on held experts
    # never exceed slots alive x top_k x expert layers; idle slots add 0.
    assert all(int(c.sum()) <= 2 * 4 * 2 for c in seen[:2])
    assert all(int(c.sum()) <= 1 * 4 * 2 for c in seen[2:])


def test_the_benchmarks_seeded_tree_has_the_programs_shape():
    from benchmark.builders.latent_moe import seeded_params

    seeded_params(MODEL, CFG, 3, REF)
    with pytest.raises(RuntimeError, match="program's shape"):
        seeded_params(MODEL, config_of(dict(MODEL, q_lora_rank=40)), 3,
                      REF)
