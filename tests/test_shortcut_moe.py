"""The shortcut-connected mixture-of-experts decoder
(models/shortcut_moe.py: two latent attentions and two dense feed-forwards
a layer, one expert layer on the shortcut, zero-compute experts beside the
real ones, softmax top-k with a choice bias) through the serving engine,
against the plain float32 reference the benchmark keeps
(benchmark/refs/longcat-flash-omni-ep32.py, which imports nothing of the
program).  Tiny widths, seeded weights, logits and not tokens."""

import functools
import json
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import horovod_tpu.telemetry as telemetry
from benchmark import cells
from benchmark.builders import latent_moe as latent_builder
from benchmark.builders.shortcut_moe import config_of, seeded_params
from horovod_tpu.models import latent_moe as lm
from horovod_tpu.models import shortcut_moe as sm
from horovod_tpu.models.transformer import (TransformerConfig,
                                            init_transformer)
from horovod_tpu.parallel.expert import (held_chunk_rows, moe_layer_held,
                                         route_softmax_top_k)
from horovod_tpu.serving import InferenceEngine
from test_latent_moe import view_tokens_of

REF = cells.load_module("refs", "longcat-flash-omni-ep32")
FLOPS = cells.load_module("flops", "longcat-flash-omni-ep32")
FIXTURES = os.path.join(cells.HERE, "tests", "fixtures", "configs")
with open(os.path.join(FIXTURES, "tiny-longcat.json")) as f:
    MODEL = json.load(f)["model"]      # float32; 4 of 16 real experts held,
CFG = config_of(MODEL)                 # 8 zero-compute outputs, 4 a token
UNCUT = dict(MODEL, n_routed_experts=16)       # every real expert held
REAL, ZERO, TOP_K = 16, 8, 4
D = MODEL["hidden_size"]

# float32 on both sides: what is left is the order of sums (the paged
# view, the absorbed form, sorted rows against dense masked products).
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


# -- (a) the program against the reference -----------------------------------

def test_program_and_reference_agree_on_whole_sequences():
    toks = jnp.asarray([prompt(11, 48), prompt(12, 48)], jnp.int32)
    logits, entries, counts, zero, routed = jax.jit(
        lambda p, t: sm.forward_full(p, t, CFG))(params(), toks)
    want = REF.served_logits(MODEL, params(), np.asarray(toks).tolist())
    assert np.abs(np.asarray(logits) - np.stack(want)).max() < TOL
    # Two cache layers a decoder layer; one expert layer a decoder layer.
    assert entries.shape == (4, 2, 48, CFG.entry_width)
    assert counts.shape == (2, 4) and counts.dtype == jnp.int32
    assert np.asarray(routed).tolist() == [2 * 48 * TOP_K] * 2
    assert all(0 < z < 2 * 48 * TOP_K for z in np.asarray(zero))


def test_the_tolerance_would_catch_bfloat16():
    seqs = [prompt(5, 40)]
    f32 = REF.served_logits(MODEL, params(), seqs, "f32")[0]
    b16 = REF.served_logits(MODEL, params(), seqs, "bf16")[0]
    assert np.abs(f32 - b16).max() > 10 * TOL


def test_the_reference_in_blocks_is_the_reference_layer_by_layer():
    """``served_logits`` upcasts one attention, one feed-forward, one
    expert at a time; the same equations a whole layer at a time
    (``decoder_layer``) give the same logits."""
    seq = prompt(6, 40)
    p = params()
    x = p["embed"][jnp.asarray(seq)]
    for lp in p["layers"]:
        x = REF.decoder_layer(MODEL, lp, x, "f32")
    whole = REF.head(MODEL, p["norm_f"], p["unembed"], x, "f32")
    blocks = REF.served_logits(MODEL, p, [seq])[0]
    assert np.abs(np.asarray(whole) - blocks).max() < 1e-5


def test_absorbed_attention_equals_rebuilt():
    toks = jnp.asarray([prompt(21, 40)], jnp.int32)
    rebuilt = jax.jit(lambda p, t: sm.forward_full(p, t, CFG))(
        params(), toks)
    absorbed = jax.jit(lambda p, t: sm.forward_full(
        p, t, CFG, absorbed=True))(params(), toks)
    assert np.abs(np.asarray(rebuilt[0]) - np.asarray(absorbed[0])
                  ).max() < 1e-5
    for a, b in zip(rebuilt[2:], absorbed[2:]):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


def _moved_shortcut(p, seq):
    """The reference's pieces with the expert layer's result added at the
    end of the FIRST sublayer: a sequential layer, not the published
    one."""
    x = p["embed"][jnp.asarray(seq)]
    eps = MODEL["rms_norm_eps"]
    for lp in p["layers"]:
        for j in (0, 1):
            x = x + REF.attention(MODEL, lp["attn"][j], x, "f32")
            g = REF._rms(x, lp["ffn_norm"][j], eps)
            x = x + REF.swiglu(lp["ffn"][j], g, "f32")
            if j == 0:
                x = x + REF.expert_ffn(MODEL, lp, g, "f32")
    return np.asarray(REF.head(MODEL, p["norm_f"], p["unembed"], x, "f32"))


def test_the_shortcut_lands_after_the_second_dense_ffn():
    seq = prompt(31, 40)
    got = np.asarray(jax.jit(lambda p, t: sm.forward_full(p, t, CFG))(
        params(), jnp.asarray([seq], jnp.int32))[0][0])
    assert np.abs(got - REF.served_logits(MODEL, params(), [seq])[0]
                  ).max() < TOL
    assert np.abs(got - _moved_shortcut(params(), seq)).max() > 10 * TOL


def test_the_rescaling_after_the_norms_is_in_both_and_seen():
    """``mla_scale_q_lora`` / ``mla_scale_kv_lora``: sqrt(64/48) on the
    queries, sqrt(64/32) on the latent (what the cache holds); a model
    without them is another model."""
    assert REF.lora_scales(MODEL) == pytest.approx(((64 / 48) ** 0.5,
                                                    (64 / 32) ** 0.5))
    assert lm.lora_scales(CFG.mla) == REF.lora_scales(MODEL)
    assert lm.lora_scales(lm.LatentMoEConfig()) == (None, None)
    seq = prompt(32, 24)
    plain = dict(MODEL, mla_scale_q_lora=False, mla_scale_kv_lora=False)
    with_scale = REF.served_logits(MODEL, params(), [seq])[0]
    without = REF.served_logits(plain, params(), [seq])[0]
    assert np.abs(with_scale - without).max() > 100 * TOL
    got = jax.jit(lambda p, t: sm.forward_full(p, t, config_of(plain)))(
        params(), jnp.asarray([seq], jnp.int32))[0][0]
    assert np.abs(np.asarray(got) - without).max() < TOL
    assert lm.softmax_scale(CFG.mla) == pytest.approx(24 ** -0.5)
    np.testing.assert_allclose(lm.yarn_inv_freq(CFG.mla),
                               REF.inv_freq(MODEL), rtol=1e-6)


# -- (b) prefill then decode through the latent paged cache -------------------

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
    orig_prefill, orig_decode = eng._prefill, eng._decode_iteration

    def prefill(slot, req, *a, **kw):
        out = orig_prefill(slot, req, *a, **kw)
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
    # The live lengths, whole pages, the mean over the slots (both cache
    # layers of a decoder layer read the same): test_latent_moe.py's sum.
    assert (counter("serving.decode_view_tokens") - views
            == view_tokens_of(lengths, new))
    seqs = [p + toks for p, (_, toks) in zip(prompts, got)]
    want = REF.served_logits(MODEL, params(), seqs, "f32")
    for p, n, (rows, toks), ref in zip(prompts, new, got, want):
        assert len(toks) == n and rows.shape[0] == n
        ref_rows = ref[len(p) - 1:len(p) - 1 + n]
        assert np.abs(rows - ref_rows).max() < TOL
    assert eng.cache.free_pages() == eng.cache.total_pages


def test_run_ahead_loop_equals_the_loop_held_at_depth_0(monkeypatch):
    """The decode loop one iteration ahead over this model's program
    (three counts beside the logits): the same tokens and the same
    counters as the loop that fetches before it launches."""
    eng = engine()
    trace = [(prompt(400 + i, n), new, at) for i, (n, new, at) in enumerate(
        [(20, 7, 0), (70, 2, 0), (9, 5, 1), (33, 1, 2), (140, 6, 2)])]
    names = ("serving.decode_ahead", "serving.decode_iterations",
             "serving.moe_assignments", "serving.moe_zero_assignments",
             "serving.moe_routed_pairs")

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

    ahead, counted = replay()
    monkeypatch.setattr(eng, "_runs_ahead", lambda active: False)
    held, h_counted = replay()
    assert ahead == held
    assert h_counted[0] == 0 and 0.5 * counted[1] < counted[0] < counted[1]
    # (the loop that runs ahead may launch one iteration it then drops)
    assert counted[2:] == h_counted[2:] and min(counted[2:]) > 0
    # Every token but a prefill's rode one slot of one decode iteration
    # through top_k outputs of each of the two expert layers.
    assert counted[4] == (sum(n for _, n, _ in trace) - len(trace)
                          ) * TOP_K * 2


# -- (c) the expert layer: the share, the zero-compute experts, the bias ------

def _layer_params(seed, held, offset):
    """One layer of the UNCUT tiny model, and the share ``offset ..
    offset + held`` of its real experts."""
    lp = REF.init_params(UNCUT, seed)["layers"][1]
    share = dict(lp, **{k: lp[k][offset:offset + held]
                        for k in ("w_gate", "w_up", "w_down")})
    return lp, share


def _held(h, share, offset, **kw):
    router, bias = kw.pop("router", share["router"]), share["router_bias"]
    return moe_layer_held(
        h, share, num_experts=REAL + ZERO, expert_offset=offset,
        top_k=TOP_K, zero_experts=ZERO,
        routing=lambda x: route_softmax_top_k(x, router, bias, TOP_K, 6.0),
        **kw)


def test_all_shares_add_up_to_the_uncut_layer():
    """The zero-compute term is computed by every rank alike (where the
    token lives) and counted ONCE when the shares are added."""
    lp, _ = _layer_params(3, 16, 0)
    h = jax.random.normal(jax.random.PRNGKey(4), (24, D), jnp.float32)
    whole = REF.expert_ffn(dict(UNCUT, expert_offset=0), lp, h, "f32")
    idx, w = REF.route(UNCUT, h, lp["router"], lp["router_bias"], "f32")
    zero = REF.zero_term(UNCUT, h, idx, w)
    total, assigned, zero_pairs = zero, 0, set()
    for offset in range(0, 16, 4):
        _, share = _layer_params(3, 4, offset)
        out = _held(h, share, offset)
        ref = REF.expert_ffn(dict(MODEL, expert_offset=offset), share, h,
                             "f32")
        assert np.abs(np.asarray(out.out) - np.asarray(ref)).max() < 1e-5
        total = total + (out.out - zero)        # the zero term once
        assigned += int(out.counts.sum())
        zero_pairs.add(int(out.zero_pairs))
        assert int(out.routed_pairs) == 24 * TOP_K
    assert np.abs(np.asarray(total) - np.asarray(whole)).max() < 1e-5
    # Every pair exactly once: on a real expert of some rank, or on a
    # zero-compute one (the same count on every rank).
    (z,) = zero_pairs
    assert z == int((np.asarray(idx) >= REAL).sum()) > 0
    assert assigned + z == 24 * TOP_K


@pytest.mark.parametrize("chunk_rows", [8, None])
def test_a_token_with_only_zero_compute_choices_gets_its_input_back(
        chunk_rows):
    """Tokens 0..4 choose four zero-compute outputs: they get ``x *
    sum(w)``, count nowhere on held experts, and nothing is dropped; the
    masked rows reach no expert of either kind."""
    _, share = _layer_params(5, 4, 4)
    # Zero-compute columns 16..19 of the router are all ones: they win
    # for the first five tokens (every value positive) and lose for the
    # rest (every value negative).
    h = jnp.abs(jax.random.normal(jax.random.PRNGKey(6), (40, D),
                                  jnp.float32))
    h = h * jnp.where(jnp.arange(40) < 5, 1.0, -1.0)[:, None]
    router = share["router"].at[:, 16:20].set(1.0)
    mask = jnp.arange(40) < 37                  # three rows are padding
    out = _held(h, share, 4, router=router, token_mask=mask,
                chunk_rows=chunk_rows)
    idx, w = route_softmax_top_k(h, router, share["router_bias"], TOP_K, 6.0)
    idx, w = np.asarray(idx), np.asarray(w)
    assert (idx[:5] >= REAL).all()
    np.testing.assert_allclose(
        np.asarray(out.out)[:5], np.asarray(h)[:5] * w[:5].sum(-1)[:, None],
        rtol=1e-5, atol=1e-6)
    live = idx[:37]
    want = [(live == 4 + e).sum() for e in range(4)]
    assert np.asarray(out.counts).tolist() == want
    assert int(out.zero_pairs) == (live >= REAL).sum() >= 20
    assert int(out.routed_pairs) == 37 * TOP_K
    ref = REF.expert_ffn(dict(MODEL, expert_offset=4),
                         dict(share, router=router), h, "f32")
    assert np.abs(np.asarray(out.out)[:37] - np.asarray(ref)[:37]
                  ).max() < 1e-5
    assert np.abs(np.asarray(out.out)[37:]).max() == 0.0


def test_the_bias_changes_the_choice_and_never_a_kept_weight():
    lp, _ = _layer_params(8, 16, 0)
    h = jax.random.normal(jax.random.PRNGKey(9), (64, D), jnp.float32)
    none = jnp.zeros((REAL + ZERO,), jnp.float32)
    i0, w0 = (np.asarray(a) for a in route_softmax_top_k(
        h, lp["router"], none, TOP_K, 6.0))
    # The seeded bias of the fixture already flips some choices...
    i1, w1 = (np.asarray(a) for a in route_softmax_top_k(
        h, lp["router"], lp["router_bias"], TOP_K, 6.0))
    changed = [t for t in range(64) if set(i0[t]) != set(i1[t])]
    assert 5 < len(changed) < 64
    # ...and a large one on output 3 puts it among every token's four.
    i2, w2 = (np.asarray(a) for a in route_softmax_top_k(
        h, lp["router"], none.at[3].set(1.0), TOP_K, 6.0))
    assert (i2 == 3).any(axis=1).all() and not (i0 == 3).any(axis=1).all()
    scores = np.asarray(jax.nn.softmax(jnp.dot(
        h, lp["router"], precision=jax.lax.Precision.HIGHEST), axis=-1))
    for idx, w in ((i0, w0), (i1, w1), (i2, w2)):
        # Whatever chose it, an expert's weight is 6 x its UNBIASED
        # score, not renormalised.
        np.testing.assert_allclose(
            w, 6.0 * np.take_along_axis(scores, idx, axis=1), rtol=1e-5)
    assert (w2.sum(-1) < w0.sum(-1) + 1e-6).all()
    # The reference routes the same way.
    ri, rw = REF.route(UNCUT, h, lp["router"], lp["router_bias"], "f32")
    np.testing.assert_array_equal(np.sort(np.asarray(ri), -1),
                                  np.sort(i1, -1))
    np.testing.assert_allclose(np.sort(np.asarray(rw), -1),
                               np.sort(w1, -1), rtol=1e-5)


def test_a_layer_without_a_shared_expert_and_with_one():
    """The held-experts layer is ONE code path for both families: without
    ``shared`` in its parameters it adds none; ``latent_moe``'s call (no
    routing given, no zero-compute outputs) still counts all pairs."""
    _, share = _layer_params(5, 4, 0)
    h = jax.random.normal(jax.random.PRNGKey(2), (16, D), jnp.float32)
    sigmoid = moe_layer_held(h, {k: share[k] for k in (
        "router", "w_gate", "w_up", "w_down")}, num_experts=REAL + ZERO,
        expert_offset=0, top_k=TOP_K, routed_scale=2.5)
    assert int(sigmoid.zero_pairs) == 0
    assert int(sigmoid.routed_pairs) == 16 * TOP_K
    ffn = REF.init_params(MODEL, 1)["layers"][0]["ffn"][0]
    with_shared = moe_layer_held(h, dict(share, shared=ffn),
                                 num_experts=REAL + ZERO, expert_offset=0,
                                 top_k=TOP_K, routed_scale=2.5)
    alone = REF.swiglu(ffn, h, "f32")
    assert np.abs(np.asarray(with_shared.out - sigmoid.out)
                  - np.asarray(alone)).max() < 1e-5


@pytest.mark.parametrize("tokens,rows", [(128, 128), (1024, 512), (2, 24),
                                         (40, 128), (4096, 2048)])
def test_chunk_rows_follow_the_routers_width(tokens, rows):
    """16 held of 768 outputs, 12 a token: a quarter of a pair a token."""
    assert held_chunk_rows(tokens, 12, 16, 768) == rows


def test_held_experts_among_the_zero_compute_outputs_are_refused():
    _, share = _layer_params(5, 4, 4)
    with pytest.raises(ValueError, match="compute nothing"):
        _held(jnp.zeros((2, D)), share, 14)


# -- (d) the protocol: cache entry, identity, refusals, counters --------------

def test_the_store_holds_two_layers_a_decoder_layer_and_decode_writes_both():
    eng = engine()
    entry = eng.model.cache_entry()
    assert entry["n_layers"] == 2 * MODEL["num_layers"] == 4
    (store,) = eng.cache.pages
    assert store.shape == (4, 1 + 8 * 32, 8, 128)   # 32 + 8, to a lane row
    assert counter("serving.cache_entry_bytes") == 128 * 4
    eng.cache.replace_pages(jnp.zeros_like(store))
    rollout(eng, [prompt(51, 16)], [3])
    (store,) = eng.cache.pages
    written = np.asarray(jnp.any(store[:, 1:] != 0, axis=-1))  # not trash
    per_layer = written.reshape(4, -1).sum(axis=1)
    # Sixteen prompt positions (a whole bucket: no padding) from the
    # prefill and two from the decode iterations, on a third page, in
    # EVERY cache layer (the last served token is never fed back).
    assert per_layer.tolist() == [18, 18, 18, 18]


def test_identity_tells_the_two_latent_families_apart():
    with open(os.path.join(FIXTURES, "tiny-axk1.json")) as f:
        other = latent_builder.config_of(json.load(f)["model"])
    a, b = CFG.serving_model().identity(), other.serving_model().identity()
    assert a["family"] == "shortcut_moe" and b["family"] == "latent_moe"
    assert json.loads(engine().fingerprint) == json.loads(json.dumps(a))
    # Everything that changes a compiled program or what the cache holds.
    for key, value in (("zero_expert_num", 4), ("moe_topk", 2),
                       ("mla_scale_kv_lora", False), ("num_layers", 1),
                       ("routed_scaling_factor", 2.0), ("rope_theta", 5e5),
                       ("expert_offset", 4)):
        changed = config_of(dict(MODEL, **{key: value}))
        assert changed.serving_model().identity() != a, key


def test_prefix_cache_draft_and_tensor_parallel_stay_refused():
    eng = engine()
    assert not eng.cache.prefix_enabled and sm.ShortcutMoEServing.prefix_cache_why
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


def test_decode_feeds_the_five_expert_counters():
    eng = engine()
    names = ("serving.moe_assignments", "serving.moe_expert_load_max",
             "serving.moe_experts_touched", "serving.moe_zero_assignments",
             "serving.moe_routed_pairs", "serving.decode_iterations")
    before = {n: counter(n) for n in names}
    seen = []
    orig = eng.model.observe_decode

    def spy(extras):
        seen.append([np.asarray(e) for e in extras])
        orig(extras)

    eng.model.observe_decode = spy
    try:
        rollout(eng, [prompt(41, 12), prompt(42, 30)], [5, 3])
    finally:
        del eng.model.observe_decode
    d = {n: counter(n) - before[n] for n in names}
    assert d["serving.decode_iterations"] == len(seen) == 4
    assert all(c.shape == (2, 4) and z.shape == r.shape == (2,)
               for c, z, r in seen)
    assert d["serving.moe_assignments"] == sum(int(c.sum())
                                               for c, _, _ in seen)
    assert d["serving.moe_expert_load_max"] == sum(
        int(c.max(axis=1).sum()) for c, _, _ in seen)
    assert d["serving.moe_experts_touched"] == sum(
        int((c > 0).sum()) for c, _, _ in seen)
    assert d["serving.moe_zero_assignments"] == sum(int(z.sum())
                                                    for _, z, _ in seen)
    # Two slots alive for two iterations, then one for two: live tokens x
    # 4 a token x 2 expert layers; idle slots add nothing anywhere.
    assert [int(r.sum()) for _, _, r in seen] == [16, 16, 8, 8]
    assert d["serving.moe_routed_pairs"] == 48
    for c, z, r in seen:
        assert int(c.sum()) + int(z.sum()) <= int(r.sum())
    assert 0 < d["serving.moe_zero_assignments"] < 48


def test_the_benchmarks_seeded_tree_has_the_programs_shape():
    seeded_params(MODEL, CFG, 3, REF)
    with pytest.raises(RuntimeError, match="program's shape"):
        seeded_params(MODEL, config_of(dict(MODEL, q_lora_rank=40)), 3, REF)
    with pytest.raises(ValueError, match="plain rotary"):
        config_of(dict(MODEL, rope_scaling={"type": "yarn"}))
    bias = params()["layers"][0]["router_bias"]
    assert bias.dtype == jnp.float32 and bias.shape == (REAL + ZERO,)


# -- (e) the published sizes ---------------------------------------------------

def test_the_published_sizes_give_the_issues_parameter_and_byte_counts():
    with open(os.path.join(cells.HERE, "configs",
                           "longcat-flash-omni-ep32.json")) as f:
        config = json.load(f)
    model, m = config["model"], 1e6
    p = FLOPS.param_counts(model)
    assert round(p["mla"] / m, 2) == 90.57
    assert round(p["dense_ffn"] / m, 2) == 226.49
    assert round(p["router"] / m, 2) == 4.72 and FLOPS.router_outputs(
        model) == 768
    assert round(p["expert"] / m, 2) == 37.75
    assert round(FLOPS.layer_params_outside_experts(model) / m, 1) == 638.8
    assert round(16 * p["expert"] / m, 1) == 604.0
    assert round((FLOPS.layer_params_outside_experts(model)
                  + 16 * p["expert"]) / m, 1) == 1242.8
    assert round((p["embed"] + p["head"]) / m, 1) == 201.3
    assert round(FLOPS.total_params(model) / 1e9, 2) == 5.17
    assert round(2 * FLOPS.total_params(model) / 1e9, 2) == 10.35
    # The cache: 8 latent layers, 576 values needed, 640 stored.
    cfg = config_of(model)
    assert cfg.cache_layers == FLOPS.cache_layers(model) == 8
    assert cfg.entry_width == 640 and FLOPS.entry_bytes(model) == 1152
    assert 8 * 640 * 2 * 128 * 2048 == pytest.approx(2.68e9, rel=2e-3)
    # The program's tree holds exactly the counted parameters (and the
    # norms' and the bias's few).
    tree = jax.eval_shape(lambda: sm.init_shortcut_moe(
        jax.random.PRNGKey(0), cfg))
    sizes = [int(np.prod(x.shape)) for x in jax.tree_util.tree_leaves(tree)]
    small = sum(s for s in sizes if s < 10_000)
    assert sum(sizes) - small == FLOPS.total_params(model)
    # One decode iteration at 40 slots alive, 544 cached tokens each:
    # 5.11 GB outside the experts, 2.3 GB of touched experts, 0.2 GB of
    # head, 0.2 GB of cache: 7.8 GB, 9.5 ms at 819 GB/s.
    touched = 4 * FLOPS.expected_touched(model, 40)
    assert touched == pytest.approx(29.9, rel=1e-2)
    outside = 4 * FLOPS.layer_params_outside_experts(model) * 2
    assert outside == pytest.approx(5.11e9, rel=1e-3)
    least = FLOPS.decode_iteration_bytes(model, touched, 40 * 544, 40)
    assert least == pytest.approx(7.78e9, rel=1e-2)
    assert least / 819e9 == pytest.approx(9.5e-3, rel=1e-2)
    # A third of a token's pairs cost nothing; a quarter of a pair a
    # token lands on the 16 held.
    assert model["zero_expert_num"] / FLOPS.router_outputs(
        model) == pytest.approx(1 / 3)
    assert FLOPS.held_pair_share(model) == 0.25
    assert config["reduced"] == ["num_layers", "n_routed_experts",
                                 "vocab_size"]
