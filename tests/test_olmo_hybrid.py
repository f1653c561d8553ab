"""The ``olmo_hybrid`` decoder (models/olmo_hybrid.py: gated delta-rule
linear attention and multi-head full attention by the published
``layer_types``) through the serving engine, against the plain float32
reference the benchmark keeps (benchmark/refs/olmo-hybrid-7b-l16.py: the
SEQUENTIAL recurrence; it imports nothing of the program).  Toy widths (4
heads of 16 x 64 in the linear layers, 4 of 16 in the full one, chunks of
16, three linear layers to one full), seeded weights, logits and not
tokens."""

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
from benchmark.builders.olmo_hybrid import config_of, seeded_params
from horovod_tpu.memory import planner
from horovod_tpu.models import mamba2_hybrid as mh
from horovod_tpu.models import olmo_hybrid as oh
from horovod_tpu.models.transformer import (TransformerConfig,
                                            init_transformer)
from horovod_tpu.serving import InferenceEngine
from horovod_tpu.serving.kv_cache import PagedKVCache
from test_hybrid_ssm import counter, rollout

REF = cells.load_module("refs", "olmo-hybrid-7b-l16")
FLOPS = cells.load_module("flops", "olmo-hybrid-7b-l16")
with open(os.path.join(cells.HERE, "tests", "fixtures", "configs",
                       "tiny-olmohybrid.json")) as f:
    MODEL = json.load(f)["model"]          # float32
with open(os.path.join(cells.HERE, "configs",
                       "olmo-hybrid-7b-l16.json")) as f:
    CUT = json.load(f)["model"]
CFG = config_of(MODEL)

# float32 on both sides: what is left is the order of sums (a chunk's
# triangular system and its products against the step-by-step recurrence,
# the paged blocks' online softmax, the new token's key beside the store).
# The logits have a spread of 0.16 and these differences measure 2e-6.
# bfloat16 operands in the reference's place move them by 2e-3 and more
# (test_the_tolerance_would_catch_bfloat16), a state HELD in bfloat16 by
# 1e-3, another reading of the config by 0.2 and more: the tolerance sits
# a decade and more under the smallest of those and one over what is
# measured.
TOL = 3e-5


@functools.lru_cache(maxsize=None)
def params():
    return REF.init_params(MODEL, 46)


def prompt(seed, n):
    return [int(t) for t in np.random.default_rng(seed).integers(
        0, MODEL["vocab_size"], size=n)]


@functools.lru_cache(maxsize=None)
def _jitted(what, variant=""):
    return {"reference": jax.jit(lambda p, t: REF.forward(
                MODEL, p, t, "f32", variant)),
            "bf16": jax.jit(lambda p, t: REF.forward(MODEL, p, t, "bf16")),
            "full": jax.jit(lambda p, t: oh.forward_full(p, t, CFG)),
            "last": jax.jit(lambda p, t, n: oh.prefill_step(p, t, n, CFG)),
            "every": jax.jit(lambda p, t, n: oh.prefill_step(
                p, t, n, CFG, last_only=False))}[what]


def reference(seq, variant=""):
    return np.asarray(_jitted("reference", variant)(
        params(), jnp.asarray(seq, jnp.int32)))


# -- the layout and the sizes -------------------------------------------------

def test_the_layout_is_the_published_list():
    cfg = oh.OlmoHybridConfig()
    assert [l for l, k in enumerate(cfg.layer_types) if k == oh.FULL] \
        == [3, 7, 11, 15, 19, 23, 27, 31]
    assert list(cfg.layer_types[:16]) == CUT["layer_types"]
    assert CFG.layer_types == tuple(MODEL["layer_types"]) == (
        oh.LINEAR,) * 3 + (oh.FULL,)
    with pytest.raises(ValueError, match="layer_types"):
        oh.OlmoHybridConfig(num_hidden_layers=3,
                            layer_types=(oh.LINEAR, oh.FULL))
    with pytest.raises(ValueError, match="layer_types"):
        oh.OlmoHybridConfig(num_hidden_layers=2,
                            layer_types=(oh.LINEAR, "mamba"))
    with pytest.raises(ValueError, match="lacks one of the two"):
        oh.OlmoHybridConfig(num_hidden_layers=2, layer_types=(oh.FULL,) * 2)
    with pytest.raises(ValueError, match="as many key heads"):
        oh.OlmoHybridConfig(linear_num_key_heads=15)
    with pytest.raises(ValueError, match="olmo_hybrid"):
        config_of(dict(MODEL, rope_parameters={"rope_theta": 500000.0}))
    with pytest.raises(ValueError, match="olmo_hybrid"):
        config_of(dict(MODEL, tie_word_embeddings=True))


def test_the_published_sizes_count_7_43_billion_parameters_and_the_cut_4_10():
    """Shapes only (``jax.eval_shape``): nothing is allocated."""
    def count(cfg):
        tree = jax.eval_shape(
            lambda: oh.init_olmo_hybrid(jax.random.PRNGKey(0), cfg))
        by_kind = {}
        for kind, lp in zip(cfg.layer_types, tree["layers"]):
            by_kind[kind] = sum(math.prod(x.shape)
                                for x in jax.tree_util.tree_leaves(lp))
        return tree, by_kind, sum(
            math.prod(x.shape) for x in jax.tree_util.tree_leaves(tree))

    cfg = oh.OlmoHybridConfig()
    tree, by_kind, total = count(cfg)
    assert by_kind == {oh.LINEAR: 215_570_172, oh.FULL: 185_809_920}
    assert by_kind == {k: FLOPS.param_counts(CUT)[k] for k in by_kind}
    published = dict(CUT, num_hidden_layers=32,
                     layer_types=list(cfg.layer_types))
    assert total == 7_430_870_688 == FLOPS.total_params(published)
    assert round(total / 1e9, 2) == 7.43
    _, _, cut = count(config_of(CUT))
    assert cut == 4_100_788_944 == FLOPS.total_params(CUT)
    assert round(cut / 1e9, 2) == 4.10 and round(2 * cut / 1e9, 2) == 8.20
    assert tree["layers"][0]["mixer"]["w_in"].shape == (3840, 17340)
    assert tree["layers"][3]["mixer"]["w_qkv"].shape == (3840, 11520)
    assert tree["unembed"].shape == (3840, 100352)
    assert cfg.key_width == 2880 and cfg.value_width == 5760
    assert cfg.conv_width == 11520 and cfg.head_dim == 128
    assert cfg.kv_width == 3840
    # A slot's recurrent state: a [96, 192] matrix a head, two heads to a
    # lane row; 2,280,960 bytes a layer with the tail, 27.37 MB over the
    # cut's 12 linear layers; a cached token 61,440 bytes over its 4 full.
    assert cfg.state_shape == (15, 96, 384)
    model = config_of(CUT).serving_model()
    assert model.slot_layer_bytes == 2_280_960 == FLOPS.slot_state_bytes(CUT)
    assert (model.n_linear, model.n_full) == (12, 4)
    assert round(12 * model.slot_layer_bytes / 1e6, 2) == 27.37
    assert FLOPS.position_bytes(CUT) == 61_440
    # The issue's 13.8 GB: parameters, 96 slots' state and a 3.0 GB pool.
    stores = FLOPS.store_bytes(CUT, 96, 3_000_000_000 // 61_440)
    assert round(stores["state"] / 1e9, 2) == 2.63
    assert round((2 * cut + sum(stores.values())) / 1e9, 1) == 13.8
    assert round(96 * 2816 * 61_440 / 1e9, 1) == 16.6


def test_the_benchmarks_seeded_tree_has_the_programs_shape():
    seeded_params(MODEL, CFG, 3, REF)
    with pytest.raises(RuntimeError, match="program's shape"):
        seeded_params(MODEL, config_of(dict(MODEL,
                                            linear_conv_kernel_dim=3)),
                      3, REF)


def test_the_seeded_initialisation_is_the_stated_one():
    lp = params()["layers"][0]
    mp = lp["mixer"]
    a = np.exp(np.asarray(mp["A_log"]))
    assert 1.0 <= a.min() and a.max() <= 16.0
    step = np.asarray(jax.nn.softplus(mp["dt_bias"]))
    assert 1e-3 * 0.99 < step.min() and step.max() < 1e-1 * 1.01
    assert 0.3 < float(jnp.std(mp["conv_w"])) < 0.7      # d_conv ** -0.5
    assert "conv_b" not in mp
    post = (2.0 * MODEL["num_hidden_layers"]) ** -0.5
    assert np.allclose(np.asarray(lp["norm_mix"]), post)
    assert np.allclose(np.asarray(lp["norm_mlp"]), post)
    for w in (mp["norm"], params()["layers"][3]["mixer"]["q_norm"]):
        w = np.asarray(w)
        assert 0.5 <= w.min() and w.max() <= 1.5 and w.std() > 0.2


def test_the_traffic_reaches_the_negative_eigenvalues():
    """``beta`` passes 1 for a real share of tokens and the decays run from
    a head that forgets in a few steps to one that remembers hundreds: else
    no comparison would reach what ``linear_allow_neg_eigval`` buys."""
    seq = jnp.asarray(prompt(3, 64), jnp.int32)
    p = params()
    # (Layer 0 sees the bare embedding, rms 0.02: its gates sit at their
    # biases.  The later layers' spread with the stream.)
    full = jax.jit(lambda p, t: _gates(p, t))(p, seq)
    beta = np.concatenate([b.ravel() for b, _ in full])
    alpha = np.concatenate([np.exp(g).ravel() for _, g in full])
    assert 0.25 < (beta > 1.0).mean() < 0.75
    assert beta.max() > 1.05 and beta.min() < 0.95
    assert (alpha * (1 - beta)).min() < -0.02
    # (Twelve heads in all here: the slowest of 360 at the published sizes
    # keeps 0.999 a step.)
    assert alpha.min() < 0.7 and alpha.max() > 0.98


def _gates(p, tokens):
    """``(beta, g)`` of every linear layer over one sequence, from the
    program's own pieces."""
    x = p["embed"][tokens].astype(jnp.float32)
    out = []
    for kind, lp in zip(CFG.layer_types, p["layers"]):
        mp = lp["mixer"]
        if kind == oh.LINEAR:
            _, _, a, b = oh._split_in(x, mp, CFG)
            _, _, _, g, beta = oh._delta_inputs(
                jnp.zeros((tokens.shape[0], CFG.conv_width)), a, b, mp, CFG)
            out.append((beta, g))
            mix, _, _ = oh.delta_prefill(x, mp, tokens.shape[0], CFG)
        else:
            q, k, v = oh.project(x, mp, CFG)
            mix = oh._dot(oh._afmoe.attend_block(q, k, v, CFG), mp["w_o"])
        x = oh.mlp(oh.join(x, mix, lp["norm_mix"], CFG), lp, CFG)
    return out


# -- whole sequences ----------------------------------------------------------

@pytest.mark.parametrize("n", [5, 16, 17, 50])
def test_program_and_reference_agree_on_whole_sequences(n):
    """The program's chunked form (chunks of 16: one chunk, an edge, four)
    against the reference's sequential recurrence."""
    seq = prompt(20 + n, n)
    got = _jitted("full")(params(), jnp.asarray(seq, jnp.int32))
    assert np.abs(np.asarray(got) - reference(seq)).max() < TOL


@pytest.mark.parametrize("n,bucket", [(3, 4), (24, 32), (32, 32), (9, 64)])
def test_a_buckets_padding_advances_nothing(n, bucket):
    seq = prompt(40 + n, n)
    toks = jnp.asarray(seq + [7] * (bucket - n), jnp.int32)
    last, left = _jitted("last")(params(), toks, jnp.int32(n))
    every, left_all = _jitted("every")(params(), toks, jnp.int32(n))
    assert float(jnp.abs(last - every[n - 1]).max()) < TOL
    assert np.abs(np.asarray(last) - reference(seq)[-1]).max() < TOL
    _, exact = _jitted("last")(params(), jnp.asarray(seq, jnp.int32),
                               jnp.int32(n))
    for name in ("state", "tail"):
        assert float(jnp.abs(left[name] - left_all[name]).max()) == 0.0
        assert float(jnp.abs(left[name] - exact[name]).max()) < 1e-6
    assert left["state"].shape == (3, *CFG.state_shape)
    assert left["tail"].shape == (3, 3, CFG.conv_width)
    assert left["k"].shape == (1, bucket, CFG.kv_width)


def test_the_tolerance_would_catch_bfloat16():
    seq = prompt(77, 40)
    exact = reference(seq)
    rounded = np.asarray(_jitted("bf16")(params(),
                                         jnp.asarray(seq, jnp.int32)))
    assert np.abs(rounded - exact).max() > 10 * TOL
    cfg16 = config_of(dict(MODEL, dtype="bfloat16"))
    p16 = jax.tree_util.tree_map(lambda a: a.astype(jnp.bfloat16), params())
    got = jax.jit(lambda p, t: oh.forward_full(p, t, cfg16))(
        p16, jnp.asarray(seq, jnp.int32))
    assert np.abs(np.asarray(got) - exact).max() > 10 * TOL


def test_the_tolerance_would_catch_a_state_held_in_bfloat16():
    """Float32 everywhere but the STATE, rounded to bfloat16 after the
    prefill and after every decode step (a wrapper of the test's, not an
    option of the program)."""
    p = params()
    seq = prompt(78, 44)
    exact = reference(seq)
    round16 = lambda s: s.astype(jnp.bfloat16).astype(jnp.float32)
    table = jnp.zeros((1, 16), jnp.int32).at[0, :12].set(
        jnp.arange(1, 13))
    k_pages = jnp.zeros((1, 17, 4, CFG.kv_width), jnp.float32)
    step = jax.jit(lambda st, tok, n: oh.decode_step(
        p, tok, n, st, table, CFG))
    worst = {}
    for held in (False, True):
        _, left = _jitted("last")(p, jnp.asarray(seq[:24], jnp.int32),
                                  jnp.int32(24))
        state = left["state"][:, None]
        kp, vp = mh.write_prompt_pages(k_pages, k_pages, left["k"],
                                       left["v"], table)
        tail = left["tail"][:, None]
        gap = 0.0
        for i in range(24, 44):
            if held:
                state = round16(state)
            n = jnp.asarray([i], jnp.int32)
            logits, new = step((kp, vp, state, tail),
                               jnp.asarray(seq[i:i + 1], jnp.int32), n)
            kp, vp = mh.write_token_rows(kp, vp, new["k"], new["v"], table,
                                         n)
            state, tail = new["state"], new["tail"]
            gap = max(gap, float(np.abs(np.asarray(logits[0])
                                        - exact[i]).max()))
        worst[held] = gap
    assert worst[False] < TOL and worst[True] > 10 * TOL, worst


# Each choice under ``assumed`` in the configuration's file is where the
# equations put it: the reference read another way is thousands of
# tolerances out (measured: 0.2 to 1.0 against a spread of 0.16).
@pytest.mark.parametrize("variant", REF.VARIANTS)
def test_another_reading_of_the_config_fails_the_comparison(variant):
    seq = prompt(5, 24)
    got = np.asarray(_jitted("full")(params(), jnp.asarray(seq, jnp.int32)))
    assert np.abs(got - reference(seq)).max() < TOL
    wrong = reference(seq, variant)
    assert np.abs(got - wrong).max() > 1000 * TOL, np.abs(got - wrong).max()


def test_the_variants_are_the_ones_the_file_assumes():
    with open(os.path.join(cells.HERE, "configs",
                           "olmo-hybrid-7b-l16.json")) as f:
        assumed = json.load(f)["assumed"]
    for variant in REF.VARIANTS:
        assert any(variant in text for text in assumed.values()), variant
    assert {"reordered_norm", "qk_norm", "positions", "beta", "l2_norm",
            "norm_then_gate", "attention_head", "A_log_dt_bias", "chunk",
            "bytes", "weights", "dtype"} <= set(assumed)


@pytest.mark.parametrize("what", ["prefill", "decode"])
def test_the_model_runs_the_kernels_it_is_tested_with(monkeypatch, what):
    """The model's prefill through the chunked kernel and the flash
    forward, and its decode through the step kernel and the paged
    attention kernel (all interpreted), equal what it computes through
    their twins."""
    toks = jnp.asarray(prompt(1, 24) + [0] * 8, jnp.int32)
    if what == "prefill":
        plain, left = _jitted("last")(params(), toks, jnp.int32(24))
        monkeypatch.setattr(oh, "INTERPRET", True)
        fn = jax.jit(lambda p, t, n: oh.prefill_step(p, t, n, CFG))
        text = str(jax.make_jaxpr(fn)(params(), toks, jnp.int32(24)))
        assert "gdn_chunk_scan" in text and "gqa_flash_fwd" in text
        kernel, left_k = fn(params(), toks, jnp.int32(24))
        assert float(jnp.abs(left["state"] - left_k["state"]).max()) < 1e-6
    else:
        eng = engine()
        table, _ = eng.cache.device_tables()
        table = jnp.asarray(np.arange(1, 1 + table.size).reshape(
            table.shape) % eng.cache.n_pages, jnp.int32)
        lengths = jnp.asarray([5, -1, 0, 17, -1, -1, 30, -1], jnp.int32)
        stores = tuple(jax.random.normal(jax.random.PRNGKey(i), a.shape,
                                         a.dtype) * 0.1
                       for i, a in enumerate(eng.cache.arrays))
        step = lambda: jax.jit(lambda p, t: oh.decode_step(
            p, t, lengths, stores, table, CFG))
        plain, new = step()(params(), toks[:8])
        monkeypatch.setattr(oh, "INTERPRET", True)
        text = str(jax.make_jaxpr(step())(params(), toks[:8]))
        assert "gdn_step" in text and "gqa_paged_attn" in text
        kernel, new_k = step()(params(), toks[:8])
        on = np.asarray(lengths) >= 0
        assert float(jnp.abs(new["state"] - new_k["state"]).max()) < 1e-6
        # An idle slot's state is bit for bit what it was, either way.
        for got in (new, new_k):
            assert np.array_equal(np.asarray(got["state"])[:, ~on],
                                  np.asarray(stores[2])[:, ~on])
            assert np.array_equal(np.asarray(got["tail"])[:, ~on],
                                  np.asarray(stores[3])[:, ~on])
        plain, kernel = plain[on], kernel[on]
    assert float(jnp.abs(plain - kernel).max()) < 1e-5


# -- prefill then decode through the engine's stores --------------------------

POOL = 120_000        # bytes: 57 pages of 4 where 8 slots x 32 would be 256


@functools.lru_cache(maxsize=None)
def engine():
    eng = InferenceEngine(params(), CFG, max_slots=8, page_size=4,
                          capacity=128, kv_pool_bytes=POOL,
                          kv_expected_tokens=48)
    eng.warm_start()
    return eng


def check_against_reference(prompts, new, got):
    for p, n, (rows, toks) in zip(prompts, new, got):
        assert len(toks) == n and rows.shape[0] == n
        ref = reference(p + toks)[len(p) - 1:len(p) - 1 + n]
        assert np.abs(rows - ref).max() < TOL


# Ragged slots: a prompt of 3 (bucket 4: a pad that must advance nothing),
# exactly a bucket, prompts across several chunks of the scan, answers that
# carry the state on step by step; the last case fills most of the pool.
@pytest.mark.parametrize("lengths,new", [
    ((3,), (12,)), ((32,), (3,)), ((6, 19), (9, 4)),
    ((20, 5, 70), (6, 14, 3)),
    ((40, 9, 60, 30, 12), (5, 6, 7, 8, 9))])
def test_prefill_then_decode_equals_the_reference(lengths, new):
    eng = engine()
    prompts = [prompt(100 + n, n) for n in lengths]
    got = rollout(eng, prompts, new)
    check_against_reference(prompts, new, got)
    assert eng.cache.free_pages() == eng.cache.total_pages


@pytest.mark.parametrize("bucket", [2, 4, 8, 16, 32, 64, 128])
def test_every_prefill_bucket_serves_the_reference(bucket):
    eng = engine()
    n = bucket - 1 if bucket > 2 else 2
    if bucket == 128:
        n = 120                  # leave room to decode under capacity
    p = prompt(500 + bucket, n)
    assert eng._bucket_for(n) == bucket
    check_against_reference([p], [3], rollout(eng, [p], [3]))


def test_a_slot_admitted_after_an_eviction_carries_nothing_over():
    """Recurrent state has no mask: the slot's state and tails must be
    REPLACED by the next prefill.  A long sequence leaves its state in
    slot 0, a short one follows it there."""
    eng = engine()
    resets = counter("serving.state_slot_resets")
    long_p, short_p = prompt(901, 90), prompt(902, 5)
    rollout(eng, [long_p], [20])
    second = rollout(eng, [short_p], [12])
    assert counter("serving.state_slot_resets") - resets == 2
    fresh = InferenceEngine(params(), CFG, max_slots=8, page_size=4,
                            capacity=128, kv_pool_bytes=POOL,
                            kv_expected_tokens=48)
    first = rollout(fresh, [short_p], [12])
    assert second[0][1] == first[0][1]
    assert np.abs(second[0][0] - first[0][0]).max() == 0.0
    check_against_reference([short_p], [12], second)


def test_run_ahead_loop_equals_the_loop_held_at_depth_0(monkeypatch):
    """Staggered admissions and finishes serve the same tokens one
    iteration ahead as at depth 0, and count the same reads: the paged
    positions attended, the state a live slot makes an iteration move and
    the real prompt tokens prefilled."""
    eng = engine()
    trace = [(prompt(400 + i, n), new, at) for i, (n, new, at) in enumerate(
        [(20, 7, 0), (40, 2, 0), (9, 5, 1), (33, 1, 2), (50, 6, 2),
         (12, 4, 6), (36, 3, 6)])]
    names = ("serving.decode_ahead", "serving.decode_iterations",
             "serving.shared_kv_tokens", "serving.state_bytes_moved",
             "serving.tokens_generated", "serving.prefill_tokens")

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

    ahead, (n_ahead, n_iter, shared, moved, tokens, scanned) = replay()
    monkeypatch.setattr(eng, "_runs_ahead", lambda active: False)
    held, (h_ahead, _, h_shared, h_moved, h_tokens, h_scanned) = replay()
    assert ahead == held and [len(t) for t in ahead] == [
        n for _, n, _ in trace]
    assert h_ahead == 0 and 0.5 * n_iter < n_ahead < n_iter
    assert tokens == h_tokens == sum(n for _, n, _ in trace)
    assert shared == h_shared == sum(len(p) + i for p, n, _ in trace
                                     for i in range(1, n))
    a_slot = 3 * (4 * math.prod(CFG.state_shape) + 3 * CFG.conv_width * 4)
    assert moved == h_moved == 2 * a_slot * sum(n - 1 for _, n, _ in trace)
    # Real prompt tokens (what the chunk kernel's roofline is reckoned on);
    # the buckets' padding is not among them (20 + 40 + 9 + 33 + 50 + 12 +
    # 36 = 200 of 272 bucket rows).
    assert scanned == h_scanned == 200


def test_pages_exhausted_and_slots_free_defers_and_later_admits():
    """The pool holds 57 pages where the slots could map 256: requests
    that may come to hold 70, 84 and 84 positions (18 + 21 + 21 pages)
    overfill it with five slots free; the third waits for the first to
    leave."""
    eng = engine()
    assert eng.cache.total_pages == 57
    assert eng.cache.headroom().tolist() == [57]
    deferred = counter("serving.admission_deferred")
    reqs = [eng.submit(prompt(700 + i, 40), max_new_tokens=n)
            for i, n in enumerate((30, 44, 44))]
    eng.step()
    assert eng.scheduler.occupancy() == 2 and eng.scheduler.queue_depth() == 1
    assert counter("serving.admission_deferred") - deferred >= 1
    assert int(eng.cache.headroom()[0]) == 57 - 18 - 21
    eng.run_until_idle()
    assert all(r.finish_reason == "max_new_tokens" for r in reqs)
    assert [len(r.result(0)) for r in reqs] == [30, 44, 44]
    assert reqs[2].t_admit > reqs[0].t_done - 1e-3
    assert eng.cache.free_pages() == eng.cache.total_pages == 57


# -- the protocol -------------------------------------------------------------

def test_the_counters_of_a_fixed_batch_with_idle_slots():
    model = config_of(CUT).serving_model()
    names = ("serving.shared_kv_tokens", "serving.state_bytes_moved")
    before = [counter(n) for n in names]
    model.observe_launch(np.asarray([899, -1, 0, 2047, -1, -1], np.int32))
    got = [counter(n) - b for n, b in zip(names, before)]
    # Three live slots x 12 layers x 2,280,960 bytes, read and written.
    assert got == [900 + 1 + 2048, 2 * 3 * 12 * 2_280_960]
    model.observe_launch(np.full((6,), -1, np.int32))
    assert counter(names[1]) - before[1] == 2 * 3 * 12 * 2_280_960
    assert model.decode_view(np.asarray([899, -1, 0, 2047], np.int32), 16,
                             176) == (57 + 0 + 128) * 16 / 4


def test_the_cache_entry_is_four_paged_layers_in_a_pool_the_state_and_the_tail():
    entry = config_of(CUT).serving_model().cache_entry()
    assert entry["n_layers"] == 4 and entry["widths"] == (3840, 3840)
    assert (entry["n_heads"], entry["head_dim"]) == (30, 128)
    assert "groups" not in entry
    assert [(s["name"], s["kind"], s["shape"]) for s in entry["slot_stores"]
            ] == [("delta_state", "state", (12, 15, 96, 384)),
                  ("conv_tail", "state", (12, 3, 11520))]
    assert entry["slot_stores"][0]["dtype"] == jnp.float32
    # The toy engine's cache manager holds them as told: a POOL of pages
    # smaller than the slots' capacity, and the per-slot stores whole.
    c = engine().cache
    assert c.n_layers == 1 and len(c.arrays) == 4 and c.arrays[:2] == c.pages
    assert c.pages[0].shape == (1, 58, 4, CFG.kv_width)
    assert c.total_pages == 57 < 8 * c.pages_per_slot
    assert [x.shape for x in c.slot_state] == [
        (3, 8, *CFG.state_shape), (3, 8, 3, CFG.conv_width)]
    nbytes = c.slot_store_bytes()
    assert nbytes == {"state": 8 * 3 * (4 * math.prod(CFG.state_shape)
                                        + 3 * CFG.conv_width * 4)}
    assert telemetry.metrics()["serving.state_bytes"]["value"] \
        == nbytes["state"]
    # The pool's gauges, under the full group's name.
    got = telemetry.metrics()
    assert got["serving.kv_group_pages_total.full"]["value"] == 57
    assert got["serving.kv_group_pages_peak.full"]["value"] <= 57
    assert c.group_pages() == {"full": (0, 57)}


def test_the_planner_prices_the_pool_and_the_per_slot_stores():
    c = engine().cache
    entry = engine().model.cache_entry()
    assert planner.slot_store_bytes(entry["slot_stores"], 8, c.capacity) \
        == sum(c.slot_store_bytes().values())
    entry = config_of(CUT).serving_model().cache_entry()
    group = ({"name": "full", "n_layers": 4},)
    pools = planner.size_page_pools(group, 15_360, 16, 176, 96,
                                    3_000_000_000, expected_tokens=768)
    assert pools == (3050,)          # + the trash page: 3051 x 983,040 B
    assert 48_000 < pools[0] * 16 < 49_000       # tokens
    plan = planner.plan_serving(
        entry["n_layers"], entry["n_heads"], entry["head_dim"], 96, 176, 16,
        dtype="bfloat16", slot_stores=entry["slot_stores"], groups=group,
        pool_pages=pools).framework
    assert round(plan["serving.slot_state"] / 1e9, 2) == 2.63
    assert plan["serving.kv_pages"] == 3051 * 16 * 61_440
    assert round(plan["serving.kv_pages"] / 1e9, 2) == 3.00
    # Without a pool every slot would hold its capacity: 16.6 GB.
    whole = planner.plan_serving(
        entry["n_layers"], entry["n_heads"], entry["head_dim"], 96, 176, 16,
        dtype="bfloat16", slot_stores=entry["slot_stores"]).framework
    assert round(whole["serving.kv_pages"] / 1e9, 1) == 16.6


@pytest.mark.parametrize("stores", [False, True])
def test_a_pool_and_per_slot_stores_do_not_change_each_others_arrays(stores):
    """A pool without stores and stores without a pool are the stores the
    parent built: the same arrays in the same order, so the same program
    text; only the page count and the admission arithmetic know the
    pool."""
    spec = ({"name": "s", "kind": "state", "shape": (2, 5, 6),
             "dtype": jnp.float32},) if stores else ()
    kw = dict(dtype=jnp.float32, entry_widths=(16, 16), slot_stores=spec)
    plain = PagedKVCache(3, 2, 8, 4, 6, 4, **kw)
    pooled = PagedKVCache(3, 2, 8, 4, 6, 4, pool_pages=(10,), **kw)
    assert [a.shape[2:] for a in plain.arrays] == [
        a.shape[2:] for a in pooled.arrays]
    assert [a.shape for a in plain.arrays[2:]] == [
        a.shape for a in pooled.arrays[2:]]
    assert plain.pages[0].shape[1] == 25 and pooled.pages[0].shape[1] == 11
    assert plain.table_width == pooled.table_width == 6
    assert not plain._group_gauges and len(pooled._group_gauges) == 1
    # The pool reserves; the plain store prices the prompt alone.
    assert plain.admission_need([1] * 9, 12).tolist() == [3]
    assert pooled.admission_need([1] * 9, 12).tolist() == [6]
    pooled.begin_slot(0, 9, reserve_tokens=21)
    assert pooled.headroom().tolist() == [4] and pooled.free_pages() == 7


def test_the_identity_tells_the_family_from_the_other_hybrid():
    mine = engine().model.identity()
    other = mh.Mamba2HybridConfig().serving_model().identity()
    assert mine["family"] == "olmo_hybrid" != other["family"]
    assert mine["layer_types"] == MODEL["layer_types"]
    assert mine["linear"] == [4, 4, 16, 64, 4, True, 16]
    assert config_of(dict(MODEL, linear_allow_neg_eigval=False)
                     ).serving_model().identity() != mine


def test_prefix_cache_draft_and_tensor_parallel_are_refused_with_reasons():
    eng = InferenceEngine(params(), CFG, max_slots=2, page_size=4,
                          capacity=64, prefix_cache=True)
    assert not eng.cache.prefix_enabled
    assert "not page-addressable" in eng.model.prefix_cache_why
    dcfg = TransformerConfig(vocab_size=MODEL["vocab_size"], d_model=32,
                             n_heads=2, n_layers=1, d_ff=64, max_seq_len=512)
    draft = (init_transformer(jax.random.PRNGKey(0), dcfg), dcfg)
    with pytest.raises(ValueError, match="speculative decoding"):
        InferenceEngine(params(), CFG, max_slots=2, page_size=4,
                        capacity=64, draft=draft)
    from horovod_tpu.core.topology import make_mesh

    mesh = make_mesh(data=jax.device_count() // 2, model=2)
    with pytest.raises(ValueError, match="per-slot state stores.*cannot be "
                                         "sharded"):
        InferenceEngine(params(), CFG, mesh=mesh, max_slots=2, page_size=4,
                        capacity=64)
