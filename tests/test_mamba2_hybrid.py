"""The Mamba-2 hybrid decoder (models/mamba2_hybrid.py: state-space and
grouped-query attention layers by the published ``layer_types``) through
the serving engine, against the plain float32 reference the benchmark
keeps (benchmark/refs/granite-4.0-h-micro.py: the SEQUENTIAL recurrence;
it imports nothing of the program).  Toy widths (8 heads of 32 over 16
state rows, chunks of 8, six layers of which two attend), seeded weights,
logits and not tokens."""

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
from benchmark.builders.mamba2_hybrid import config_of, seeded_params
from horovod_tpu.models import hybrid_ssm as hs
from horovod_tpu.models import mamba2_hybrid as mh
from horovod_tpu.models.transformer import (TransformerConfig,
                                            init_transformer)
from horovod_tpu.ops import gqa_paged_attention as gpa
from horovod_tpu.ops import ssd
from horovod_tpu.serving import InferenceEngine
from test_hybrid_ssm import counter, rollout

REF = cells.load_module("refs", "granite-4.0-h-micro")
FLOPS = cells.load_module("flops", "granite-4.0-h-micro")
with open(os.path.join(cells.HERE, "tests", "fixtures", "configs",
                       "tiny-granite4h.json")) as f:
    MODEL = json.load(f)["model"]          # float32
with open(os.path.join(cells.HERE, "configs",
                       "granite-4.0-h-micro.json")) as f:
    PUBLISHED = json.load(f)["model"]
CFG = config_of(MODEL)

# float32 on both sides: what is left is the order of sums (the chunked
# form's products against the step-by-step recurrence, the paged kernel's
# blocks, the new token's key beside the cached ones, blockwise softmax).
# The logits have a spread of 0.0024 (the embedding is drawn at 0.02 / 12
# and logits_scaling divides by 8); these differences measure 1e-8.
# bfloat16 operands in the reference's place move them by 6e-5 and more
# (test_the_tolerance_would_catch_bfloat16), a wrong multiplier by 1e-4 to
# 2e-2: the tolerance sits a decade and more under the first and two over
# what is measured.
TOL = 2e-6
# The paged kernel's block at the toy sizes, for the tests that want several
# blocks a slot: 16 tokens (4 pages of 4) where the shapes would give 128.
SMALL_TILE = 16 * 4 * CFG.num_attention_heads


@functools.lru_cache(maxsize=None)
def params():
    return REF.init_params(MODEL, 11)


def prompt(seed, n):
    return [int(t) for t in np.random.default_rng(seed).integers(
        0, MODEL["vocab_size"], size=n)]


@functools.lru_cache(maxsize=None)
def _jitted(what, variant="", layout=None):
    return {"reference": jax.jit(lambda p, t: REF.forward(
                MODEL, p, t, "f32", variant, layout)),
            "bf16": jax.jit(lambda p, t: REF.forward(MODEL, p, t, "bf16")),
            "full": jax.jit(lambda p, t: mh.forward_full(p, t, CFG)),
            "last": jax.jit(lambda p, t, n: mh.prefill_step(p, t, n, CFG)),
            "every": jax.jit(lambda p, t, n: mh.prefill_step(
                p, t, n, CFG, last_only=False))}[what]


def reference(seq, variant="", layout=None):
    return np.asarray(_jitted("reference", variant, layout)(
        params(), jnp.asarray(seq, jnp.int32)))


# -- the layout and the sizes -------------------------------------------------

def test_the_layout_is_the_published_list():
    cfg = mh.Mamba2HybridConfig()
    assert list(cfg.layer_types) == PUBLISHED["layer_types"]
    assert [l for l, k in enumerate(cfg.layer_types)
            if k == "attention"] == [5, 15, 25, 35]
    assert CFG.layer_types == tuple(MODEL["layer_types"]) == (
        "mamba", "mamba", "attention", "mamba", "attention", "mamba")
    with pytest.raises(ValueError, match="layer_types"):
        mh.Mamba2HybridConfig(num_hidden_layers=3,
                              layer_types=("mamba", "attention"))
    with pytest.raises(ValueError, match="layer_types"):
        mh.Mamba2HybridConfig(num_hidden_layers=2,
                              layer_types=("mamba", "window"))
    with pytest.raises(ValueError, match="one group"):
        mh.Mamba2HybridConfig(mamba_n_groups=2)
    with pytest.raises(ValueError, match="granitemoehybrid"):
        config_of(dict(MODEL, num_local_experts=8))


def test_the_published_sizes_count_3_19_billion_parameters():
    cfg = mh.Mamba2HybridConfig()
    tree = jax.eval_shape(
        lambda: mh.init_mamba2_hybrid(jax.random.PRNGKey(0), cfg))
    by_kind = {}
    for kind, lp in zip(cfg.layer_types, tree["layers"]):
        by_kind[kind] = sum(math.prod(x.shape)
                            for x in jax.tree_util.tree_leaves(lp))
    assert by_kind == {"mamba": 76_182_976, "attention": 60_821_504}
    total = sum(math.prod(x.shape) for x in jax.tree_util.tree_leaves(tree))
    assert total == 3_191_396_096 == FLOPS.total_params(PUBLISHED)
    assert tree["layers"][0]["mixer"]["w_in"].shape == (2048, 8512)
    assert cfg.d_inner == 4096 and cfg.conv_width == 4352
    assert cfg.head_dim == 64 and cfg.kv_width == 512
    # A slot's recurrent state: a [64, 128] matrix a head kept [128, 64],
    # two heads to a lane row; 2,123,264 bytes a layer with the tail,
    # 76.4 MB over the 36 layers.
    assert cfg.state_shape == (32, 128, 128)
    model = cfg.serving_model()
    assert model.slot_layer_bytes == 2_123_264 == FLOPS.slot_state_bytes(
        PUBLISHED)
    assert round(36 * model.slot_layer_bytes / 1e6, 1) == 76.4
    # The Motivation's 13.3 GB: parameters, state, pages and one view.
    stores = FLOPS.store_bytes(PUBLISHED, 64, 3072)
    assert [round(stores[k] / 1e9, 2) for k in ("state", "paged", "view")] \
        == [4.89, 1.61, 0.40]
    assert round((2 * total + sum(stores.values())) / 1e9, 1) == 13.3


def test_the_benchmarks_seeded_tree_has_the_programs_shape():
    seeded_params(MODEL, CFG, 3, REF)
    with pytest.raises(RuntimeError, match="program's shape"):
        seeded_params(MODEL, config_of(dict(MODEL, mamba_d_conv=3)), 3, REF)


def test_the_seeded_initialisation_is_the_stated_one():
    mp = params()["layers"][0]["mixer"]
    a = np.exp(np.asarray(mp["A_log"]))
    assert 1.0 <= a.min() and a.max() <= 16.0 and a.std() > 1.0
    assert np.all(np.asarray(mp["D"]) == 1.0)
    assert np.all(np.asarray(mp["norm"]) == 1.0)
    step = np.asarray(jax.nn.softplus(mp["dt_bias"]))
    assert 1e-3 * 0.99 < step.min() and step.max() < 1e-1 * 1.01
    assert 0.3 < float(jnp.std(mp["conv_w"])) < 0.7      # d_conv ** -0.5


# -- whole sequences ----------------------------------------------------------

@pytest.mark.parametrize("n", [5, 8, 9, 40])
def test_program_and_reference_agree_on_whole_sequences(n):
    """The program's chunked form (chunks of 8: one chunk, an edge, five)
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
    assert left["state"].shape == (4, *CFG.state_shape)
    assert left["k"].shape == (2, bucket, CFG.kv_width)


def test_the_tolerance_would_catch_bfloat16():
    seq = prompt(77, 40)
    exact = reference(seq)
    rounded = np.asarray(_jitted("bf16")(params(),
                                         jnp.asarray(seq, jnp.int32)))
    assert np.abs(rounded - exact).max() > 10 * TOL
    cfg16 = config_of(dict(MODEL, dtype="bfloat16"))
    p16 = jax.tree_util.tree_map(lambda a: a.astype(jnp.bfloat16), params())
    got = jax.jit(lambda p, t: mh.forward_full(p, t, cfg16))(
        p16, jnp.asarray(seq, jnp.int32))
    assert np.abs(np.asarray(got) - exact).max() > 10 * TOL


# Each multiplier is where the equations put it, and the layout follows
# the list: the reference read another way is ten times outside the
# tolerance and more (measured: below).
@pytest.mark.parametrize("variant,layout", [
    ("residual_1", None), ("scale_sqrt", None), ("gate_after_norm", None),
    ("", ("mamba", "attention", "mamba", "mamba", "attention", "mamba"))])
def test_another_reading_of_the_config_fails_the_comparison(variant, layout):
    seq = prompt(5, 24)
    got = np.asarray(_jitted("full")(params(), jnp.asarray(seq, jnp.int32)))
    assert np.abs(got - reference(seq)).max() < TOL
    if layout is not None:
        # The same tree under a list with an attention layer moved: the
        # shapes no longer fit, so swap the two layers' parameters with it.
        tree = dict(params())
        layers = list(tree["layers"])
        layers[1], layers[2] = layers[2], layers[1]
        tree["layers"] = layers
        wrong = np.asarray(jax.jit(lambda p, t: REF.forward(
            MODEL, p, t, "f32", "", layout))(
                tree, jnp.asarray(seq, jnp.int32)))
    else:
        wrong = reference(seq, variant)
    assert np.abs(got - wrong).max() > 10 * TOL, np.abs(got - wrong).max()


def test_the_logits_are_not_decided_by_the_tied_embedding():
    """The head is the embedding.  Drawn at 0.02 with the residual
    projections divided by depth, ``x_0 = 12 E[token]`` outweighs all the
    layers' branches, the input token's own logit leads the rest by 30
    standard deviations, every greedy token repeats its input and no
    comparison of served tokens sees anything (PERF.md section 6, PR 39:
    found on the chip).  As seeded, the input token is one logit among
    the others, and the spread is sqrt(hidden) x (0.02 / 12) / 8."""
    seq = prompt(9, 48)
    logits = reference(seq)
    assert 0.5 < logits.std() / (math.sqrt(MODEL["hidden_size"]) * 0.02
                                 / MODEL["embedding_multiplier"]
                                 / MODEL["logits_scaling"]) < 2.0
    assert logits.std() > 500 * TOL
    own = logits[np.arange(len(seq)), seq]
    lead = (own - logits.mean(-1)) / logits.std(-1)
    assert abs(lead.mean()) < 3.0        # 0.24 at the published sizes
    assert (logits.argmax(-1) == np.asarray(seq)).mean() < 0.2
    embed = np.asarray(params()["embed"], np.float32)
    assert 0.8 < embed.std() * MODEL["embedding_multiplier"] / 0.02 < 1.2
    w2 = np.asarray(params()["layers"][0]["mlp"]["w2"], np.float32)
    assert 0.9 < w2.std() / 0.02 < 1.1          # not divided by depth


@pytest.mark.parametrize("what", ["prefill", "decode", "paged"])
def test_the_model_runs_the_kernels_it_is_tested_with(monkeypatch, what):
    """The model's prefill through the chunked kernel, its decode through
    the step kernel and its decode's attention through the paged kernel
    (all interpreted) equal what it computes through their twins."""
    toks = jnp.asarray(prompt(1, 24) + [0] * 8, jnp.int32)
    if what == "prefill":
        plain, left = _jitted("last")(params(), toks, jnp.int32(24))
        monkeypatch.setattr(mh, "ssd_chunk_scan", functools.partial(
            ssd.ssd_chunk_scan, interpret=True))
        kernel, left_k = jax.jit(
            lambda p, t, n: mh.prefill_step(p, t, n, CFG))(
                params(), toks, jnp.int32(24))
        assert float(jnp.abs(left["state"] - left_k["state"]).max()) < 1e-6
    else:
        eng = engine()
        # Every slot's table row mapped: nothing cached, one position, a
        # page's edge, one past it, several of the paged kernel's blocks.
        table = jnp.asarray(1 + np.random.default_rng(3).permutation(
            eng.cache.total_pages).reshape(8, -1), jnp.int32)
        lengths = jnp.asarray([5, -1, 0, 17, 1, -1, 70, 4], jnp.int32)
        stores = tuple(jax.random.normal(jax.random.PRNGKey(i), a.shape,
                                         a.dtype) * 0.1
                       for i, a in enumerate(eng.cache.arrays))
        step = lambda: jax.jit(lambda p, t: mh.decode_step(
            p, t, lengths, stores, table, CFG))(params(), toks[:8])
        plain, new = step()
        if what == "decode":
            monkeypatch.setattr(mh, "ssd_step", functools.partial(
                ssd.ssd_step, interpret=True))
        else:
            # 70 cached positions are five blocks.
            monkeypatch.setattr(gpa, "SCORE_TILE_BYTES", SMALL_TILE)
            monkeypatch.setattr(mh, "PAGED_INTERPRET", True)
        kernel, new_k = step()
        on = np.asarray(lengths) >= 0
        assert float(jnp.abs(new["state"] - new_k["state"]).max()) < 1e-6
        # (An idle slot's new key lands in the trash page: the kernel
        # attends nothing for it, the twin its own new value.)
        assert float(jnp.abs(new["k"][:, on] - new_k["k"][:, on]).max()) < 1e-6
        # An idle slot's state is bit for bit what it was, either way.
        for got in (new, new_k):
            assert sorted(got) == ["k", "state", "tail", "v"]
            assert np.array_equal(np.asarray(got["state"])[:, ~on],
                                  np.asarray(stores[2])[:, ~on])
            assert np.array_equal(np.asarray(got["tail"])[:, ~on],
                                  np.asarray(stores[3])[:, ~on])
        plain, kernel = plain[on], kernel[on]
    assert float(jnp.abs(plain - kernel).max()) < 1e-6


# -- prefill then decode through the engine's stores --------------------------

def build(path="twin", warm=False):
    """A toy engine.  ``"twin"``: what the rule gives off the TPU, a table
    row gathered whole; ``"kernel"``: the paged kernel in the interpreter,
    16 tokens (4 pages) a block.  The path is the program's when it is
    traced, so ``warm`` builds every program here."""
    eng = InferenceEngine(params(), CFG, max_slots=8, page_size=4,
                          capacity=128)
    with pytest.MonkeyPatch.context() as patch:
        if path == "kernel":
            patch.setattr(mh, "PAGED_INTERPRET", True)
            patch.setattr(gpa, "SCORE_TILE_BYTES", SMALL_TILE)
        if warm or path == "kernel":
            eng.warm_start()
    return eng


@functools.lru_cache(maxsize=None)
def engine(path="twin"):
    return build(path, warm=True)


def check_against_reference(prompts, new, got):
    for p, n, (rows, toks) in zip(prompts, new, got):
        assert len(toks) == n and rows.shape[0] == n
        ref = reference(p + toks)[len(p) - 1:len(p) - 1 + n]
        assert np.abs(rows - ref).max() < TOL


# Ragged slots: a prompt of 3 (bucket 4: a pad that must advance nothing),
# exactly a bucket, prompts across several chunks of the scan, answers that
# carry the state on step by step; one cached position, a page's edge (4)
# and one past it; the last case holds several blocks of the paged kernel a
# slot beside idle slots.  Off the TPU's twin and through the kernel.
@pytest.mark.parametrize("path", ["twin", "kernel"])
@pytest.mark.parametrize("lengths,new", [
    ((3,), (12,)), ((32,), (3,)), ((6, 19), (9, 4)),
    ((1, 4, 5), (6, 14, 3)),
    ((40, 9, 100, 30, 66, 12), (5, 6, 7, 8, 9, 10))])
def test_prefill_then_decode_equals_the_reference(lengths, new, path):
    eng = engine(path)
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


@pytest.mark.parametrize("path", ["twin", "kernel"])
def test_a_slot_admitted_after_an_eviction_carries_nothing_over(path):
    """Recurrent state has no mask: the slot's state and tails must be
    REPLACED by the next prefill.  A long sequence leaves its state in
    slot 0 and its keys in pages, a short one follows it there."""
    eng = engine(path)
    resets = counter("serving.state_slot_resets")
    long_p, short_p = prompt(901, 90), prompt(902, 5)
    rollout(eng, [long_p], [20])
    second = rollout(eng, [short_p], [12])
    assert counter("serving.state_slot_resets") - resets == 2
    first = rollout(build(path), [short_p], [12])
    assert second[0][1] == first[0][1]
    assert np.abs(second[0][0] - first[0][0]).max() == 0.0
    check_against_reference([short_p], [12], second)


def test_run_ahead_loop_equals_the_loop_held_at_depth_0(monkeypatch):
    """Staggered admissions and finishes serve the same tokens one
    iteration ahead as at depth 0, and count the same reads: the paged
    positions attended and the state a live slot makes an iteration
    move."""
    eng = engine()
    trace = [(prompt(400 + i, n), new, at) for i, (n, new, at) in enumerate(
        [(20, 7, 0), (70, 2, 0), (9, 5, 1), (33, 1, 2), (100, 6, 2),
         (12, 4, 6), (66, 3, 6)])]
    names = ("serving.decode_ahead", "serving.decode_iterations",
             "serving.shared_kv_tokens", "serving.state_bytes_moved",
             "serving.tokens_generated", "serving.window_tokens")

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

    ahead, (n_ahead, n_iter, shared, moved, tokens, window) = replay()
    monkeypatch.setattr(eng, "_runs_ahead", lambda active: False)
    held, (h_ahead, _, h_shared, h_moved, h_tokens, _) = replay()
    assert ahead == held and [len(t) for t in ahead] == [
        n for _, n, _ in trace]
    assert h_ahead == 0 and 0.5 * n_iter < n_ahead < n_iter
    assert tokens == h_tokens == sum(n for _, n, _ in trace)
    # A request at its i-th decoded token attends len(prompt) + i positions
    # (its own included) and moves its state in the four state-space
    # layers once, in and out.
    assert shared == h_shared == sum(len(p) + i for p, n, _ in trace
                                     for i in range(1, n))
    a_slot = 4 * (4 * math.prod(CFG.state_shape) + 3 * CFG.conv_width * 4)
    assert moved == h_moved == 2 * a_slot * sum(n - 1 for _, n, _ in trace)
    assert window == 0


# -- the protocol -------------------------------------------------------------

def test_the_counters_of_a_fixed_batch_with_idle_slots():
    model = mh.Mamba2HybridConfig().serving_model()
    before = [counter("serving.shared_kv_tokens"),
              counter("serving.state_bytes_moved")]
    model.observe_launch(np.asarray([899, -1, 0, 2047, -1, -1], np.int32))
    assert counter("serving.shared_kv_tokens") - before[0] == 900 + 1 + 2048
    # Three live slots x 36 layers x 2,123,264 bytes, read and written.
    assert counter("serving.state_bytes_moved") - before[1] \
        == 2 * 3 * 36 * 2_123_264
    model.observe_launch(np.full((6,), -1, np.int32))
    assert counter("serving.state_bytes_moved") - before[1] \
        == 2 * 3 * 36 * 2_123_264


def test_the_cache_entry_is_four_paged_layers_the_state_and_no_view():
    entry = mh.Mamba2HybridConfig().serving_model().cache_entry()
    assert entry["n_layers"] == 4 and entry["widths"] == (512, 512)
    assert [(s["name"], s["kind"], s["shape"]) for s in entry["slot_stores"]
            ] == [("ssm_state", "state", (36, 32, 128, 128)),
                  ("conv_tail", "state", (36, 3, 4352))]
    assert entry["slot_stores"][0]["dtype"] == jnp.float32
    # The toy engine's cache manager holds them as told: four arrays, no
    # room to gather into.
    c = engine().cache
    assert c.n_layers == 2 and len(c.arrays) == 4 and c.arrays[:2] == c.pages
    assert [x.shape for x in c.slot_state] == [
        (4, 8, *CFG.state_shape), (4, 8, 3, CFG.conv_width)]
    nbytes = c.slot_store_bytes()
    assert nbytes == {"state": 8 * 4 * (4 * math.prod(CFG.state_shape)
                                        + 3 * CFG.conv_width * 4)}
    assert telemetry.metrics()["serving.state_bytes"]["value"] \
        == nbytes["state"]
    assert c.total_pages == 8 * 32


def test_the_planner_prices_the_per_slot_stores_the_entry_declares():
    """The state store is what decides how many slots fit: 76.4 MB of
    state a slot against 25 MB of pages."""
    from horovod_tpu.memory import planner

    c = engine().cache
    entry = engine().model.cache_entry()
    assert planner.slot_store_bytes(entry["slot_stores"], 8, c.capacity) \
        == sum(c.slot_store_bytes().values())
    entry = mh.Mamba2HybridConfig().serving_model().cache_entry()

    def plan(slots):
        return planner.plan_serving(
            entry["n_layers"], entry["n_heads"], entry["head_dim"], slots,
            3072 // 16, 16, dtype="bfloat16",
            slot_stores=entry["slot_stores"]).framework

    got = plan(64)
    assert round(got["serving.slot_state"] / 1e9, 2) == 4.89     # no view
    assert round(got["serving.kv_pages"] / 1e9, 2) == 1.61
    assert plan(32)["serving.slot_state"] * 2 == got["serving.slot_state"]
    assert "serving.slot_state" not in planner.plan_serving(
        4, 8, 64, 64, 192, 16).framework


# The cell's load (23 of 64 alive), every slot at a page's edge or idle, and
# nobody alive: what the kernel copies of one layer, over the slots.
@pytest.mark.parametrize("lengths,page,entries", [
    (tuple(np.random.default_rng(7).integers(64, 2800, 23)) + (-1,) * 41,
     16, 192),
    ((0, 1, 16, 17, 3071, -1, -1, 512), 16, 192),
    ((-1,) * 8, 4, 32)])
def test_decode_view_is_what_the_kernel_copies(lengths, page, entries):
    model = mh.Mamba2HybridConfig().serving_model()
    lengths = np.asarray(lengths, np.int32)
    got = model.decode_view(lengths, page, entries)
    assert got == gpa.tokens_read(lengths, entries, page) / len(lengths)
    live = lengths[lengths >= 0]
    assert got * len(lengths) == sum(-(-int(n) // page) * page for n in live)


def test_the_identity_tells_the_family_from_the_other_hybrid():
    mine = engine().model.identity()
    other = hs.HybridSSMConfig().serving_model().identity()
    assert mine["family"] == "mamba2_hybrid" != other["family"]
    assert mine["layer_types"] == MODEL["layer_types"]
    assert mine["mamba"] == [8, 32, 16, 4, 2, 1, 8]
    assert mine["multipliers"] == [1.0, 12, 0.22, 8]
    assert "decode_chunk_tokens" not in mine      # a field nothing reads
    assert set(mine) != set(other)
    moved = config_of(dict(MODEL, layer_types=[
        "mamba", "attention", "mamba", "mamba", "attention", "mamba"]))
    assert moved.serving_model().identity() != mine


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


def _equations(jaxpr):
    """Every equation of ``jaxpr`` and of what it calls, a Pallas kernel's
    own body left out."""
    for eqn in jaxpr.eqns:
        yield eqn
        if eqn.primitive.name == "pallas_call":
            continue
        for sub in jax.core.jaxprs_in_params(eqn.params):
            yield from _equations(sub)


@pytest.mark.parametrize("path", ["twin", "kernel"])
def test_each_attention_layer_attends_its_own_paged_layer_in_place(
        monkeypatch, path):
    """The decode program as the engine builds it holds no conditional and
    no loop either way.  Through the kernel: one ``gqa_paged_attn`` call an
    attention layer over the WHOLE stores, and no gather of pages.  The
    twin: a table row of keys and one of values gathered an attention
    layer."""
    eng = engine(path)
    table, lengths = eng.cache.device_tables()
    args = (eng.params, *eng.cache.arrays, table, lengths, eng._no_tokens,
            eng._no_override)
    n = len(eng.cache.arrays)

    def fn(params, *rest):
        outs, pages = eng._decode_step(params, rest[:n], *rest[n:])
        return (*outs, *pages)

    if path == "kernel":
        monkeypatch.setattr(mh, "PAGED_INTERPRET", True)
    eqns = list(_equations(jax.make_jaxpr(fn)(*args).jaxpr))
    names = [e.primitive.name for e in eqns]
    assert "cond" not in names and "while" not in names
    c = eng.cache
    rows = (8, c.pages_per_slot, c.page_size, CFG.kv_width)
    gathers = [e for e in eqns if e.primitive.name == "gather"
               and e.outvars[0].aval.shape == rows]
    calls = [e for e in eqns if e.primitive.name == "pallas_call"]
    if path == "twin":
        assert not calls and len(gathers) == 2 * 2
        return
    assert not gathers and len(calls) == 2
    for e in calls:
        assert e.params["name"] == "gqa_paged_attn"
        assert [v.aval.shape for v in e.invars].count(
            c.pages[0].shape) == 2
