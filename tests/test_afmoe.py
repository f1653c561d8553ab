"""The ``afmoe`` decoder (models/afmoe.py: gated grouped-query attention in
sliding and full layers by the published ``layer_types``, leading dense
layers, then expert layers of which a chip holds a share) through the
serving engine and its two paged layer groups, against the plain float32
reference the benchmark keeps (benchmark/refs/trinity-large-ep8.py: no
cache, the experts one after another; it imports nothing of the program).
Toy widths with the structure of the cell's configuration (1 dense + 4
expert layers, window 8, page 4, 8 of 32 experts held), seeded weights,
logits and not tokens."""

import functools
import json
import math
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmark import cells
from benchmark.builders.afmoe import config_of, seeded_params
from horovod_tpu.models import afmoe as af
from horovod_tpu.ops import gqa_paged_attention as gpa
from horovod_tpu.parallel import expert as ex
from horovod_tpu.serving import InferenceEngine
import test_hybrid_ssm as th
import test_latent_paged_attention as tp
import test_ssd
from horovod_tpu.models import hybrid_ssm as hs
from horovod_tpu.models import latent_moe as lm
from horovod_tpu.ops import ssd, ssm_scan
from test_hybrid_ssm import counter, rollout
from test_latent_paged_attention import _primitives, _Run

REF = cells.load_module("refs", "trinity-large-ep8")
FLOPS = cells.load_module("flops", "trinity-large-ep8")
with open(os.path.join(cells.HERE, "tests", "fixtures", "configs",
                       "tiny-trinity.json")) as f:
    MODEL = json.load(f)["model"]          # float32
with open(os.path.join(cells.HERE, "configs",
                       "trinity-large-ep8.json")) as f:
    CONFIG = json.load(f)
PUBLISHED = CONFIG["model"]
CFG = config_of(MODEL)
WINDOW, PAGE, RING = 8, 4, 3               # ceil(8 / 4) + 1 entries

# float32 on both sides: what is left is the order of sums (the prompt's
# blocks of queries, the gathered rows' products, the new token's key
# beside them, the experts' grouped products against one expert after
# another).  The logits have a spread of
# 0.16; these differences measure 2e-7.  bfloat16 operands in the
# reference's place move them by 5e-3 (test_the_tolerance_would_catch_
# bfloat16), the nearest other reading of the config by 7e-2.
TOL = 5e-6


@functools.lru_cache(maxsize=None)
def params():
    return REF.init_params(MODEL, 11)


def prompt(seed, n):
    return [int(t) for t in np.random.default_rng(seed).integers(
        0, MODEL["vocab_size"], size=n)]


@functools.lru_cache(maxsize=None)
def _jitted(what, variant=""):
    return {"reference": jax.jit(lambda p, t: REF.forward(
                MODEL, p, t, "f32", variant)),
            "bf16": jax.jit(lambda p, t: REF.forward(MODEL, p, t, "bf16")),
            "full": jax.jit(lambda p, t: af.forward_full(p, t, CFG)),
            "last": jax.jit(lambda p, t, n: af.prefill_step(p, t, n, CFG)),
            "every": jax.jit(lambda p, t, n: af.prefill_step(
                p, t, n, CFG, last_only=False))}[what]


def reference(seq, variant=""):
    return np.asarray(_jitted("reference", variant)(
        params(), jnp.asarray(seq, jnp.int32)))


# -- the layout and the sizes -------------------------------------------------

def test_the_layout_is_the_published_list():
    cfg = af.AfmoeConfig()
    kinds = list(cfg.layer_types)
    assert kinds.count(af.SLIDING) == 45 and kinds.count(af.FULL) == 15
    assert [l for l, k in enumerate(kinds) if k == af.FULL] == list(
        range(3, 60, 4))
    # The cell keeps published layers 0 and 8-11: the leading dense layer
    # (a window layer) and one whole period.
    assert PUBLISHED["layers_kept"] == [0, 8, 9, 10, 11]
    assert PUBLISHED["layer_types"] == [kinds[l] for l in (0, 8, 9, 10, 11)]
    assert CFG.layer_types == tuple(MODEL["layer_types"]) == (
        af.SLIDING,) * 4 + (af.FULL,)
    assert af.group_layers(CFG) == ((4,), (0, 1, 2, 3))
    with pytest.raises(ValueError, match="layer_types"):
        af.AfmoeConfig(num_hidden_layers=3,
                       layer_types=(af.SLIDING, af.FULL))
    with pytest.raises(ValueError, match="layer_types"):
        af.AfmoeConfig(num_hidden_layers=2, num_dense_layers=1,
                       layer_types=("window", af.FULL))
    with pytest.raises(ValueError, match="first group"):
        af.AfmoeConfig(num_hidden_layers=2, num_dense_layers=1,
                       layer_types=(af.SLIDING,) * 2)
    with pytest.raises(ValueError, match="afmoe"):
        config_of(dict(MODEL, score_func="softmax"))
    with pytest.raises(ValueError, match="afmoe"):
        config_of(dict(MODEL, rope_scaling={"factor": 4.0}))


def test_the_share_counts_4_32_billion_parameters():
    """The arithmetic of the configuration's ``assumed.bytes``, from the
    program's own tree at the published widths."""
    cfg = config_of(PUBLISHED)
    tree = jax.eval_shape(
        lambda: af.init_afmoe(jax.random.PRNGKey(0), cfg))
    a = tree["layers"][1]["attn"]
    assert a["w_in"].shape == (3072, 6144 + 2 * 1024 + 6144)
    assert a["w_o"].shape == (6144, 3072)
    assert a["q_norm"].shape == a["k_norm"].shape == (128,)
    e = tree["layers"][1]["moe"]
    assert e["router"].shape == (3072, 256) and e["bias"].shape == (256,)
    assert e["w_gate"].shape == (32, 3072, 3072)
    assert tree["layers"][0]["mlp"]["w_gate"].shape == (3072, 12288)
    assert tree["embed"].shape == (25024, 3072) == tree["unembed"].shape[::-1]
    p = FLOPS.param_counts(PUBLISHED)
    assert p["attention"] == 62_914_560 and p["shared"] == 28_311_552
    assert p["router"] == 786_432 and p["expert"] == 28_311_552
    assert (p["attention"] + p["shared"] + p["router"] + 32 * p["expert"]
            == 997_982_208)
    assert p["attention"] + p["dense_ffn"] == 176_160_768
    total = FLOPS.total_params(PUBLISHED)
    assert total == 4_321_837_056 and round(2 * total / 1e9, 2) == 8.64
    # The tree holds the norms and the routers' biases besides: four norms
    # of 3072 and two of 128 a layer, the last norm, 256 a router.
    leaves = sum(math.prod(x.shape) for x in jax.tree_util.tree_leaves(tree))
    assert leaves - total == 5 * (4 * 3072 + 2 * 128) + 3072 + 4 * 256
    assert "4,321,837,056 parameters" in CONFIG["assumed"]["bytes"]
    # A cached token: 2 x 8 x 128 bfloat16 a layer; 16.4 KB up to the
    # window over the five layers, 4 KB beyond it.
    assert FLOPS.token_bytes(PUBLISHED) == 4096
    assert FLOPS.cache_bytes(PUBLISHED, 1000) == 1000 * 5 * 4096
    assert (FLOPS.cache_bytes(PUBLISHED, 9000)
            - FLOPS.cache_bytes(PUBLISHED, 8000)) == 1000 * 4096
    assert FLOPS.cache_bytes(PUBLISHED, 9000, 16) == 4096 * (
        9008 + 4 * 4112)


def test_the_benchmarks_seeded_tree_has_the_programs_shape():
    seeded_params(MODEL, CFG, 3, REF)
    with pytest.raises(RuntimeError, match="program's shape"):
        seeded_params(MODEL, config_of(dict(MODEL, head_dim=8)), 3, REF)


def test_the_seeded_initialisation_is_the_stated_one():
    lp = params()["layers"][2]
    post = (2.0 * MODEL["num_hidden_layers"]) ** -0.5
    assert np.allclose(np.asarray(lp["norm_post_attn"]), post)
    assert np.allclose(np.asarray(lp["norm_post_mlp"]), post)
    assert np.all(np.asarray(lp["norm_in"]) == 1.0)
    # The fixture draws the q and k norm weights at 1 x uniform 0.5..1.5,
    # the cell at 2.5 x (a sharper softmax: assumed.weights says why).
    assert MODEL["qk_norm_scale"] == 1.0 and PUBLISHED["qk_norm_scale"] == 2.5
    for name in ("q_norm", "k_norm"):
        w = np.asarray(lp["attn"][name])
        assert 0.5 <= w.min() and w.max() <= 1.5 and w.std() > 0.15
    bias = np.asarray(lp["moe"]["bias"])
    assert bias.dtype == np.float32 and 0.02 < bias.std() < 0.09
    assert 0.015 < float(jnp.std(lp["moe"]["router"])) < 0.025


# -- the router ---------------------------------------------------------------

def test_the_seeded_bias_changes_choices_and_leaves_the_load_balanced():
    """The cell's seeded routers and biases at their published size (the
    reference's own draws, the other leaves skipped): the bias changes one
    of a token's four choices for about a token in four, and this chip's
    share of the routed pairs stays near 4 x 32 / 256 = 0.5 a token
    whatever the seed.  Drawn ten times larger it changed nearly every
    token's choice and the share swung from 0.43 to 0.56 with the seed,
    and the cell's gap between tokens with it (PERF.md section 6, PR 41)."""
    from benchmark import precision

    def routers(seed):
        key, count, kept = precision.key_from_seed(seed), iter(range(10**6)), []

        def leaf(shape, how):
            if isinstance(how, str) and how != "head_norm":
                return None
            k = jax.random.fold_in(key, next(count))
            if shape in ((3072, 256), (256,)):
                scale = how[1] if isinstance(how, tuple) else how
                kept.append(np.asarray(
                    jax.random.normal(k, shape, jnp.float32) * scale))
            return None

        REF._tree(PUBLISHED, leaf)
        return kept

    assert PUBLISHED["router_bias_std"] == 0.005
    h = np.random.default_rng(0).standard_normal((2048, 3072)).astype(
        np.float32)
    h /= np.sqrt((h * h).mean(-1, keepdims=True))
    for seed in (4100000501, 4100000502, 4100000505):
        leaves = routers(seed)
        assert len(leaves) == 8
        share, changed = [], []
        for router, bias in zip(leaves[::2], leaves[1::2]):
            scores = 1.0 / (1.0 + np.exp(-(h @ router)))
            with_bias = np.sort(np.argsort(-(scores + bias), -1)[:, :4], -1)
            without = np.sort(np.argsort(-scores, -1)[:, :4], -1)
            share.append(4 * (with_bias < 32).mean())
            changed.append((with_bias != without).any(-1).mean())
        assert 0.46 < np.mean(share) < 0.54, (seed, share)
        assert 0.15 < np.mean(changed) < 0.45, (seed, changed)


def test_the_bias_changes_the_choice_and_not_the_weight():
    """A hand-worked token: scores sigmoid(2, 1, 0.5, -1) = 0.881, 0.731,
    0.622, 0.269.  Without a bias the two largest are experts 0 and 1;
    a bias of 0.5 on expert 3 lifts it to 0.769, over expert 1, so 0 and 3
    are chosen, and their weights come from the UNBIASED 0.881 and 0.269:
    0.766 and 0.234 of 2.448."""
    x = jnp.asarray([[1.0, 0.0]])
    router = jnp.asarray([[2.0, 1.0, 0.5, -1.0], [9.0, 9.0, 9.0, 9.0]])
    s = 1.0 / (1.0 + np.exp(-np.asarray([2.0, 1.0, 0.5, -1.0])))
    idx, w = ex.route_sigmoid_bias_top_k(x, router, jnp.zeros((4,)), 2,
                                         routed_scale=2.448)
    assert idx.tolist() == [[0, 1]]
    assert np.allclose(w, 2.448 * s[[0, 1]] / s[[0, 1]].sum(), atol=1e-6)
    bias = jnp.asarray([0.0, 0.0, 0.0, 0.5])
    idx, w = ex.route_sigmoid_bias_top_k(x, router, bias, 2,
                                         routed_scale=2.448)
    assert idx.tolist() == [[0, 3]]
    assert np.allclose(w, 2.448 * s[[0, 3]] / s[[0, 3]].sum(), atol=1e-6)
    assert np.allclose(w, [[1.8754, 0.5726]], atol=1e-4)
    # Not normalised: the unbiased scores themselves.
    _, raw = ex.route_sigmoid_bias_top_k(x, router, bias, 2,
                                         norm_topk=False)
    assert np.allclose(raw, [s[[0, 3]]], atol=1e-6)
    # The reference's rule is the same rule.
    model = dict(MODEL, num_experts_per_tok=2)
    r_idx, r_w = REF.route(model, x, router, bias, "f32")
    assert r_idx.tolist() == [[0, 3]] and np.allclose(r_w, w, atol=1e-6)


def test_the_shares_of_an_expert_layer_add_up_to_the_uncut_layer():
    """Four members of an expert-parallel group hold 8 of the 32 experts
    each: what their held experts add, with the shared expert counted
    once, is what the reference gives for the layer with all 32 held."""
    held, n = MODEL["n_routed_experts"], MODEL["n_routed_experts_published"]
    d, fe = MODEL["hidden_size"], MODEL["moe_intermediate_size"]
    keys = jax.random.split(jax.random.PRNGKey(5), 9)
    w = lambda k, *shape: jax.random.normal(k, shape, jnp.float32) * 0.1
    whole = {"router": w(keys[0], d, n), "bias": w(keys[1], n) * 0.5,
             "w_gate": w(keys[2], n, d, fe), "w_up": w(keys[3], n, d, fe),
             "w_down": w(keys[4], n, fe, d),
             "shared": {"w_gate": w(keys[5], d, fe), "w_up": w(keys[6], d, fe),
                        "w_down": w(keys[7], fe, d)}}
    h = jax.random.normal(keys[8], (24, d), jnp.float32)
    uncut = REF.expert_ffn(dict(MODEL, n_routed_experts=n), whole, h, "f32")
    sh = whole["shared"]
    shared = ex.swiglu(h, sh["w_gate"], sh["w_up"], sh["w_down"])
    total, pairs = shared, 0
    for rank in range(n // held):
        lo = rank * held
        share = dict(whole, **{k: whole[k][lo:lo + held]
                               for k in ("w_gate", "w_up", "w_down")})
        cfg = config_of(dict(MODEL, expert_offset=lo))
        out = ex.moe_layer_held(
            h, share, num_experts=n, expert_offset=lo,
            top_k=cfg.num_experts_per_tok,
            routing=functools.partial(
                ex.route_sigmoid_bias_top_k, router=share["router"],
                bias=share["bias"], top_k=cfg.num_experts_per_tok,
                routed_scale=cfg.route_scale, norm_topk=cfg.route_norm))
        total = total + (out.out - shared)
        pairs += int(out.counts.sum())
        # The reference given the same share leaves out the same experts.
        cut = REF.expert_ffn(dict(MODEL, expert_offset=lo), share, h, "f32")
        assert float(jnp.abs(out.out - cut).max()) < 1e-5
    assert pairs == 24 * MODEL["num_experts_per_tok"]
    assert float(jnp.abs(total - uncut).max()) < 1e-5
    assert float(jnp.abs(uncut - shared).max()) > 0.05


# -- whole sequences ----------------------------------------------------------

@pytest.mark.parametrize("n", [5, 8, 9, 40])
def test_program_and_reference_agree_on_whole_sequences(n):
    """Shorter than the window, exactly it, one past it, five times it."""
    seq = prompt(20 + n, n)
    got = _jitted("full")(params(), jnp.asarray(seq, jnp.int32))
    assert np.abs(np.asarray(got) - reference(seq)).max() < TOL


@pytest.mark.parametrize("n,q_block", [(40, 4), (41, 8), (64, 16)])
def test_a_prompts_blocks_of_queries_see_what_one_block_sees(
        monkeypatch, n, q_block):
    """Off the TPU a prompt is attended in blocks of queries against the
    keys they can see: the blocks' edges, the window's edge inside a block
    and a last block that is not full change nothing."""
    monkeypatch.setattr(af, "PREFILL_Q_BLOCK", q_block)
    seq = prompt(60 + n, n)
    got = jax.jit(lambda p, t: af.forward_full(p, t, CFG))(
        params(), jnp.asarray(seq, jnp.int32))
    assert np.abs(np.asarray(got) - reference(seq)).max() < TOL


@pytest.mark.parametrize("n", [5, 40, 64])
def test_the_prompt_through_the_flash_kernel_equals_the_reference(
        monkeypatch, n):
    """What the TPU runs for a prompt (the streaming kernel with grouped
    heads and the window, here in the interpreter) against the float32
    reference, window layers and the full layer alike; off the TPU the
    rule picks the blockwise twin."""
    assert not af.flash_runs()
    monkeypatch.setattr(af, "FLASH_INTERPRET", True)
    assert af.flash_runs()
    seq = prompt(80 + n, n)
    got = jax.jit(lambda p, t: af.forward_full(p, t, CFG))(
        params(), jnp.asarray(seq, jnp.int32))
    assert np.abs(np.asarray(got) - reference(seq)).max() < TOL


@pytest.mark.parametrize("n,bucket", [(3, 4), (24, 32), (32, 32), (9, 64)])
def test_a_buckets_padding_changes_nothing(n, bucket):
    seq = prompt(40 + n, n)
    toks = jnp.asarray(seq + [7] * (bucket - n), jnp.int32)
    last, k, v, counts = _jitted("last")(params(), toks, jnp.int32(n))
    every, k_all, _, counts_all = _jitted("every")(params(), toks,
                                                   jnp.int32(n))
    assert float(jnp.abs(last - every[n - 1]).max()) < TOL
    assert np.abs(np.asarray(last) - reference(seq)[-1]).max() < TOL
    assert k.shape == v.shape == (5, bucket, CFG.kv_width)
    # Padding reaches no expert: the held experts' pairs are the real
    # tokens' alone.
    _, _, _, exact = _jitted("last")(params(), jnp.asarray(seq, jnp.int32),
                                     jnp.int32(n))
    assert counts.shape == (4, MODEL["n_routed_experts"])
    assert np.array_equal(np.asarray(counts), np.asarray(exact))
    assert np.array_equal(np.asarray(counts), np.asarray(counts_all))


def test_the_tolerance_would_catch_bfloat16():
    seq = prompt(77, 40)
    exact = reference(seq)
    rounded = np.asarray(_jitted("bf16")(params(),
                                         jnp.asarray(seq, jnp.int32)))
    assert np.abs(rounded - exact).max() > 100 * TOL
    cfg16 = config_of(dict(MODEL, dtype="bfloat16"))
    p16 = jax.tree_util.tree_map(
        lambda a: a.astype(jnp.bfloat16) if a.ndim > 1 else a, params())
    got = jax.jit(lambda p, t: af.forward_full(p, t, cfg16))(
        p16, jnp.asarray(seq, jnp.int32))
    assert np.abs(np.asarray(got) - exact).max() > 100 * TOL


# Each thing the published config does not say is read one way
# (benchmark/configs/trinity-large-ep8.json "assumed"); the reference read
# the other way is thousands of times outside the tolerance (measured: 0.07
# to 0.7 of a spread of 0.16).  The last three switch a mechanism off: no
# comparison is blind to the gate, the window or the routed experts.
@pytest.mark.parametrize("variant", [
    "rope_full", "gate_after_o", "norm_after_rope", "no_post_norm",
    "bias_in_weight", "window_inclusive", "no_mup",
    "no_gate", "no_window", "no_routed"])
def test_another_reading_of_the_config_fails_the_comparison(variant):
    seq = prompt(5, 24)
    got = np.asarray(_jitted("full")(params(), jnp.asarray(seq, jnp.int32)))
    assert np.abs(got - reference(seq)).max() < TOL
    wrong = reference(seq, variant)
    assert np.abs(got - wrong).max() > 1000 * TOL, np.abs(got - wrong).max()
    assert set(CONFIG["assumed"]) >= {
        "rope", "gate", "qk_norm", "norms", "router", "window", "embedding",
        "weights", "bytes"}


# -- prefill then decode through the two paged groups -------------------------

@functools.lru_cache(maxsize=None)
def engine():
    eng = InferenceEngine(params(), CFG, max_slots=8, page_size=PAGE,
                          capacity=128)
    eng.warm_start()
    return eng


def check_against_reference(prompts, new, got):
    for p, n, (rows, toks) in zip(prompts, new, got):
        assert len(toks) == n and rows.shape[0] == n
        # Row i was computed after len(p) + i tokens: the reference's
        # logits at position len(p) - 1 + i.
        ref = reference(p + toks)[len(p) - 1:len(p) - 1 + n]
        assert np.abs(rows - ref).max() < TOL


def all_pages_are_back(eng):
    pages = eng.cache.group_pages()
    assert [used for used, _ in pages.values()] == [0, 0]
    assert eng.cache.free_pages() == eng.cache.total_pages
    return pages


# A sequence that never reaches the window (3 + 3 < 8); one that starts
# under it and wraps the ring of pages more than once (5 + 30 tokens: the
# ring holds 12); a prompt longer than twice the window, of which the
# prefill keeps the last ring's worth; ragged slots of all three kinds at
# once.
@pytest.mark.parametrize("lengths,new", [
    ((3,), (3,)), ((5,), (30,)), ((30,), (20,)), ((8,), (9,)),
    ((6, 19, 40), (9, 14, 25)),
    ((40, 9, 100, 30, 66, 12), (5, 6, 7, 8, 9, 10))])
def test_prefill_then_decode_equals_the_reference(lengths, new):
    eng = engine()
    prompts = [prompt(100 + n, n) for n in lengths]
    got = rollout(eng, prompts, new)
    check_against_reference(prompts, new, got)
    pages = all_pages_are_back(eng)
    assert pages == {"full": (0, 8 * 32), "window": (0, 8 * RING)}


@pytest.mark.parametrize("bucket", [2, 4, 8, 16, 32, 64, 128])
def test_every_prefill_bucket_serves_the_reference(bucket):
    eng = engine()
    n = bucket - 1 if bucket > 2 else 2
    if bucket == 128:
        n = 120                  # leave room to decode under capacity
    p = prompt(500 + bucket, n)
    assert eng._bucket_for(n) == bucket
    check_against_reference([p], [3], rollout(eng, [p], [3]))


def test_prefill_through_the_flash_kernel_then_decode_equals_the_reference(
        monkeypatch):
    """The engine's prefill programs built with the kernel (interpreted):
    what the kernel leaves in both groups' pages is what decode attends."""
    monkeypatch.setattr(af, "FLASH_INTERPRET", True)
    eng = InferenceEngine(params(), CFG, max_slots=4, page_size=PAGE,
                          capacity=64)
    prompts, new = [prompt(31, 20), prompt(32, 3)], (6, 5)
    check_against_reference(prompts, new, rollout(eng, prompts, new))


def test_a_slot_taken_again_sees_nothing_of_who_held_it():
    """A long sequence leaves its pages' content behind; a short one that
    takes the slot (and, from the free list, some of the pages) attends
    only its own positions in both groups."""
    eng = engine()
    long_p, short_p = prompt(901, 90), prompt(902, 5)
    rollout(eng, [long_p], [20])
    second = rollout(eng, [short_p], [12])
    fresh = InferenceEngine(params(), CFG, max_slots=8, page_size=PAGE,
                            capacity=128)
    first = rollout(fresh, [short_p], [12])
    assert second[0][1] == first[0][1]
    assert np.abs(second[0][0] - first[0][0]).max() < TOL
    check_against_reference([short_p], [12], second)


def test_run_ahead_loop_equals_the_loop_held_at_depth_0(monkeypatch):
    """Staggered admissions and finishes serve the same tokens one
    iteration ahead as at depth 0, count the same reads in both groups,
    and a slot never holds more window-group pages than its ring."""
    eng = engine()
    trace = [(prompt(400 + i, n), new, at) for i, (n, new, at) in enumerate(
        [(20, 7, 0), (70, 2, 0), (3, 5, 1), (33, 1, 2), (100, 6, 2),
         (5, 14, 6), (66, 3, 6)])]
    names = ("serving.decode_ahead", "serving.decode_iterations",
             "serving.shared_kv_tokens", "serving.window_tokens",
             "serving.tokens_generated", "serving.moe_assignments",
             "serving.moe_experts_touched", "serving.window_pages_reused")
    most = {}

    def replay():
        before = [counter(n) for n in names]
        reqs = [eng.submit(list(p), max_new_tokens=n, arrival=a)
                for p, n, a in trace]
        it = 0
        while not eng.scheduler.idle():
            eng.step(now=it)
            it += 1
            alive = int((eng.cache.lengths() >= 0).sum())
            used = eng.cache.group_pages()["window"][0]
            assert used <= RING * max(alive, 1)
            most["window"] = max(most.get("window", 0), used)
        all_pages_are_back(eng)
        return ([r.result(0) for r in reqs],
                [counter(n) - b for n, b in zip(names, before)])

    ahead, (n_ahead, n_iter, full, window, tokens, pairs, touched,
            reused) = replay()
    monkeypatch.setattr(eng, "_runs_ahead", lambda active: False)
    held, (h_ahead, _, h_full, h_window, h_tokens, h_pairs, _, _) = replay()
    assert ahead == held and [len(t) for t in ahead] == [
        n for _, n, _ in trace]
    assert h_ahead == 0 and 0.5 * n_iter < n_ahead < n_iter
    assert tokens == h_tokens == sum(n for _, n, _ in trace)
    # A request at its i-th decoded token attends len(prompt) + i positions
    # (its own included) in the full layer and at most the window in a
    # sliding one.
    seen = [len(p) + i for p, n, _ in trace for i in range(1, n)]
    assert full == h_full == sum(seen)
    assert window == h_window == sum(min(s, WINDOW) for s in seen)
    assert window < full
    # Every decoded token makes top_k choices in each expert layer; a
    # quarter of the experts are held here, so about a quarter arrive.
    choices = len(seen) * 4 * MODEL["num_experts_per_tok"]
    assert pairs == h_pairs and 0.1 * choices < pairs < 0.5 * choices
    assert 0 < touched <= pairs
    assert reused > 0 and most["window"] > RING


def test_a_pool_without_headroom_defers_the_admission_and_serves_it_later():
    """Pools smaller than every slot at its largest: admission reserves a
    request's whole length in BOTH pools, the request that does not fit
    waits in the queue, and what is served is the reference's all the
    same."""
    token = 2 * CFG.kv_width * 4
    eng = InferenceEngine(params(), CFG, max_slots=8, page_size=PAGE,
                          capacity=128,
                          kv_pool_bytes=(1 * 40 + 4 * 7) * PAGE * token,
                          kv_expected_tokens=96)
    pools = eng.cache.group_pages()
    assert pools["window"][1] < 3 * RING and pools["full"][1] < 8 * 32
    deferred = counter("serving.admission_deferred")
    lengths, new = (30, 21, 40), (10, 12, 8)
    prompts = [prompt(700 + n, n) for n in lengths]
    got = rollout(eng, prompts, new)
    check_against_reference(prompts, new, got)
    assert counter("serving.admission_deferred") > deferred
    all_pages_are_back(eng)
    assert eng.cache.headroom().tolist() == [t for _, t in
                                             eng.cache.group_pages().values()]


# -- the protocol -------------------------------------------------------------

def test_the_counters_of_a_fixed_batch_with_idle_slots():
    model = af.AfmoeConfig().serving_model()
    before = [counter("serving.shared_kv_tokens"),
              counter("serving.window_tokens")]
    model.observe_launch(np.asarray([899, -1, 0, 8191, -1, 4095], np.int32))
    assert counter("serving.shared_kv_tokens") - before[0] \
        == 900 + 1 + 8192 + 4096
    # The window group: the live slots' min(length, 4096), not slots x 4096.
    assert counter("serving.window_tokens") - before[1] \
        == 900 + 1 + 4096 + 4096
    model.observe_launch(np.full((6,), -1, np.int32))
    assert counter("serving.window_tokens") - before[1] == 900 + 1 + 8192


def test_the_cache_entry_is_two_paged_groups_and_nothing_beside_them():
    model = af.AfmoeConfig().serving_model()
    entry = model.cache_entry()
    assert entry["widths"] == (1024, 1024) and entry["n_layers"] == 15
    assert entry["groups"] == (
        {"name": "full", "n_layers": 15},
        {"name": "window", "n_layers": 45, "window": 4096})
    # No per-slot store at all: no window ring a slot, no room to gather a
    # view into, on any backend.
    assert set(entry) == {"n_layers", "n_heads", "head_dim", "widths",
                          "groups"}
    assert not (model.prefix_cache or model.speculative
                or model.tensor_parallel or model.slot_state)
    assert "ring" in model.prefix_cache_why
    assert af.ring_entries(4096, 16) == 257
    cell = config_of(PUBLISHED).serving_model().cache_entry()
    assert [g["n_layers"] for g in cell["groups"]] == [1, 4]


def test_the_engines_store_is_the_groups_the_model_declares():
    eng = engine()
    c = eng.cache
    assert c.group_names == ("full", "window")
    assert c.table_width == 32 + RING
    full_k, full_v, win_k, win_v = c.arrays    # the pages and no view
    assert full_k.shape == full_v.shape == (1, 1 + 8 * 32, PAGE, 32)
    assert win_k.shape == win_v.shape == (4, 1 + 8 * RING, PAGE, 32)
    assert not c.slot_state
    table, lengths = c.host_tables()
    assert table.shape == (8, 35) and not table.any()
    ident = eng.model.identity()
    assert ident["family"] == "afmoe" and ident["sliding_window"] == WINDOW
    assert ident["experts"] == [32, 8, 0, 4]


# -- decode through the paged kernel (ops/gqa_paged_attention.py) -------------

@functools.lru_cache(maxsize=None)
def _kernel_engine():
    eng = InferenceEngine(params(), CFG, max_slots=8, page_size=PAGE,
                          capacity=128)
    eng.warm_start()
    return eng


def kernel_engine(monkeypatch):
    """The engine with the kernel in its decode program, interpreted:
    ``PAGED_INTERPRET`` is read when the cache and the programs are built
    and when the host counts what a launch attends."""
    monkeypatch.setattr(af, "PAGED_INTERPRET", True)
    return _kernel_engine()


# Both sides of the window of 8, as the twin's cases above: never
# reaching it; starting under it and wrapping the ring of pages more than
# once; a prompt longer than twice the window; ragged slots of all kinds.
@pytest.mark.parametrize("lengths,new", [
    ((3,), (3,)), ((5,), (30,)), ((30,), (20,)), ((8,), (9,)),
    ((6, 19, 40), (9, 14, 25))])
def test_prefill_then_decode_through_the_kernel_equals_the_reference(
        monkeypatch, lengths, new):
    eng = kernel_engine(monkeypatch)
    assert len(eng.cache.arrays) == 4          # no view beside the pages
    prompts = [prompt(100 + n, n) for n in lengths]
    views = counter("serving.decode_view_tokens")
    got = rollout(eng, prompts, new)
    check_against_reference(prompts, new, got)
    assert all_pages_are_back(eng) == {"full": (0, 8 * 32),
                                       "window": (0, 8 * RING)}
    # Iteration i (0-based) attends the slots with more than i + 1 tokens
    # to give, each at its prompt's length plus i cached positions: what
    # the kernel copies of the full group's one layer and of the window
    # group's four, over the five layers and the eight slots.
    read = 0
    for i in range(max(new) - 1):
        at = [n + i if i + 1 < k else -1 for n, k in zip(lengths, new)]
        read += (gpa.tokens_read(at, 32, PAGE)
                 + 4 * gpa.tokens_read(at, RING, PAGE)) / 5 / 8
    assert counter("serving.decode_view_tokens") - views \
        == pytest.approx(read)


def _attention_program(monkeypatch, interpret):
    """The decode step's attention alone, one full and one sliding layer,
    as ``decode_step`` builds it: its primitives and what it returns."""
    monkeypatch.setattr(af, "PAGED_INTERPRET", interpret)
    rng = np.random.RandomState(0)
    slots, pps, kw = 4, 8, CFG.kv_width
    lengths = jnp.asarray([13, -1, 30, 5], jnp.int32)
    table = jnp.asarray(1 + rng.permutation(slots * (pps + RING)).reshape(
        slots, pps + RING), jnp.int32)
    n_pages = 1 + slots * (pps + RING)
    full_k, full_v = (jnp.asarray(rng.randn(1, n_pages, PAGE, kw),
                                  jnp.float32) for _ in range(2))
    win_k, win_v = (jnp.asarray(rng.randn(2, n_pages, PAGE, kw),
                                jnp.float32) for _ in range(2))
    q, k, v = (jnp.asarray(rng.randn(slots, w), jnp.float32)
               for w in (CFG.q_width, kw, kw))

    def f(lengths, table, full_k, full_v, win_k, win_v, q, k, v):
        groups = {af.FULL: (table[:, :pps], 0, full_k, full_v),
                  af.SLIDING: (table[:, pps:], WINDOW, win_k, win_v)}
        attend = (af.paged_attend(lengths, groups, CFG, af.PAGED_INTERPRET)
                  if af.paged_kernel_runs() else
                  af.gathered_attend(lengths, groups, CFG))
        return attend(af.FULL, 0, q, k, v), attend(af.SLIDING, 1, q, k, v)

    args = (lengths, table, full_k, full_v, win_k, win_v, q, k, v)
    return (_primitives(jax.make_jaxpr(f)(*args).jaxpr), jax.jit(f)(*args),
            np.asarray(lengths) >= 0)


def test_off_the_tpu_the_plain_twin_runs_unless_the_interpreter_is_asked_for(
        monkeypatch):
    """The rule is the backend's (``ops/flash_attention.kernel_runs``): on
    the CPU the decode program gathers a slot's table row whole and attends
    it under the kernel's mask, no conditional, no sort, no loop; with the
    kernel forced the gather is gone too; the cache is asked for the same
    store either way, and both give the same attention in both groups."""
    assert jax.default_backend() == "cpu" and not af.paged_kernel_runs()
    plain, want, on = _attention_program(monkeypatch, None)
    assert "gather" in plain
    assert not plain & {"pallas_call", "cond", "sort", "while"}
    assert "slot_stores" not in CFG.serving_model().cache_entry()
    kernel, got, _ = _attention_program(monkeypatch, True)
    assert af.paged_kernel_runs()
    assert "pallas_call" in kernel
    assert not kernel & {"cond", "gather", "sort", "scatter", "while"}
    entry = CFG.serving_model().cache_entry()
    assert "slot_stores" not in entry
    assert [g["name"] for g in entry["groups"]] == ["full", "window"]
    for a, b in zip(got, want):
        assert np.abs(np.asarray(a - b))[on].max() < TOL


def test_decode_view_is_what_the_kernel_copies_of_both_groups():
    """A fixed batch with idle slots at the cell's sizes (page 16, 576
    pages a slot, a ring of 257): the full group's one layer copies every
    live slot's pages, the window group's four at most the ring's; the
    mean over the five layers and the 8 slots, whatever the backend."""
    model = config_of(PUBLISHED).serving_model()
    lengths = np.asarray([899, -1, 0, 8191, -1, 4095, 4096, 5000], np.int32)
    full = 912 + 0 + 8192 + 4096 + 4096 + 5008
    window = 912 + 0 + 257 * 16 + 4096 + 4096 + 257 * 16
    assert model.decode_view(lengths, 16, 576) == pytest.approx(
        (full + 4 * window) / 5 / 8)
    assert model.decode_view(np.full((8,), -1, np.int32), 16, 576) == 0


# -- the counter's reader (benchmark/metrics/gqa_view_tokens.py) --------------

def test_gqa_view_tokens_is_declared_for_the_cell():
    bench = cells.load_benchmark()
    mod = cells.load_module("metrics", "gqa_view_tokens")
    (entry,) = [m for m in bench["per_layer"]
                if m["name"] == "gqa_view_tokens"]
    assert entry == {
        "name": "gqa_view_tokens", "unit": mod.UNIT, "better": mod.BETTER,
        "source": mod.SOURCE, "layer": mod.LAYER, "moves": mod.MOVES,
        "workloads": ["trinity-serve-mixed"]}
    assert (mod.UNIT, mod.BETTER, mod.SOURCE, mod.LAYER, mod.MOVES) == (
        "tokens", "lower", "program_counter", "grouped-query attention",
        "tpot_p90_ms")
    for cell in (w["name"] for w in bench["workloads"]):
        listed = [m["name"] for m in cells.resolve(bench, cell)["per_layer"]]
        assert ("gqa_view_tokens" in listed) == (
            cell == "trinity-serve-mixed")


@pytest.mark.parametrize("before,after,want", [
    # 500 iterations: 300 that copied 405 positions a slot a layer, 200
    # that copied 380.
    ({"serving.decode_view_tokens": {"value": 1280.0},
      "serving.decode_iterations": {"value": 5}},
     {"serving.decode_view_tokens": {"value": 1280.0 + 300 * 405 + 200 * 380},
      "serving.decode_iterations": {"value": 505}}, 395.0),
    # No decode in the window; a program without the counter; no serving.
    ({"serving.decode_view_tokens": {"value": 640},
      "serving.decode_iterations": {"value": 5}},
     {"serving.decode_view_tokens": {"value": 640},
      "serving.decode_iterations": {"value": 5}}, None),
    ({"serving.decode_iterations": {"value": 5}},
     {"serving.decode_iterations": {"value": 55}}, None),
    ({}, {}, None)])
def test_gqa_view_tokens_is_the_view_counter_over_the_iterations(
        before, after, want):
    got = cells.load_module("metrics", "gqa_view_tokens").read(
        _Run(before, after))
    assert got == (want if want is None else pytest.approx(want))


# -- one rule, one store: every family, every kernel ---------------------------

# The four families whose decode attends through a paged kernel, with the
# module whose ``PAGED_INTERPRET`` steers them (the shortcut family rides
# the latent one's).
SERVED = {"latent": (lm, tp.tl.CFG), "shortcut": (lm, tp.ts.CFG),
          "afmoe": (af, CFG), "hybrid_ssm": (hs, th.CFG)}


@pytest.mark.parametrize("family", sorted(SERVED))
def test_the_store_and_the_view_are_the_same_on_every_backend(monkeypatch,
                                                               family):
    """What a model asks of the cache manager and what it says a launch
    attends do not depend on where its decode attends: the kernel's twin
    gathers the same pages the kernel walks and keeps no store of its own."""
    module, cfg = SERVED[family]
    lengths = np.asarray([70, -1, 9, 140, -1, 30, 0, 255], np.int32)
    answers = []
    for interpret in (None, True):
        monkeypatch.setattr(module, "PAGED_INTERPRET", interpret)
        assert module.paged_kernel_runs() == bool(interpret)
        model = cfg.serving_model()
        answers.append((model.cache_entry(),
                        model.decode_view(lengths, 8, 32)))
    assert answers[0] == answers[1]
    # The live lengths in whole pages at most, never a view's size.
    assert 0 < answers[0][1] <= (72 + 16 + 144 + 32 + 0 + 256) / 8


def _latent_attend(interpret, patch):
    patch(lm, "PAGED_INTERPRET", interpret)
    c = tp.case((40, -1, 7, 90), seed=9)
    return jax.make_jaxpr(
        lambda n, store, table: lm.decode_attend(n, store, table, tp.CFG)[0](
            1, c["q_nope"], c["q_rope"], c["entry"], c["ap"]))(
        c["lengths"], c["store"], c["table"])


def _gqa_attend(interpret, patch):
    patch(af, "PAGED_INTERPRET", interpret)
    pages = jnp.zeros((1, 9, PAGE, CFG.kv_width), jnp.float32)
    stores = (pages, pages, pages, pages)
    tokens = jnp.zeros((2,), jnp.int32)
    table = jnp.asarray(1 + np.arange(2 * (1 + RING)).reshape(2, -1),
                        jnp.int32)
    cfg = config_of(dict(MODEL, num_hidden_layers=2, num_dense_layers=1,
                         layer_types=[af.SLIDING, af.FULL]))
    p = jax.eval_shape(lambda: af.init_afmoe(jax.random.PRNGKey(0), cfg))
    return jax.make_jaxpr(
        lambda p, n: af.decode_step(p, tokens, n, stores, table, cfg))(
        p, jnp.asarray([3, -1], jnp.int32))


def _flash_prompt(interpret, patch):
    patch(af, "FLASH_INTERPRET", bool(interpret))
    q = jnp.zeros((16, CFG.q_width), jnp.float32)
    kv = jnp.zeros((16, CFG.kv_width), jnp.float32)
    return jax.make_jaxpr(lambda q, k, v: af.attend_prompt(q, k, v, CFG, 8))(
        q, kv, kv)


def _ssd_step(interpret, patch):
    store, x, dt, a, b, c, d = test_ssd.step_operands()
    return jax.make_jaxpr(lambda store: ssd.ssd_step(
        store, x, dt, a, b, c, d, jnp.ones((6,), bool), layer=1,
        interpret=interpret))(store)


def _ssd_chunk_scan(interpret, patch):
    x, dt, a, b, c, s0 = test_ssd.operands(12)
    return jax.make_jaxpr(lambda x: ssd.ssd_chunk_scan(
        x, dt, a, b, c, ssd.pack_state(s0), 12, chunk=8,
        interpret=interpret))(x)


def _ssm_scan(interpret, patch):
    x = jnp.zeros((16, 256), jnp.float32)
    bc = jnp.zeros((16, 4), jnp.float32)
    a = jnp.zeros((4, 256), jnp.float32)
    return jax.make_jaxpr(lambda x: ssm_scan.ssm_scan(
        x, x, bc, bc, a, a, 16, interpret=interpret))(x)


@pytest.mark.parametrize("trace", [
    _ssd_step, _ssd_chunk_scan, _ssm_scan, _latent_attend, _gqa_attend,
    _flash_prompt], ids=lambda f: f.__name__.strip("_"))
def test_one_rule_says_where_every_kernel_runs(monkeypatch, trace):
    """``ops/flash_attention.kernel_runs``, through each of its callers: on
    the CPU with nothing asked the program holds no ``pallas_call``; with
    the interpreter asked for by name it holds one."""
    assert jax.default_backend() == "cpu"
    patch = monkeypatch.setattr
    assert "pallas_call" not in _primitives(trace(None, patch).jaxpr)
    assert "pallas_call" in _primitives(trace(True, patch).jaxpr)
