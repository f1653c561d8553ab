"""The decoder-hybrid-decoder (models/hybrid_ssm.py: state-space, window,
full, gated-memory and cross layers in one stack) through the serving
engine, against the plain float32 reference the benchmark keeps
(benchmark/refs/phi4-mini-flash.py, which imports nothing of the
program).  Toy widths (window 8, d_state 4, 8 layers in the same
five-kind layout), seeded weights, logits and not tokens."""

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
from benchmark.builders.hybrid_ssm import config_of, seeded_params
from horovod_tpu.models import hybrid_ssm as hs
from horovod_tpu.models.transformer import (TransformerConfig,
                                            init_transformer)
from horovod_tpu.memory.planner import ring_entries
from horovod_tpu.ops import gqa_paged_attention as gpa
from horovod_tpu.ops import ssm_scan as scan
from horovod_tpu.serving import InferenceEngine
from horovod_tpu.serving.kv_cache import PagedKVCache

REF = cells.load_module("refs", "phi4-mini-flash")
with open(os.path.join(cells.HERE, "tests", "fixtures", "configs",
                       "tiny-phi4flash.json")) as f:
    MODEL = json.load(f)["model"]          # float32
CFG = config_of(MODEL)
WINDOW = MODEL["sliding_window"]
PAGE = 4
RING = ring_entries(WINDOW, PAGE)      # ceil(8 / 4) + 1 entries of a slot

# float32 on both sides: what is left is the order of sums (the gathered
# rows' block-diagonal products, the ring's order of keys, the new
# token's key beside the view, blockwise softmax, the kernels' chunks).
# The logits are of order 1; these differences measure 1e-6.  bfloat16
# operands in the reference's place move them by 1e-3 and more
# (test_the_tolerance_would_catch_bfloat16), so the tolerance sits between
# the two with a decade and more on each side.
TOL = 5e-5


@functools.lru_cache(maxsize=None)
def params():
    return REF.init_params(MODEL, 11)


def prompt(seed, n):
    return [int(t) for t in np.random.default_rng(seed).integers(
        0, MODEL["vocab_size"], size=n)]


def counter(name):
    return telemetry.metrics().get(name, {}).get("value", 0)


@functools.lru_cache(maxsize=None)
def _jitted(what):
    # Eager, these are hundreds of separate compiles a call.
    return {"reference": jax.jit(lambda p, t: REF.forward(MODEL, p, t)),
            "bf16": jax.jit(lambda p, t: REF.forward(MODEL, p, t, "bf16")),
            "full": jax.jit(lambda p, t: hs.forward_full(p, t, CFG)),
            "last": jax.jit(lambda p, t, n: hs.prefill_step(p, t, n, CFG)),
            "every": jax.jit(lambda p, t, n: hs.prefill_step(
                p, t, n, CFG, last_only=False))}[what]


def reference(seq):
    return np.asarray(_jitted("reference")(params(),
                                           jnp.asarray(seq, jnp.int32)))


# -- the layout and the sizes -------------------------------------------------

def test_the_five_kinds_lie_where_the_paper_puts_them():
    kinds = hs.layer_kinds(32)
    assert [l for l, k in enumerate(kinds) if k == "ssm"] == list(
        range(0, 17, 2))
    assert [l for l, k in enumerate(kinds) if k == "window"] == list(
        range(1, 16, 2))
    assert kinds[17] == "full"
    assert [l for l, k in enumerate(kinds) if k == "gmu"] == list(
        range(18, 32, 2))
    assert [l for l, k in enumerate(kinds) if k == "cross"] == list(
        range(19, 32, 2))
    assert list(hs.layer_kinds(8)) == ["ssm", "window", "ssm", "window",
                                       "ssm", "full", "gmu", "cross"]
    assert list(hs.layer_kinds(8)) == REF.layer_kinds(8)
    assert list(kinds) == REF.layer_kinds(32)


def test_the_published_widths_count_3_85_billion_parameters():
    cfg = hs.HybridSSMConfig()
    tree = jax.eval_shape(
        lambda: hs.init_hybrid_ssm(jax.random.PRNGKey(0), cfg))
    by_kind = {}
    for kind, lp in zip(hs.layer_kinds(32), tree["layers"]):
        by_kind[kind] = sum(math.prod(x.shape)
                            for x in jax.tree_util.tree_leaves(lp))
    m = 1e6
    assert round(by_kind["ssm"] / m, 1) == 119.9
    assert round(by_kind["window"] / m, 1) == round(
        by_kind["full"] / m, 1) == 98.3
    assert round(by_kind["gmu"] / m, 1) == 104.9
    assert round(by_kind["cross"] / m, 1) == 91.8
    total = sum(math.prod(x.shape) for x in jax.tree_util.tree_leaves(tree))
    assert round(total / 1e9, 2) == 3.85
    assert cfg.d_inner == 5120 and cfg.head_dim == 64
    assert cfg.dt_rank == math.ceil(cfg.hidden_size / 16)


def test_the_benchmarks_seeded_tree_has_the_programs_shape():
    seeded_params(MODEL, CFG, 3, REF)
    with pytest.raises(RuntimeError, match="program's shape"):
        seeded_params(MODEL, config_of(dict(MODEL, dt_rank=5)), 3, REF)


def test_the_seeded_initialisation_is_the_stated_one():
    mp = params()["layers"][0]["mixer"]
    n = MODEL["d_state"]
    assert np.allclose(np.asarray(mp["A_log"])[:, 0],
                       np.log(np.arange(1, n + 1)))
    assert np.all(np.asarray(mp["D"]) == 1.0)
    step = np.asarray(jax.nn.softplus(mp["b_dt"]))
    assert 1e-3 * 0.99 < step.min() and step.max() < 1e-1 * 1.01


# -- differential attention ---------------------------------------------------

def test_lambda_against_hand_computed_values():
    assert hs.lambda_init(0) == pytest.approx(0.2)
    assert hs.lambda_init(17) == pytest.approx(0.8 - 0.6 * math.exp(-5.1))
    ap = {"lambda_q1": jnp.asarray([0.5, -1.0]),
          "lambda_k1": jnp.asarray([2.0, 1.0]),
          "lambda_q2": jnp.asarray([1.0, 1.0]),
          "lambda_k2": jnp.asarray([0.25, 0.25])}
    # exp(0.5 * 2 - 1) - exp(0.5) + lambda_init(3)
    want = 1.0 - math.exp(0.5) + 0.8 - 0.6 * math.exp(-0.9)
    assert float(hs.diff_lambda(ap, 3)) == pytest.approx(want, rel=1e-6)


def _hand_attention(q, k, v, ap, layer, masks):
    """Pair by pair in numpy float64, straight from the equations."""
    t, h_n, hd = q.shape
    g_n = k.shape[1]
    lam0 = 0.8 - 0.6 * math.exp(-0.3 * layer)
    lam = (math.exp(float(np.dot(ap["lambda_q1"], ap["lambda_k1"])))
           - math.exp(float(np.dot(ap["lambda_q2"], ap["lambda_k2"])))
           + lam0)
    out = np.zeros((t, h_n // 2, 2 * hd))
    for i in range(h_n // 2):
        j = i // (h_n // g_n)
        vv = np.concatenate([v[:, 2 * j], v[:, 2 * j + 1]], axis=-1)
        both = []
        for c in (0, 1):
            s = q[:, 2 * i + c] @ k[:, 2 * j + c].T / math.sqrt(hd)
            s = np.where(masks, s, -np.inf)
            p = np.exp(s - s.max(axis=-1, keepdims=True))
            both.append((p / p.sum(axis=-1, keepdims=True)) @ vv)
        a = both[0] - lam * both[1]
        a = a / np.sqrt((a * a).mean(axis=-1, keepdims=True) + 1e-5)
        out[:, i] = a * np.asarray(ap["subln"], np.float64) * (1 - lam0)
    return out.reshape(t, -1)


@pytest.mark.parametrize("window", [0, WINDOW])
def test_differential_attention_against_hand_computed_values(window):
    rng = np.random.default_rng(5)
    t, h_n, g_n, hd = 13, CFG.num_attention_heads, \
        CFG.num_key_value_heads, CFG.head_dim
    q = rng.normal(size=(t, h_n, hd))
    k = rng.normal(size=(t, g_n, hd))
    v = rng.normal(size=(t, g_n, hd))
    ap = {n: rng.normal(size=(hd,)) * 0.3 for n in
          ("lambda_q1", "lambda_k1", "lambda_q2", "lambda_k2")}
    ap["subln"] = rng.normal(size=(2 * hd,))
    rows, cols = np.arange(t)[:, None], np.arange(t)[None, :]
    mask = cols <= rows
    if window:
        mask &= cols > rows - window
    want = _hand_attention(q, k, v, ap, 5, mask)
    f = lambda x: jnp.asarray(x, jnp.float32)
    apj = {n: f(x) for n, x in ap.items()}
    block = hs.attend_block(f(q).reshape(t, -1), f(k).reshape(t, -1),
                            f(v).reshape(t, -1), apj, 5, CFG, window)
    assert np.abs(np.asarray(block) - want).max() < 2e-5
    # The decode's form: the last query over a view of the keys before it
    # (in any order: there are no positions) and its own beside the view.
    lo = max(0, t - window) if window else 0
    order = rng.permutation(np.arange(lo, t - 1))
    view = hs.attend_view(
        f(q[-1]).reshape(1, -1), f(k[-1]).reshape(1, -1),
        f(v[-1]).reshape(1, -1), f(k[order]).reshape(1, len(order), -1),
        f(v[order]).reshape(1, len(order), -1),
        jnp.ones((1, len(order)), bool), apj, 5, CFG)
    assert np.abs(np.asarray(view)[0] - want[-1]).max() < 2e-5


# -- the scan kernel ----------------------------------------------------------

@pytest.mark.parametrize("t,n_valid", [(300, 300), (300, 129), (300, 128),
                                       (300, 5), (2, 1), (16, 16)])
def test_chunked_scan_equals_the_sequential_recurrence(t, n_valid):
    """Pallas in interpret mode, across chunk edges (128 steps a chunk),
    from an initial state, with a valid count: steps past it leave the
    state, rows of chunks wholly past it are zero."""
    d, n = 256, 4
    k = jax.random.split(jax.random.PRNGKey(t + n_valid), 6)
    x = jax.random.normal(k[0], (t, d))
    dt = jax.nn.softplus(jax.random.normal(k[1], (t, d)) - 2.0)
    b, c = jax.random.normal(k[2], (t, n)), jax.random.normal(k[3], (t, n))
    a = -jnp.exp(jax.random.normal(k[4], (n, d)))
    s0 = jax.random.normal(k[5], (n, d))
    y_ref, s_ref = scan.ssm_scan_reference(x, dt, b, c, a, s0, n_valid)
    y, s = scan.ssm_scan(x, dt, b, c, a, s0, n_valid, interpret=True)
    assert y.shape == (t, d) and bool(jnp.isfinite(y).all())
    assert float(jnp.abs(y[:n_valid] - y_ref[:n_valid]).max()) < 1e-5
    assert float(jnp.abs(s - s_ref).max()) < 1e-5
    # The state after n_valid steps is the state of the shorter scan.
    _, s_short = scan.ssm_scan_reference(
        x[:n_valid], dt[:n_valid], b[:n_valid], c[:n_valid], a, s0, n_valid)
    assert float(jnp.abs(s - s_short).max()) < 1e-5
    first_skipped = -(-n_valid // scan.TIME_BLOCK) * scan.TIME_BLOCK
    assert not np.asarray(y[first_skipped:]).any()


def test_the_prefill_runs_the_kernel_it_is_tested_with(monkeypatch):
    """The model's prefill through the Pallas kernel (interpreted) equals
    its prefill through the sequential scan."""
    toks = jnp.asarray(prompt(1, 24) + [0] * 8, jnp.int32)
    plain, left = _jitted("last")(params(), toks, jnp.int32(24))
    monkeypatch.setattr(hs, "ssm_scan", functools.partial(scan.ssm_scan,
                                                          interpret=True))
    kernel, left_k = jax.jit(lambda p, t, n: hs.prefill_step(p, t, n, CFG))(
        params(), toks, jnp.int32(24))
    assert float(jnp.abs(plain - kernel).max()) < 1e-5
    assert float(jnp.abs(left["state"] - left_k["state"]).max()) < 1e-5


# -- whole sequences ----------------------------------------------------------

@pytest.mark.parametrize("n", [5, 8, 9, 40])
def test_program_and_reference_agree_on_whole_sequences(n):
    seq = prompt(20 + n, n)
    got = _jitted("full")(params(), jnp.asarray(seq, jnp.int32))
    assert np.abs(np.asarray(got) - reference(seq)).max() < TOL


@pytest.mark.parametrize("n,bucket", [(3, 4), (24, 32), (32, 32), (9, 64)])
def test_last_token_second_half_equals_every_layer_over_the_prompt(n,
                                                                   bucket):
    """Layers after the full one for the last real token only, in a
    bucket whose padding must advance nothing."""
    seq = prompt(40 + n, n)
    toks = jnp.asarray(seq + [7] * (bucket - n), jnp.int32)
    last, left = _jitted("last")(params(), toks, jnp.int32(n))
    every, left_all = _jitted("every")(params(), toks, jnp.int32(n))
    assert float(jnp.abs(last - every[n - 1]).max()) < TOL
    assert np.abs(np.asarray(last) - reference(seq)[-1]).max() < TOL
    # What the prompt leaves is the same either way, and is what the
    # unpadded prompt leaves.
    _, exact = _jitted("last")(params(), jnp.asarray(seq, jnp.int32),
                               jnp.int32(n))
    for name in ("state", "tail"):
        assert float(jnp.abs(left[name] - left_all[name]).max()) == 0.0
        assert float(jnp.abs(left[name] - exact[name]).max()) < 1e-6
    # The window layers' keys and values of the real tokens, whole: the
    # caller keeps the last ring's worth.
    for name in ("window_k", "window_v"):
        assert left[name].shape[:2] == (hs.layer_kinds(
            MODEL["num_hidden_layers"]).count("window"), bucket)
        assert float(jnp.abs(left[name][:, :n] - exact[name]).max()) < 1e-6


def test_the_tolerance_would_catch_bfloat16():
    seq = prompt(77, 40)
    exact = reference(seq)
    rounded = np.asarray(_jitted("bf16")(params(),
                                         jnp.asarray(seq, jnp.int32)))
    assert np.abs(rounded - exact).max() > 20 * TOL
    # And the program in bfloat16 is as far off.
    cfg16 = config_of(dict(MODEL, dtype="bfloat16"))
    p16 = jax.tree_util.tree_map(lambda a: a.astype(jnp.bfloat16), params())
    got = jax.jit(lambda p, t: hs.forward_full(p, t, cfg16))(
        p16, jnp.asarray(seq, jnp.int32))
    assert np.abs(np.asarray(got) - exact).max() > 20 * TOL


# -- prefill then decode through the engine's stores --------------------------

@functools.lru_cache(maxsize=None)
def engine():
    eng = InferenceEngine(params(), CFG, max_slots=8, page_size=PAGE,
                          capacity=128)
    eng.warm_start()
    return eng


def rollout(eng, prompts, max_new):
    """Drive ``prompts`` together; returns, for each, the logits rows the
    engine's own executables produced (the prefill's last row, then one
    row a decode iteration) and the tokens it served."""
    reqs = [eng.submit(list(p), max_new_tokens=n)
            for p, n in zip(prompts, max_new)]
    rows = {r.rid: [] for r in reqs}
    orig_prefill, orig_retire = eng._prefill, eng._retire

    def prefill(slot, req, *a, **kw):
        out = orig_prefill(slot, req, *a, **kw)
        rows[req.rid].append(np.asarray(out[2]))  # (token, tokens, last)
        return out

    def retire(flight, first):
        owners = {slot: req.rid for slot, req in flight.riders.items()
                  if req.finish_reason is None}
        logits = np.asarray(orig_retire(flight, first))
        for slot, rid in owners.items():
            rows[rid].append(logits[slot].copy())
        return logits

    eng._prefill, eng._retire = prefill, retire
    try:
        eng.run_until_idle()
    finally:
        eng._prefill, eng._retire = orig_prefill, orig_retire
    return [(np.stack(rows[r.rid]), r.result(0)) for r in reqs]


def check_against_reference(prompts, new, got):
    for p, n, (rows, toks) in zip(prompts, new, got):
        assert len(toks) == n and rows.shape[0] == n
        # Row i was computed after len(p) + i tokens: the reference's
        # logits at position len(p) - 1 + i.
        ref = reference(p + toks)[len(p) - 1:len(p) - 1 + n]
        assert np.abs(rows - ref).max() < TOL


# Ragged slots.  Prompts of 3 (bucket 4: a pad that must advance nothing),
# exactly a bucket (32), shorter than the window, longer than it (the
# prefill's ring has wrapped) and answers that cross it in decode (the
# ring wraps under decode); the last case has six of eight slots alive.
@pytest.mark.parametrize("lengths,new", [
    ((3,), (12,)), ((32,), (3,)), ((6, 19), (9, 4)),
    ((20, 5, 70), (6, 14, 3)),
    ((40, 9, 100, 30, 66, 12), (5, 6, 7, 8, 9, 10))])
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


def test_a_reused_slot_answers_as_a_fresh_engine_does():
    """Recurrent state has no mask: the slot's state and tails must be
    REPLACED by the next prefill.  A long sequence leaves its state in slot
    0, a short one follows it there."""
    eng = engine()
    resets = counter("serving.state_slot_resets")
    long_p, short_p = prompt(901, 90), prompt(902, 5)
    rollout(eng, [long_p], [20])
    second = rollout(eng, [short_p], [12])
    assert counter("serving.state_slot_resets") - resets == 2
    fresh = InferenceEngine(params(), CFG, max_slots=8, page_size=4,
                            capacity=128)
    first = rollout(fresh, [short_p], [12])
    assert second[0][1] == first[0][1]
    assert np.abs(second[0][0] - first[0][0]).max() == 0.0
    check_against_reference([short_p], [12], second)


def test_run_ahead_loop_equals_the_loop_held_at_depth_0(monkeypatch):
    """The decode loop one iteration ahead over this model's program (six
    donated arrays, state beside two groups' pages): staggered admissions and
    finishes serve the same tokens as the loop that fetches before it
    launches, token for token, and count the same reads."""
    eng = engine()
    trace = [(prompt(400 + i, n), new, at) for i, (n, new, at) in enumerate(
        [(20, 7, 0), (70, 2, 0), (9, 5, 1), (33, 1, 2), (100, 6, 2),
         (12, 4, 6), (66, 3, 6)])]
    names = ("serving.decode_ahead", "serving.decode_iterations",
             "serving.shared_kv_tokens", "serving.window_tokens",
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

    ahead, (n_ahead, n_iter, shared, window, tokens, prefilled) = replay()
    monkeypatch.setattr(eng, "_runs_ahead", lambda active: False)
    held, (h_ahead, h_iter, h_shared, h_window, h_tokens, _) = replay()
    assert ahead == held and [len(t) for t in ahead] == [
        n for _, n, _ in trace]
    assert h_ahead == 0 and 0.5 * n_iter < n_ahead < n_iter
    assert tokens == h_tokens == sum(n for _, n, _ in trace)
    assert prefilled == sum(len(p) for p, _, _ in trace)
    # A decode iteration attends, for a request at its i-th decoded token,
    # len(prompt) + i positions of the one store (its own included) and at
    # most the window of each ring.  The loop one ahead may launch a
    # request once more than it is fed (never here: every end is a count).
    want_shared = sum(len(p) + i for p, n, _ in trace for i in range(1, n))
    want_window = sum(min(len(p) + i, WINDOW) for p, n, _ in trace
                      for i in range(1, n))
    assert shared == h_shared == want_shared
    assert window == h_window == want_window


# -- the protocol -------------------------------------------------------------

def test_the_cache_manager_owns_four_kinds_of_state():
    eng = engine()
    c = eng.cache
    kinds = hs.layer_kinds(MODEL["num_hidden_layers"])
    kvw = CFG.kv_width
    n_win, n_ssm = kinds.count("window"), kinds.count("ssm")
    # (a) ONE paged layer of every position; (b) the window layers' pages,
    # a ring a slot, bounded by the window and not by the capacity; (c)
    # recurrent state that is no function of position; (d) nothing at all
    # for the gated-memory and cross layers.
    assert c.n_layers == 1 and c.entry_widths == (kvw, kvw)
    assert c.group_names == ("full", "window")
    assert [p.shape for p in c.pages] == [(1, c.n_pages, PAGE, kvw)] * 2
    assert c.table_width == 32 + RING and RING * PAGE < c.capacity
    assert [s["name"] for s in c.slot_stores] == ["ssm_state", "conv_tail"]
    assert [x.shape for x in c.slot_state] == [
        (n_ssm, 8, CFG.d_state, CFG.d_inner),
        (n_ssm, 8, CFG.d_conv - 1, CFG.d_inner)]
    assert c.slot_state[0].dtype == jnp.float32
    # What the executables take and return: the full group's two arrays,
    # the window group's two, the two state stores.  No room to gather
    # into, no ring store.
    assert len(c.arrays) == 6 and c.arrays[:2] == c.pages
    assert [x.shape for x in c.arrays[2:4]] == [
        (n_win, 8 * RING + 1, PAGE, kvw)] * 2
    nbytes = c.slot_store_bytes()
    assert set(nbytes) == {"state"}
    assert nbytes["state"] == n_ssm * 8 * (CFG.d_state + CFG.d_conv - 1) \
        * CFG.d_inner * 4
    assert telemetry.metrics()["serving.state_bytes"]["value"] \
        == nbytes["state"]
    # Every slot keeps its ring's pages (no pool, no reservation): a free
    # slot implies room in both groups.
    assert c.group_pages() == {"full": (0, 8 * 32), "window": (0, 8 * RING)}
    assert c.total_pages == 8 * 32 and c.free_pages() == c.total_pages
    with pytest.raises(ValueError, match="page arrays"):
        c.replace_pages(*c.pages)


def test_the_cache_entry_declares_two_groups_and_two_state_stores():
    entry = CFG.serving_model().cache_entry()
    kinds = hs.layer_kinds(MODEL["num_hidden_layers"])
    assert entry["groups"] == (
        {"name": "full", "n_layers": 1},
        {"name": "window", "n_layers": kinds.count("window"),
         "window": WINDOW})
    assert [(s["name"], s["kind"]) for s in entry["slot_stores"]] == [
        ("ssm_state", "state"), ("conv_tail", "state")]
    # At the published sizes: 8 window layers behind a ring of 33 pages of
    # 16 a slot, whatever the capacity.
    big = hs.HybridSSMConfig().serving_model().cache_entry()
    assert big["groups"][1] == {"name": "window", "n_layers": 8,
                                "window": 512}
    assert ring_entries(512, 16) == 33
    assert not any(s["kind"] in ("window", "scratch")
                   for s in big["slot_stores"])


def test_the_ledger_holds_every_store():
    from horovod_tpu.memory import ledger as mem

    if not mem.enabled():
        pytest.skip("the memory ledger is off")
    eng = engine()
    got = mem.ledger.bytes_by_category()
    assert got["serving.slot_state"] >= sum(
        eng.cache.slot_store_bytes().values())
    # Both groups' pages: arrays[:4].
    assert got["serving.kv_pages"] >= sum(
        mem.resident_nbytes(p) for p in eng.cache.arrays[:4])


def _window_rows(eng, slot, layer=0):
    """What the window group holds for ``slot`` in one of its layers, by
    position: ``{position: key row}`` of every position the new token
    would attend, read through the slot's ring as the program reads it."""
    c = eng.cache
    table, lengths = c.host_tables()
    cached = int(lengths[slot])
    ring = table[slot, c.pages_per_slot:]
    k = np.asarray(c.arrays[2])[layer]
    return {pos: k[ring[(pos // PAGE) % RING], pos % PAGE]
            for pos in range(max(0, cached - WINDOW + 1), cached)}


def _admit(eng, seq):
    """Admit ``seq`` and stop after the pass that prefilled it (which also
    launches its first decode iterations), the slot still alive."""
    req = eng.submit(list(seq), max_new_tokens=6)
    eng.step(now=0)
    (slot,) = [s for s in range(eng.max_slots)
               if eng.cache.length(s) >= len(seq)]
    return req, slot


def _holds_the_prompts_window(eng, slot, seq):
    """Every position the next token attends lies in the slot's ring, and
    those of the prompt hold the window layer's keys the prompt left."""
    _, left = _jitted("last")(params(), jnp.asarray(seq + [0, 0], jnp.int32),
                              jnp.int32(len(seq)))
    cached = eng.cache.length(slot)
    rows = _window_rows(eng, slot)
    assert sorted(rows) == list(range(max(0, cached - WINDOW + 1), cached))
    mine = [pos for pos in rows if pos < len(seq)]
    assert len(mine) >= min(len(seq), WINDOW - 3)
    for pos in mine:
        assert np.abs(rows[pos]
                      - np.asarray(left["window_k"][0, pos])).max() < 1e-6


def test_a_prompt_longer_than_the_window_leaves_exactly_its_last_ring():
    """A prompt of 30 in pages of 4 behind a ring of 3: the window group
    maps 3 pages where the full group maps 8, and they hold the window
    layer's keys of the last positions, each where the ring's rule puts
    it."""
    eng = engine()
    seq = prompt(950, 30)
    req, slot = _admit(eng, seq)
    try:
        used = eng.cache.group_pages()
        assert used["window"][0] == RING and used["full"][0] == 8
        _holds_the_prompts_window(eng, slot, seq)
    finally:
        eng.run_until_idle()
    assert len(req.result(0)) == 6
    assert eng.cache.group_pages()["window"][0] == 0


def test_a_reused_slots_ring_holds_nothing_of_its_last_owner():
    """A long sequence wraps slot 0's ring many times and leaves; a short
    one follows it there: every row the short one attends is its own, and
    it is served as a fresh engine serves it (its ring's pages may be the
    very pages the long one wrote)."""
    eng = engine()
    long_p, short_p = prompt(960, 70), prompt(961, 5)
    rollout(eng, [long_p], [20])
    req, slot = _admit(eng, short_p)
    try:
        assert slot == 0
        _holds_the_prompts_window(eng, slot, short_p)
        assert eng.cache.group_pages()["window"][0] == 2   # 5 to 8 rows
    finally:
        eng.run_until_idle()
    fresh = InferenceEngine(params(), CFG, max_slots=8, page_size=PAGE,
                            capacity=128)
    assert rollout(fresh, [short_p], [6])[0][1] == req.result(0)


@pytest.mark.parametrize("lengths", [
    (899, -1, 0, 511, -1, 512, 528, 6143), (-1,) * 8,
    (1100,) * 26 + (-1,) * 38])
def test_decode_view_is_what_the_kernel_copies_of_both_groups(lengths):
    """At the cell's sizes (page 16, 384 pages a slot, a ring of 33): the
    full group's entries in use once for each of its 8 readers (the full
    layer and 7 cross layers), the window group's, at most the ring, once
    for each of the 8 window layers; over the 16 and the slots."""
    model = hs.HybridSSMConfig().serving_model()
    lengths = np.asarray(lengths, np.int32)
    full = gpa.tokens_read(lengths, 384, 16)
    window = gpa.tokens_read(lengths, 33, 16)
    got = model.decode_view(lengths, 16, 384)
    assert got == pytest.approx((8 * full + 8 * window) / 16 / len(lengths))
    live = lengths[lengths >= 0]
    if not len(live):
        assert got == 0
    elif len(live) == 26:
        # The cell's load: 26 alive at 1100: (1104 + 528) / 2 x 26 / 64.
        assert got == pytest.approx(331.5)
    else:
        by_hand = (912 + 0 + 512 + 512 + 528 + 6144,
                   528 + 0 + 512 + 512 + 528 + 528)
        assert (full, window) == by_hand


def test_a_dense_model_has_no_slot_store_and_its_two_arrays():
    c = PagedKVCache(2, 2, 16, max_slots=2, pages_per_slot=2, page_size=4)
    assert c.slot_state == () and c.arrays == c.pages and len(c.arrays) == 2
    assert c.slot_store_bytes() == {}


def test_prefix_cache_is_off_with_its_reason():
    from horovod_tpu.telemetry import flight

    eng = InferenceEngine(params(), CFG, max_slots=2, page_size=4,
                          capacity=64, prefix_cache=True)
    assert not eng.cache.prefix_enabled
    assert "not page-addressable" in eng.model.prefix_cache_why
    events = flight.snapshot()
    assert not events or any(
        kind == "serve_prefix_cache_off"
        and eng.model.prefix_cache_why in args for _, kind, args in events)


def test_draft_and_tensor_parallel_are_refused_clearly():
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




# -- decode through the paged kernel (ops/gqa_paged_attention.py) -------------

@functools.lru_cache(maxsize=None)
def _kernel_engine():
    eng = InferenceEngine(params(), CFG, max_slots=8, page_size=PAGE,
                          capacity=128)
    eng.warm_start()
    return eng


def kernel_engine(monkeypatch):
    """The engine with the kernel in its decode program, interpreted:
    ``PAGED_INTERPRET`` is read when the programs are built."""
    monkeypatch.setattr(hs, "PAGED_INTERPRET", True)
    return _kernel_engine()


# Both sides of the window of 8 and of its ring of 3 pages of 4: never
# reaching it; starting under it and wrapping the ring more than once; a
# prompt longer than twice the window; ragged slots of all kinds.
@pytest.mark.parametrize("lengths,new", [
    ((3,), (3,)), ((5,), (30,)), ((30,), (20,)), ((8,), (9,)),
    ((6, 19, 40), (9, 14, 25))])
def test_prefill_then_decode_through_the_kernel_equals_the_reference(
        monkeypatch, lengths, new):
    eng = kernel_engine(monkeypatch)
    prompts = [prompt(100 + n, n) for n in lengths]
    views = counter("serving.decode_view_tokens")
    got = rollout(eng, prompts, new)
    check_against_reference(prompts, new, got)
    assert eng.cache.group_pages() == {"full": (0, 8 * 32),
                                       "window": (0, 8 * RING)}
    # Iteration i (0-based) attends the slots with more than i + 1 tokens
    # to give, each at its prompt's length plus i cached positions: what
    # the kernel copies of the full group for its two readers (the toy has
    # one cross layer) and of the window group for its two window layers,
    # over the four and the eight slots.
    read = 0
    for i in range(max(new) - 1):
        at = [n + i if i + 1 < k else -1 for n, k in zip(lengths, new)]
        read += (2 * gpa.tokens_read(at, 32, PAGE)
                 + 2 * gpa.tokens_read(at, RING, PAGE)) / 4 / 8
    assert counter("serving.decode_view_tokens") - views \
        == pytest.approx(read)


def _decode_primitives(eng):
    from test_latent_paged_attention import _primitives

    table, lengths = eng.cache.device_tables()
    n = len(eng.cache.arrays)

    def fn(params, *rest):
        outs, pages = eng._decode_step(params, rest[:n], *rest[n:])
        return (*outs, *pages)

    return _primitives(jax.make_jaxpr(fn)(
        eng.params, *eng.cache.arrays, table, lengths, eng._no_tokens,
        eng._no_override).jaxpr)


def test_off_the_tpu_the_rows_are_gathered_unless_the_interpreter_is_asked_for(
        monkeypatch):
    """The rule is the backend's (``ops/flash_attention.kernel_runs``): on
    the CPU the decode program gathers a slot's table row and attends that; with the kernel
    forced, sixteen calls' worth of ``pallas_call`` and the only gathers
    left are the embedding's and the tables'.  Neither holds a ladder: no
    conditional, no loop over chunks."""
    assert jax.default_backend() == "cpu" and not hs.paged_kernel_runs()
    twin = _decode_primitives(engine())
    assert "gather" in twin and "pallas_call" not in twin
    assert not twin & {"cond", "while", "sort"}
    eng = kernel_engine(monkeypatch)
    assert hs.paged_kernel_runs()
    kernel = _decode_primitives(eng)
    assert "pallas_call" in kernel
    assert not kernel & {"cond", "while", "sort"}
    # The same store either way: the model asks for no room to gather into.
    assert len(eng.cache.arrays) == len(engine().cache.arrays) == 6


# -- the counter's reader (benchmark/metrics/hybrid_view_tokens.py) -----------

def test_hybrid_view_tokens_is_declared_for_the_cell():
    bench = cells.load_benchmark()
    mod = cells.load_module("metrics", "hybrid_view_tokens")
    (entry,) = [m for m in bench["per_layer"]
                if m["name"] == "hybrid_view_tokens"]
    assert entry == {
        "name": "hybrid_view_tokens", "unit": mod.UNIT, "better": mod.BETTER,
        "source": mod.SOURCE, "layer": mod.LAYER, "moves": mod.MOVES,
        "workloads": ["phi4flash-serve-reason"]}
    assert (mod.UNIT, mod.BETTER, mod.SOURCE, mod.LAYER, mod.MOVES) == (
        "tokens", "lower", "program_counter", "grouped-query attention",
        "tpot_p90_ms")
    for cell in (w["name"] for w in bench["workloads"]):
        listed = [m["name"] for m in cells.resolve(bench, cell)["per_layer"]]
        assert ("hybrid_view_tokens" in listed) == (
            cell == "phi4flash-serve-reason")


def test_hybrid_view_tokens_is_the_view_counter_over_the_iterations():
    from test_latent_paged_attention import _Run

    read = cells.load_module("metrics", "hybrid_view_tokens").read
    assert read(_Run(
        {"serving.decode_view_tokens": {"value": 768.0},
         "serving.decode_iterations": {"value": 1}},
        {"serving.decode_view_tokens": {"value": 768.0 + 300 * 340
                                        + 100 * 300},
         "serving.decode_iterations": {"value": 401}})) \
        == pytest.approx(330.0)
    assert read(_Run({"serving.decode_iterations": {"value": 5}},
                     {"serving.decode_iterations": {"value": 55}})) is None
    assert read(_Run({}, {})) is None
