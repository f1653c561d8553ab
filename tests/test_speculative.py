"""hvd-spec: speculative decoding with the bitwise-greedy acceptance
kernel, and its composition with the shared-prefix page cache.

The load-bearing assertion (ISSUE 15 acceptance): speculative greedy
completions are BITWISE-equal to non-speculative greedy completions —
for ANY draft model (the acceptance rule gates every token through the
target's verify logits, which are bitwise-equal to the decode
executable's at every position), any acceptance pattern, any batch
mix, and across an elastic drain/resume.  The draft only ever moves
wall-clock, never tokens.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from horovod_tpu.models.transformer import (TransformerConfig,
                                            init_transformer,
                                            serving_forward)
from horovod_tpu.serving import InferenceEngine, Request
from horovod_tpu.utils import xla_dispatch

CFG = TransformerConfig(vocab_size=97, d_model=64, n_heads=4, n_layers=2,
                        d_ff=128, max_seq_len=64)
PARAMS = init_transformer(jax.random.PRNGKey(0), CFG)
# A RANDOM draft: its proposals are essentially uncorrelated with the
# target's greedy tokens (acceptance ~0) — the adversarial case for
# the bitwise contract.
DRAFT_CFG = TransformerConfig(vocab_size=97, d_model=32, n_heads=2,
                              n_layers=1, d_ff=64, max_seq_len=64)
DRAFT = init_transformer(jax.random.PRNGKey(9), DRAFT_CFG)


def _zeroed_layers(params):
    """Every layer's residual contribution zeroed (attention and FFN
    output projections): the logits are ``ln_f(embed + pos) @ unembed``
    whatever the depth or width."""
    layers = dict(params["layers"])
    for k in ("wo", "w_out", "b_out"):
        layers[k] = jnp.zeros_like(layers[k])
    return dict(params, layers=layers)


def agreement_pair():
    """(target, draft) whose greedy argmax agrees at every position:
    both models' layers are zeroed and the draft shares the target's
    embed/pos/ln_f/unembed, so acceptance is deterministically 1.0
    while the draft still pays only its own, smaller, layer stack."""
    tcfg = CFG
    dcfg = TransformerConfig(vocab_size=97, d_model=64, n_heads=4,
                             n_layers=1, d_ff=32, max_seq_len=64)
    tparams = _zeroed_layers(init_transformer(jax.random.PRNGKey(0), tcfg))
    dparams = _zeroed_layers(init_transformer(jax.random.PRNGKey(1), dcfg))
    for k in ("embed", "pos_embed", "ln_f", "unembed"):
        dparams[k] = tparams[k]
    return (tparams, tcfg), (dparams, dcfg)


def count_spec_dispatches(engine):
    """``(propose_calls, verify_calls, eager_dispatches)`` of ONE
    steady-state speculative iteration of ``engine`` (slots active)."""
    keys = (("draft_propose", engine.spec_tokens),
            ("verify", engine.spec_tokens + 1))
    saved = {k: engine._exec[k] for k in keys}
    calls = dict.fromkeys(keys, 0)

    def counted(key):
        def call(*a):
            calls[key] += 1
            return saved[key](*a)
        return call

    engine._exec.update({k: counted(k) for k in keys})
    try:
        with xla_dispatch.exact_scope():
            with xla_dispatch.record(all_threads=True) as scope:
                engine.step()
    finally:
        engine._exec.update(saved)
    return calls[keys[0]], calls[keys[1]], scope.count


def make_engine(params=PARAMS, cfg=CFG, **kw):
    kw.setdefault("max_slots", 3)
    kw.setdefault("page_size", 8)
    kw.setdefault("capacity", 32)
    return InferenceEngine(params, cfg, **kw)


def make_spec_engine(**kw):
    kw.setdefault("draft", (DRAFT, DRAFT_CFG))
    kw.setdefault("spec_tokens", 3)
    return make_engine(**kw)


# Warm engines are the dominant test cost (each warm_start AOT-compiles
# decode + propose + verify); tests that leave the engine idle share
# these module-scoped ones.  Tests that drain, relaunch, or need
# bespoke shapes still build their own.
_CACHED = {}


def spec_eng():
    if "spec" not in _CACHED:
        e = make_spec_engine()
        e.warm_start()
        _CACHED["spec"] = e
    return _CACHED["spec"]


def base_eng():
    if "base" not in _CACHED:
        e = make_engine()
        e.warm_start()
        _CACHED["base"] = e
    return _CACHED["base"]


def agree_eng():
    if "agree" not in _CACHED:
        (tp, tc), (dp, dc) = agreement_pair()
        e = make_engine(tp, tc, draft=(dp, dc), spec_tokens=3)
        e.warm_start()
        _CACHED["agree"] = (e, tp, tc)
    return _CACHED["agree"]


def reference_rollout(prompt, n, capacity, params=PARAMS, cfg=CFG):
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
# The bitwise-greedy acceptance contract
# ---------------------------------------------------------------------------

def test_spec_bitwise_with_uncorrelated_draft():
    """ANY draft yields bitwise non-speculative completions — here an
    uncorrelated one whose proposals are almost always rejected, so
    every iteration exercises the rejection/rollback path."""
    eng = spec_eng()
    prompts = [[5, 3, 8], [1, 2, 3, 4, 5, 6], [9, 9, 2, 6]]
    ref = [reference_rollout(p, 7, eng.capacity) for p in prompts]
    assert [eng.generate(list(p), max_new_tokens=7)
            for p in prompts] == ref
    # Concurrent: the three share the decode batch; completions are
    # invariant to batch composition under speculation too.
    reqs = [eng.submit(list(p), max_new_tokens=7) for p in prompts]
    eng.run_until_idle()
    assert [r.result(0) for r in reqs] == ref
    # The uncorrelated draft's acceptance really is low — the test
    # above exercised rejection, not a lucky always-accept draft.
    assert eng.spec_acceptance_rate is not None
    assert eng.spec_acceptance_rate < 0.5


@pytest.mark.slow
@pytest.mark.parametrize("spec_tokens", [1, 3, 5])
def test_spec_depth_never_changes_tokens(spec_tokens):
    eng = make_spec_engine(spec_tokens=spec_tokens)
    eng.warm_start()
    ref = reference_rollout([7, 1, 4], 9, eng.capacity)
    assert eng.generate([7, 1, 4], max_new_tokens=9) == ref


def test_spec_full_acceptance_emits_blocks():
    """The agreement pair accepts every proposal: each iteration emits
    spec_tokens + 1 tokens, and the completions still match the
    target's own reference rollout bitwise."""
    eng, tp, tc = agree_eng()
    ref = reference_rollout([5, 3, 8], 12, eng.capacity, tp, tc)
    req = eng.submit([5, 3, 8], max_new_tokens=12)
    iters = 0
    while not eng.scheduler.idle():
        eng.step()
        iters += 1
    assert req.result(0) == ref
    assert eng.spec_acceptance_rate == 1.0
    # 12 tokens: 1 at prefill + 11 through blocks of <= 4 -> the first
    # step (admission+block) plus at most 2 more iterations.
    assert iters <= 4


def test_spec_steady_state_is_one_propose_one_verify_dispatch():
    """Dispatch contract under speculation: a steady-state iteration is
    exactly ONE draft propose + ONE target verify executable call,
    with zero eager launches — the decode path's megakernel discipline
    carried over (verify included in the one target dispatch)."""
    eng = spec_eng()
    for p in ([1, 2, 3], [4, 5, 6, 7]):
        eng.submit(list(p), max_new_tokens=8)
    eng.step()  # admissions + prefills + first block
    proposes, verifies, eager = count_spec_dispatches(eng)
    assert (proposes, verifies) == (1, 1), (proposes, verifies)
    assert eager == 0, (
        f"{eager} eager dispatches leaked out of the speculative "
        f"iteration")
    eng.run_until_idle()


def test_spec_eos_mid_block_stops_exactly_at_eos():
    """EOS landing inside an accepted block: the tokens after it are
    discarded exactly as non-speculative decode would never have
    produced them."""
    eng, tp, tc = agree_eng()
    ref = reference_rollout([5, 3, 8], 12, 32, tp, tc)
    # Stop on the 4th reference token — mid-block at full acceptance
    # (the first block after prefill emits ref[1..4]).
    out = eng.generate([5, 3, 8], max_new_tokens=12, eos_id=ref[3])
    assert out == ref[:4]


@pytest.mark.slow
@pytest.mark.parametrize("use_agreement", [False, True])
def test_spec_capacity_finish_is_bitwise(use_agreement):
    """A CAPACITY-finished speculative rollout (blocks written at the
    view's edge, trash-dropped past it) matches the non-incremental
    reference bitwise."""
    if use_agreement:
        (tp, tc), (dp, dc) = agreement_pair()
    else:
        (tp, tc), (dp, dc) = (PARAMS, CFG), (DRAFT, DRAFT_CFG)
    eng = make_engine(tp, tc, draft=(dp, dc), spec_tokens=3)
    eng.warm_start()
    prompt = [int(t) for t in jax.random.randint(
        jax.random.PRNGKey(7), (eng.capacity - 5,), 0, tc.vocab_size)]
    req = eng.submit(list(prompt), max_new_tokens=99)
    eng.run_until_idle()
    out = req.result(0)
    assert req.finish_reason == "capacity"
    assert len(prompt) + len(out) == eng.capacity
    assert out == reference_rollout(prompt, len(out), eng.capacity,
                                    tp, tc)


def test_spec_mixed_batch_with_temperature_slot():
    """Mixed speculative/non-speculative batch: greedy slots ride the
    acceptance rule, a temperature slot samples from the block's first
    position — bitwise what the non-speculative engine samples."""
    eng = spec_eng()
    base = base_eng()
    greedy_ref = reference_rollout([5, 3, 8], 6, eng.capacity)
    temp_base = base.generate([2, 4, 6], max_new_tokens=6,
                              temperature=0.8, seed=17)
    r_greedy = eng.submit([5, 3, 8], max_new_tokens=6)
    r_temp = eng.submit([2, 4, 6], max_new_tokens=6, temperature=0.8,
                        seed=17)
    eng.run_until_idle()
    assert r_greedy.result(0) == greedy_ref
    assert r_temp.result(0) == temp_base


def test_spec_drain_resume_reproduces_uninterrupted_rollout():
    """Elastic drain mid-speculation → export → fresh spec engine →
    import: the stitched completion equals the uninterrupted one (and
    the non-speculative reference)."""
    ref = reference_rollout([3, 1, 4, 1, 5], 10, 32)
    eng = make_spec_engine()
    eng.warm_start()
    req = eng.submit([3, 1, 4, 1, 5], max_new_tokens=10)
    eng.step()
    eng.step()  # a couple of speculative iterations in
    exported = eng.drain()
    assert exported and req.finish_reason == "drained"
    eng2 = make_spec_engine()
    eng2.warm_start()
    [req2] = eng2.import_requests(exported)
    eng2.run_until_idle()
    assert req2.result(0) == ref


def test_spec_client_disconnect_releases_draft_and_target_slots():
    """abort_request mid-speculation: the iteration-boundary eviction
    frees the slot's pages on BOTH stores and decrements the prefix
    refcounts — nothing leaks."""
    eng = spec_eng()
    req = eng.submit(list(range(1, 18)), max_new_tokens=50)
    eng.step()
    assert eng.scheduler.occupancy() == 1
    assert eng.abort_request(req) == "active"
    eng.step()  # the boundary eviction
    assert req.finish_reason == "client_disconnect"
    assert eng.cache.free_pages() == eng.cache.total_pages
    assert eng.draft_cache.free_pages() == eng.draft_cache.total_pages
    assert eng.cache.prefix_stats()["referenced_pages"] == 0


def test_spec_composes_with_prefix_cache():
    """Prefix hit + speculation together: the second request maps the
    first's header pages copy-free AND speculates — completions stay
    bitwise-equal to the plain engine with both features off."""
    header = list(range(1, 17))  # two full pages at page_size=8
    # Ground truth: the non-incremental reference (≡ a cache-off
    # engine, per the standing contract).
    a_ref = reference_rollout(header + [20, 21], 6, 32)
    b_ref = reference_rollout(header + [30, 31, 32], 6, 32)
    eng = spec_eng()
    assert eng.generate(header + [20, 21], max_new_tokens=6) == a_ref
    before = eng.cache.prefix_stats()["cached_pages"]
    assert before >= 2
    assert eng.generate(header + [30, 31, 32],
                        max_new_tokens=6) == b_ref


def test_spec_draft_store_takes_prefix_hits_of_its_own():
    """hvd-route satellite: the DRAFT KV store rides the shared-prefix
    index too — a repeated header maps copy-free on BOTH stores, and
    the draft's hits count on the split ``serving.prefix_hits_draft``
    counter (hvd-tune's hit-rate sensor sums the two)."""
    from horovod_tpu import telemetry as _telemetry

    def draft_hits():
        return _telemetry.metrics().get(
            "serving.prefix_hits_draft", {}).get("value", 0)

    header = list(range(40, 56))  # two full pages at page_size=8
    eng = spec_eng()
    eng.generate(header + [60, 61], max_new_tokens=4)
    # The first request published the header pages on both stores.
    assert len(eng.draft_cache.lookup_prefix(header + [70])) == 2
    assert eng.draft_cache.prefix_stats()["cached_pages"] >= 2
    h0 = draft_hits()
    ref = reference_rollout(header + [70, 71], 4, 32)
    assert eng.generate(header + [70, 71], max_new_tokens=4) == ref
    assert draft_hits() - h0 == 1


@pytest.mark.slow
def test_spec_warm_start_records_and_rebuilds_executables(tmp_path,
                                                          monkeypatch):
    """The manifest records verify/draft_propose/draft_prefill entries
    keyed to the draft model + speculation depth; a fresh engine's
    warm_start rebuilds them BEFORE any request, and an engine with a
    different depth skips the foreign entries."""
    import json as _json

    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path))
    e1 = make_spec_engine()
    e1.warm_start()
    out1 = e1.generate([1, 2, 3, 4, 5], max_new_tokens=6)
    man = _json.loads(
        (tmp_path / "megakernel_manifest.json").read_text())
    kinds = {(e["kind"], e.get("bucket")) for e in man["entries"]
             if e["variant"] == "serving"}
    assert ("verify", 4) in kinds and ("draft_propose", 3) in kinds
    assert any(k == "draft_prefill" for k, _ in kinds)

    e2 = make_spec_engine()
    warmed = e2.warm_start(str(tmp_path))
    assert warmed >= 3
    assert ("verify", 4) in e2._exec
    assert ("draft_propose", 3) in e2._exec
    assert e2.generate([1, 2, 3, 4, 5], max_new_tokens=6) == out1

    # Different speculation depth: the spec executables are foreign
    # (not rebuilt from the manifest), but warm_start still builds its
    # own fresh pair.
    e3 = make_spec_engine(spec_tokens=2)
    e3.warm_start(str(tmp_path))
    assert ("verify", 3) in e3._exec
    assert ("verify", 4) not in e3._exec


def test_spec_health_reports_speculation():
    eng = spec_eng()
    ready, payload = eng.health()
    assert ready and payload["speculative"] is True
    assert payload["spec_tokens"] == 3
    _, payload2 = base_eng().health()
    assert payload2["speculative"] is False


def test_spec_telemetry_counters_flow():
    from horovod_tpu import telemetry as _telemetry

    def counter(name):
        return _telemetry.metrics().get(name, {}).get("value", 0)

    before_p = counter("serving.spec_proposed")
    before_a = counter("serving.spec_accepted")
    eng, _tp, _tc = agree_eng()
    eng.generate([5, 3, 8], max_new_tokens=8)
    proposed = counter("serving.spec_proposed") - before_p
    accepted = counter("serving.spec_accepted") - before_a
    assert proposed > 0 and accepted > 0
    assert counter("serving.spec_acceptance_rate") > 0.0


def test_spec_tokens_env_zero_is_fine_without_a_draft(monkeypatch):
    """HVD_TPU_SPEC_TOKENS=0 (the natural 'speculation off' setting)
    must not break draft-less engines — the depth is unused there."""
    monkeypatch.setenv("HVD_TPU_SPEC_TOKENS", "0")
    eng = make_engine()
    assert eng.spec_tokens == 0
    with pytest.raises(ValueError, match="spec_tokens"):
        make_engine(draft=(DRAFT, DRAFT_CFG))  # armed -> validated


def test_spec_all_temperature_batch_falls_back_to_decode():
    """An iteration with no greedy slot runs plain decode: sampled
    slots never consult proposals, so propose + wide verify would be
    pure overhead."""
    eng = spec_eng()
    req = eng.submit([4, 4, 4], max_new_tokens=4, temperature=0.7,
                     seed=5)
    eng.step()
    proposes = {"n": 0}
    pkey = ("draft_propose", 3)
    p_exec = eng._exec[pkey]
    eng._exec[pkey] = lambda *a: (
        proposes.__setitem__("n", proposes["n"] + 1) or p_exec(*a))
    eng.run_until_idle()
    eng._exec[pkey] = p_exec
    assert proposes["n"] == 0
    base = base_eng()
    assert req.result(0) == base.generate([4, 4, 4], max_new_tokens=4,
                                          temperature=0.7, seed=5)


def test_seed_prefixes_failure_frees_ghost_pages():
    """A prefill that raises mid-seed must return the ghost pages to
    the free list and let the restore continue with the next chain."""
    eng = make_engine(prefix_cache=True)
    eng.warm_start()
    free_before = eng.cache.free_pages()

    orig = eng._prefill_exec
    calls = {"n": 0}

    def failing(bucket, draft=False):
        calls["n"] += 1
        if calls["n"] == 1:
            raise RuntimeError("seeded prefill failure")
        return orig(bucket, draft)

    eng._prefill_exec = failing
    seeded = eng.seed_prefixes([list(range(16)),
                                list(range(50, 66))])
    eng._prefill_exec = orig
    # First chain failed and freed its pages; second seeded.
    assert seeded == 2
    assert eng.cache.free_pages() == free_before
    assert eng.cache.prefix_stats()["cached_pages"] == 2


def test_spec_rejects_bad_draft_configs():
    bad_vocab = TransformerConfig(vocab_size=50, d_model=32, n_heads=2,
                                  n_layers=1, d_ff=64, max_seq_len=64)
    with pytest.raises(ValueError, match="vocab_size"):
        make_engine(draft=(DRAFT, bad_vocab))
    short = TransformerConfig(vocab_size=97, d_model=32, n_heads=2,
                              n_layers=1, d_ff=64, max_seq_len=16)
    with pytest.raises(ValueError, match="max_seq_len"):
        make_engine(draft=(DRAFT, short))
    with pytest.raises(ValueError, match="spec_tokens"):
        make_spec_engine(spec_tokens=0)


# ---------------------------------------------------------------------------
# Planner what-ifs (hvd-mem satellite)
# ---------------------------------------------------------------------------

def test_planner_draft_and_prefix_whatifs_match_runtime():
    """--draft-layers / --prefix-pages share the runtime byte
    formulas: the plan's serving.prefix_pages equals the cache's
    construction-time ledger partition exactly, serving.draft_kv the
    draft cache's charge, and serving.draft_params the actual
    init_transformer tree bytes."""
    from horovod_tpu.memory import ledger as led
    from horovod_tpu.memory import planner
    from horovod_tpu.serving.kv_cache import PagedKVCache

    led.ledger.reset()
    cache = PagedKVCache(2, 4, 16, max_slots=4, pages_per_slot=4,
                         page_size=8, prefix_cache=True, prefix_pages=6)
    got = led.ledger.bytes_by_category()
    assert cache.n_pages == 1 + 16 + 6  # trash + slots + prefix reserve
    plan = planner.plan_serving(
        n_layers=2, n_heads=4, head_dim=16, max_slots=4,
        pages_per_slot=4, page_size=8, prefix_pages=6, draft_layers=1,
        vocab_size=97)
    fw = plan.framework
    assert got["serving.kv_pages"] == fw["serving.kv_pages"]
    assert got["serving.prefix_pages"] == fw["serving.prefix_pages"]
    dcfg = TransformerConfig(vocab_size=97, d_model=64, n_heads=4,
                             n_layers=1, d_ff=256, max_seq_len=32)
    dp = init_transformer(jax.random.PRNGKey(0), dcfg)
    actual = sum(x.nbytes for x in jax.tree_util.tree_leaves(dp))
    assert fw["serving.draft_params"] == actual
    led.ledger.reset()


def test_planner_cli_accepts_spec_knobs():
    from horovod_tpu.memory.__main__ import main as mem_main

    rc = mem_main(["--plan", "--model", "serving", "--draft-layers",
                   "1", "--prefix-pages", "8"])
    assert rc == 0


def test_draft_ledger_categories_live_and_release():
    from horovod_tpu.memory import ledger as led

    led.ledger.reset()
    eng = make_spec_engine()
    got = led.ledger.bytes_by_category()
    assert got.get("serving.draft_kv", 0) > 0
    assert got.get("serving.draft_params", 0) > 0
    expected = sum(x.nbytes for x in
                   jax.tree_util.tree_leaves(eng._draft_params))
    assert got["serving.draft_params"] == expected
