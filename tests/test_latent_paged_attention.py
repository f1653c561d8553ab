"""The paged latent-attention kernel (ops/latent_paged_attention.py) in the
Pallas interpreter against ``mla_absorbed_attention`` over a gathered
view (what ``latent_moe.gathered_attend``, its twin off the TPU, does in
the decode program), and through the serving engine for the two latent families with the
kernel forced (``latent_moe.PAGED_INTERPRET``)."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import test_latent_moe as tl
import test_shortcut_moe as ts
from horovod_tpu.models import latent_moe as lm
from horovod_tpu.ops import latent_paged_attention as lpa
from horovod_tpu.serving import InferenceEngine

# 64 heads make a block of 512 tokens (32 pages of 16), so a slot of 80
# pages is two blocks and a half; the other widths are small.
CFG = lm.LatentMoEConfig(
    num_attention_heads=64, kv_lora_rank=128, qk_rope_head_dim=64,
    qk_nope_head_dim=16, v_head_dim=16, dtype=jnp.float32)
PAGE, PPS, LAYERS = 16, 80, 3
BLOCK = PAGE * lpa.block_pages(PAGE, PPS, 64, CFG.entry_width, 4)
# float32 operands on both sides: what differs is the order of float32
# sums (a block at a time, the new entry first).  A softmax whose scores
# were rounded to bfloat16 misses it by two decades
# (test_a_bfloat16_softmax_would_fail).
TOL = 2e-5


def case(lengths, seed=0, dtype=jnp.float32, nan_elsewhere=False,
         consecutive=False):
    """A store of ``LAYERS`` layers, a page table (permuted unless
    ``consecutive``), one query and one new entry a slot.  With
    ``nan_elsewhere`` every page no live slot owns under these lengths,
    page 0 among them, is NaN in every layer."""
    lengths = np.asarray(lengths, np.int32)
    slots = len(lengths)
    n_pages = slots * PPS + 1
    rng = np.random.RandomState(seed)
    ks = jax.random.split(jax.random.PRNGKey(seed), 5)
    h, w = CFG.num_attention_heads, CFG.entry_width
    store = jax.random.normal(ks[0], (LAYERS, n_pages, PAGE, w),
                              jnp.float32)
    pages = np.arange(1, n_pages)
    table = (pages if consecutive else rng.permutation(pages)).reshape(
        slots, PPS).astype(np.int32)
    if nan_elsewhere:
        owned = np.zeros(n_pages, bool)
        for s, n in enumerate(lengths):
            if n >= 0:
                owned[table[s, :-(-int(n) // PAGE)]] = True
        store = jnp.where(owned[None, :, None, None], store, jnp.nan)
    q_nope = jax.random.normal(ks[1], (slots, 1, h, CFG.qk_nope_head_dim))
    q_rope = jax.random.normal(ks[2], (slots, 1, h, CFG.qk_rope_head_dim))
    entry = jax.random.normal(ks[3], (slots, 1, w))
    ap = {"w_ukv": jax.random.normal(
        ks[4], (CFG.kv_lora_rank, h * (CFG.qk_nope_head_dim
                                       + CFG.v_head_dim))) * 0.1}
    cast = lambda x: x.astype(dtype)
    return dict(lengths=jnp.asarray(lengths), store=cast(store),
                table=jnp.asarray(table), q_nope=cast(q_nope),
                q_rope=cast(q_rope), entry=cast(entry),
                ap=jax.tree_util.tree_map(cast, ap))


def over_a_gathered_view(c, layer, softmax_dtype=None):
    """``mla_absorbed_attention`` over every slot's whole row of the
    table, the new entry set at its position: the ladder's arithmetic on
    one full rung.  Rows the mask hides are zeroed (a hidden NaN would
    reach the product as ``0 * NaN``)."""
    slots = c["lengths"].shape[0]
    view = c["store"][layer][c["table"]].reshape(slots, PPS * PAGE, -1)
    pos = jnp.clip(c["lengths"], 0, None)
    view = view.at[jnp.arange(slots), pos].set(c["entry"][:, 0])
    seen = jnp.arange(PPS * PAGE)[None, :] <= c["lengths"][:, None]
    view = jnp.where(seen[:, :, None], view, 0)
    if softmax_dtype is not None:
        # The same attention with its scores rounded on their way to the
        # softmax: what the tolerance has to catch.
        orig = lm._masked_softmax
        rounded = lambda s, m: orig(
            s.astype(softmax_dtype).astype(jnp.float32), m)
        lm._masked_softmax = rounded
    try:
        # Jitted: XLA's CPU client has no eager bfloat16 dot.
        return jax.jit(lambda: lm.mla_absorbed_attention(
            c["q_nope"], c["q_rope"], view, c["lengths"][:, None], c["ap"],
            CFG)[:, 0])()
    finally:
        if softmax_dtype is not None:
            lm._masked_softmax = orig


def through_the_kernel(c, layer):
    def f(c):
        attend = lm.paged_attend(c["lengths"], c["store"], c["table"], CFG,
                                 True)
        return attend(layer, c["q_nope"], c["q_rope"], c["entry"],
                      c["ap"])[:, 0]

    return jax.jit(f)(c)


def gap(a, b):
    return float(jnp.max(jnp.abs(a.astype(jnp.float32)
                                 - b.astype(jnp.float32))))


# Ragged lengths with idle slots between the live ones; lengths at, one
# under and one over a page edge; the same at a block edge and at two
# blocks; a slot that holds nothing but its new token; a full slot.
@pytest.mark.parametrize("lengths", [
    (300, -1, 37, -1, -1, 600, 5),
    (PAGE - 1, PAGE, PAGE + 1, 2 * PAGE),
    (BLOCK - 1, BLOCK, BLOCK + 1, -1, 2 * BLOCK - 1, 2 * BLOCK,
     2 * BLOCK + 1),
    (0, -1, 1, 0),
    (PAGE * PPS - 1, -1, 3)])
def test_kernel_equals_attention_over_a_gathered_view(lengths):
    c = case(lengths, seed=len(lengths))
    got, want = through_the_kernel(c, 1), over_a_gathered_view(c, 1)
    on = np.asarray(lengths) >= 0
    assert gap(got[on], want[on]) < TOL
    # Idle slots: exact zeros, out of the kernel and out of the twin.
    assert not np.asarray(got)[~on].any()
    assert not np.asarray(want)[~on].any()


def test_a_bfloat16_softmax_would_fail():
    c = case((300, 37, 600, 5), seed=3)
    want = over_a_gathered_view(c, 1)
    assert gap(through_the_kernel(c, 1), want) < TOL
    assert gap(over_a_gathered_view(c, 1, jnp.bfloat16), want) > 100 * TOL


def test_bfloat16_entries_round_where_the_twin_rounds():
    """The served type: probabilities and the attended latent are rounded
    to bfloat16 as the twin rounds them, one unit in the last place
    apart at most."""
    c = case((300, -1, 37, 600, BLOCK), seed=4, dtype=jnp.bfloat16)
    got, want = through_the_kernel(c, 2), over_a_gathered_view(c, 2)
    assert got.dtype == jnp.bfloat16
    scale = float(jnp.max(jnp.abs(want.astype(jnp.float32))))
    assert gap(got, want) <= scale * 2 ** -7


def test_a_consecutive_table_and_a_permuted_one_read_the_same_entries():
    """The same entries behind another table: the kernel follows the
    table, not the page order."""
    lengths = (70, -1, 2 * BLOCK + 9, 33)
    a = case(lengths, seed=5, consecutive=True)
    b = dict(a)
    perm = np.random.RandomState(5).permutation(a["store"].shape[1])
    inverse = np.argsort(perm)
    b["store"] = a["store"][:, perm]           # page p of b is perm[p] of a
    b["table"] = jnp.asarray(inverse)[a["table"]]
    assert not np.array_equal(np.diff(np.asarray(b["table"])[0]),
                              np.ones(PPS - 1))
    assert gap(through_the_kernel(a, 0), through_the_kernel(b, 0)) == 0.0
    assert gap(through_the_kernel(a, 0), over_a_gathered_view(a, 0)) < TOL


def test_a_traced_layer_reads_only_the_live_slots_own_pages():
    """The whole store and a ``layer`` under ``lax.scan``; every page no
    live slot owns (other slots', idle slots', the unmapped rest of a live
    slot's row, page 0) is NaN in every layer, and nothing of it
    arrives."""
    lengths = (100, -1, BLOCK + 3, -1, 17)
    c = case(lengths, seed=6, nan_elsewhere=True)
    assert bool(jnp.isnan(c["store"]).any())
    attend = lm.paged_attend(c["lengths"], c["store"], c["table"], CFG,
                             True)

    def one(carry, layer):
        return carry, attend(layer, c["q_nope"], c["q_rope"], c["entry"],
                             c["ap"])[:, 0]

    _, got = jax.jit(lambda: jax.lax.scan(one, 0, jnp.arange(LAYERS)))()
    assert bool(jnp.isfinite(got).all())
    for layer in range(LAYERS):
        assert gap(got[layer], over_a_gathered_view(c, layer)) < TOL
    assert gap(got[0], got[1]) > 0.1           # the layers differ


def test_nobody_alive_is_all_zeros():
    c = case((-1, -1, -1), seed=7, nan_elsewhere=True)
    got = through_the_kernel(c, 0)
    assert got.shape == (3, 64 * CFG.v_head_dim)
    assert not np.asarray(got).any()


def test_the_new_entry_is_attended_though_the_store_lacks_it():
    """A slot that caches nothing attends its new entry alone (weight 1:
    the output is that entry's latent through ``W_uv``); with entries
    cached, another new entry moves the output and the twin agrees."""
    c = case((0, 40), seed=8)
    got = through_the_kernel(c, 0)
    alone = lm._absorbed_output(
        jnp.broadcast_to(c["entry"][:, None, :, :CFG.kv_lora_rank],
                         (2, 1, 64, CFG.kv_lora_rank)), c["ap"], CFG)[:, 0]
    assert gap(got[0], alone[0]) < TOL
    other = dict(c, entry=c["entry"] + 1.0)
    moved = through_the_kernel(other, 0)
    assert gap(moved[1], got[1]) > 1e-3
    assert gap(moved[1], over_a_gathered_view(other, 0)[1]) < TOL


def test_live_first_needs_no_sort():
    lengths = jnp.asarray([-1, 5, 0, -1, -1, 9, -1, 2], jnp.int32)
    order, n = lpa.live_first(lengths)
    assert np.asarray(order).tolist() == [1, 2, 5, 7, 0, 3, 4, 6]
    assert np.asarray(n).tolist() == [4]
    text = str(jax.make_jaxpr(lpa.live_first)(lengths))
    assert "sort" not in text and "scatter" not in text


def test_tokens_read_rounds_live_lengths_up_to_the_page():
    assert lpa.tokens_read([-1, 0, 1, 16, 17, -1, 600], 16) == (
        0 + 16 + 16 + 32 + 608)
    assert lpa.tokens_read([-1, -1], 16) == 0
    # From the shapes alone: the two cells' block is 32 pages.
    assert lpa.block_pages(16, 128, 64, 640, 2) == 32
    assert lpa.block_pages(16, 256, 64, 640, 2) == 32
    assert lpa.block_pages(8, 32, 4, 128, 4) == 32     # a slot's all


# -- through the model and the serving engine ---------------------------------

def _primitives(jaxpr, found=None):
    """Names of the primitives of a program, through its sub-programs but
    not into a ``pallas_call``'s kernel."""
    found = set() if found is None else found
    for eqn in jaxpr.eqns:
        found.add(eqn.primitive.name)
        if eqn.primitive.name != "pallas_call":
            for sub in jax.core.jaxprs_in_params(eqn.params):
                _primitives(sub, found)
    return found


def _program(monkeypatch, interpret):
    monkeypatch.setattr(lm, "PAGED_INTERPRET", interpret)
    c = case((40, -1, 7, 90), seed=9)

    def f(lengths, store, table, q_nope, q_rope, entry):
        attend, pos = lm.decode_attend(lengths, store, table, CFG)
        return attend(1, q_nope, q_rope, entry, c["ap"])[:, 0], pos

    args = [c[k] for k in ("lengths", "store", "table", "q_nope", "q_rope",
                           "entry")]
    return (_primitives(jax.make_jaxpr(f)(*args).jaxpr),
            jax.jit(f)(*args)[0])


def test_off_the_tpu_the_plain_twin_runs_unless_the_interpreter_is_asked_for(
        monkeypatch):
    """The rule is the backend's (``ops/flash_attention.kernel_runs``): on
    the CPU the decode program gathers every slot's table row whole and
    attends it under the lengths, no conditional, no sort, no loop; with
    the kernel forced the gather is gone too, and both give the same
    attention."""
    assert jax.default_backend() == "cpu" and not lm.paged_kernel_runs()
    plain, want = _program(monkeypatch, None)
    assert "gather" in plain
    assert not plain & {"pallas_call", "cond", "sort", "while"}
    kernel, got = _program(monkeypatch, True)
    assert lm.paged_kernel_runs()
    assert "pallas_call" in kernel
    assert not kernel & {"cond", "sort", "while", "gather", "scatter"}
    assert gap(got, want) < TOL
    # Neither asks the cache manager for a store beside the pages.
    assert "slot_stores" not in lm.LatentMoEServing(CFG).cache_entry()


def _engine(monkeypatch, cfg, params):
    monkeypatch.setattr(lm, "PAGED_INTERPRET", True)
    eng = InferenceEngine(params, cfg, max_slots=8, page_size=8,
                          capacity=256)
    eng.warm_start()
    return eng


@pytest.mark.parametrize("family", ["latent", "shortcut"])
def test_prefill_then_decode_through_the_kernel_equals_the_reference(
        monkeypatch, family):
    """The engine's own executables with the kernel in the decode program
    (interpreted): logits against the non-incremental float32 reference
    within the families' tolerance, and ``serving.decode_view_tokens``
    counting what the kernel copied: each live length, the token in
    flight not yet cached, rounded up to the page, over the slots."""
    t = tl if family == "latent" else ts
    eng = _engine(monkeypatch, t.CFG, t.params())
    assert isinstance(eng.model, lm.LatentMoEServing)
    lengths, new = (70, 9, 140, 30), (6, 3, 5, 4)
    prompts = [t.prompt(500 + n, n) for n in lengths]
    views = t.counter("serving.decode_view_tokens")
    got = t.rollout(eng, prompts, new)
    seqs = [p + toks for p, (_, toks) in zip(prompts, got)]
    want = t.REF.served_logits(t.MODEL, t.params(), seqs, "f32")
    for p, n, (rows, toks), ref in zip(prompts, new, got, want):
        assert len(toks) == n and rows.shape[0] == n
        assert np.abs(rows - ref[len(p) - 1:len(p) - 1 + n]).max() < t.TOL
    # Iteration i (0-based) attends the slots with more than i + 1 tokens
    # to give, each at its prompt's length plus i cached entries.
    read = sum(
        lpa.tokens_read([n + i if i + 1 < k else -1
                         for n, k in zip(lengths, new)], 8)
        for i in range(max(new) - 1))
    assert t.counter("serving.decode_view_tokens") - views == read / 8
    assert eng.cache.free_pages() == eng.cache.total_pages


# -- the counter's reader (benchmark/metrics/latent_view_tokens.py) -----------

class _Run:
    def __init__(self, before, after):
        self.before, self.after = before, after

    def counter_delta(self, name, field="value"):
        return (self.after.get(name, {}).get(field, 0)
                - self.before.get(name, {}).get(field, 0))


def test_latent_view_tokens_is_declared_for_the_two_latent_cells():
    from benchmark import cells

    bench = cells.load_benchmark()
    mod = cells.load_module("metrics", "latent_view_tokens")
    (entry,) = [m for m in bench["per_layer"]
                if m["name"] == "latent_view_tokens"]
    assert entry == {
        "name": "latent_view_tokens", "unit": mod.UNIT, "better": mod.BETTER,
        "source": mod.SOURCE, "layer": mod.LAYER, "moves": mod.MOVES,
        "workloads": ["axk1-serve-decode", "longcat-serve-turns"]}
    assert (mod.UNIT, mod.BETTER, mod.SOURCE, mod.LAYER, mod.MOVES) == (
        "tokens", "lower", "program_counter", "latent attention",
        "tpot_p90_ms")
    for cell in (w["name"] for w in bench["workloads"]):
        listed = [m["name"] for m in cells.resolve(bench, cell)["per_layer"]]
        assert ("latent_view_tokens" in listed) == (
            cell in ("axk1-serve-decode", "longcat-serve-turns"))


@pytest.mark.parametrize("before,after,want", [
    # 500 iterations: 300 that copied 222 tokens a slot, 200 that copied 240.
    ({"serving.decode_view_tokens": {"value": 1280.0},
      "serving.decode_iterations": {"value": 5}},
     {"serving.decode_view_tokens": {"value": 1280.0 + 300 * 222 + 200 * 240},
      "serving.decode_iterations": {"value": 505}}, 229.2),
    # No decode in the window; a program without the counter; no serving.
    ({"serving.decode_view_tokens": {"value": 640},
      "serving.decode_iterations": {"value": 5}},
     {"serving.decode_view_tokens": {"value": 640},
      "serving.decode_iterations": {"value": 5}}, None),
    ({"serving.decode_iterations": {"value": 5}},
     {"serving.decode_iterations": {"value": 55}}, None),
    ({}, {}, None)])
def test_latent_view_tokens_is_the_view_counter_over_the_iterations(
        before, after, want):
    from benchmark import cells

    got = cells.load_module("metrics", "latent_view_tokens").read(
        _Run(before, after))
    assert got == (want if want is None else pytest.approx(want))
