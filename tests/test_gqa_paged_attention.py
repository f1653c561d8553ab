"""The paged grouped-query kernel (ops/gqa_paged_attention.py) in the Pallas
interpreter against its twin, ``mamba2_hybrid.attend_view`` (``afmoe``'s
too) over every slot's table row gathered whole under ``attended_rows``
(what ``afmoe.gathered_attend`` does in the decode program): a table that
holds every page (the full group), a ring that has not wrapped, one that
has wrapped once and several times.  Then the same kernel under another head map, differential attention's
(``models/hybrid_ssm.py``), against ``attend_view`` over the positions
themselves, gathered one by one.  Last, kernel and twin at
``granite-4.0-h-micro``'s head layout against the softmax written out in
float64."""

import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from horovod_tpu.models import afmoe as af
from horovod_tpu.models import hybrid_ssm as hs
from horovod_tpu.models import mamba2_hybrid as mh
from horovod_tpu.ops import gqa_paged_attention as gpa

# 32 query heads on 4 key/value heads make a block of 1024 tokens (64 pages
# of 16), so a slot of 80 pages is a block and a quarter and a ring of 129
# entries two blocks and one page; the heads are 16 wide.
CFG = af.AfmoeConfig(
    num_attention_heads=32, num_key_value_heads=4, head_dim=16,
    hidden_size=64, num_hidden_layers=2, num_dense_layers=1,
    layer_types=(af.SLIDING, af.FULL), dtype=jnp.float32)
PAGE, PPS, LAYERS, SLOTS = 16, 80, 3, 8
BLOCK = PAGE * gpa.block_pages(PAGE, PPS, 32, CFG.kv_width, 4)
# The issue's small ring: window 48 in pages of 16 is 4 entries, one block.
WINDOW, RING = 48, af.ring_entries(48, PAGE)
# A ring longer than two blocks: 129 entries.
WIDE, WIDE_RING = 2048, af.ring_entries(2048, PAGE)
# float32 operands on both sides: what differs is the order of float32
# sums (a block at a time, the new key first).  A softmax whose scores
# were rounded to bfloat16 misses it by two decades
# (test_a_bfloat16_softmax_would_fail).
TOL = 2e-5


def case(lengths, entries, seed=0, dtype=jnp.float32, nan_elsewhere=False,
         consecutive=False, kw=CFG.kv_width, qw=CFG.q_width):
    """Two stores of ``LAYERS`` layers, a table of ``entries`` a slot
    (permuted unless ``consecutive``), one query and one new key and value
    a slot; ``SLOTS`` slots, those past ``lengths`` idle (one shape a
    table: the tests share its compiled programs).  With ``nan_elsewhere``
    every page that holds no entry in use of a live slot, page 0 among
    them, is NaN in every layer."""
    lengths = np.asarray(tuple(lengths) + (-1,) * (SLOTS - len(lengths)),
                         np.int32)
    slots = SLOTS
    n_pages = slots * entries + 1
    rng = np.random.RandomState(seed)
    ks = jax.random.split(jax.random.PRNGKey(seed), 5)
    k_pages = jax.random.normal(ks[0], (LAYERS, n_pages, PAGE, kw))
    v_pages = jax.random.normal(ks[1], (LAYERS, n_pages, PAGE, kw))
    pages = np.arange(1, n_pages)
    table = (pages if consecutive else rng.permutation(pages)).reshape(
        slots, entries).astype(np.int32)
    if nan_elsewhere:
        owned = np.zeros(n_pages, bool)
        for s, n in enumerate(lengths):
            if n >= 0:
                owned[table[s, :int(gpa.mapped_entries(n, entries, PAGE))]
                      ] = True
        k_pages, v_pages = (jnp.where(owned[None, :, None, None], x, jnp.nan)
                            for x in (k_pages, v_pages))
    cast = lambda x: x.astype(dtype)
    return dict(lengths=jnp.asarray(lengths), table=jnp.asarray(table),
                k_pages=cast(k_pages), v_pages=cast(v_pages),
                q=cast(jax.random.normal(ks[2], (slots, qw))),
                k_self=cast(jax.random.normal(ks[3], (slots, kw))),
                v_self=cast(jax.random.normal(ks[4], (slots, kw))))


@functools.lru_cache(maxsize=None)
def _twin(window, softmax_dtype):
    def f(c, layer):
        k, v, mask = gpa.gathered_rows(
            jnp.clip(c["lengths"], 0, None), c["table"], c["k_pages"],
            c["v_pages"], layer, window)
        k, v = (jnp.where(mask[..., None], x, 0) for x in (k, v))
        o = af.attend_view(c["q"], c["k_self"], c["v_self"], k, v, mask, CFG)
        return jnp.where(c["lengths"][:, None] >= 0, o, 0)

    # Jitted: XLA's CPU client has no eager bfloat16 dot.
    return jax.jit(f)


def over_a_gathered_view(c, layer, window=0, softmax_dtype=None):
    """``attend_view`` over every slot's table row, gathered whole in
    table order, under the kernel's own mask as an array: the decode
    program's twin off the TPU.  Rows the mask hides are zeroed (a hidden
    NaN would reach the product as ``0 * NaN``); an idle slot's row is its
    new value alone, which the decode program never reads: zeroed as the
    kernel writes it."""
    if softmax_dtype is None:
        return _twin(window, None)(c, layer)
    # The same attention with its scores rounded on their way to the
    # softmax: what the tolerance has to catch.
    orig = mh._masked_exp
    mh._masked_exp = lambda s, mask, m: orig(
        s.astype(softmax_dtype).astype(jnp.float32), mask,
        m.astype(softmax_dtype).astype(jnp.float32))
    try:
        return _twin(window, softmax_dtype)(c, layer)
    finally:
        mh._masked_exp = orig


@functools.lru_cache(maxsize=None)
def _kernel(window):
    return jax.jit(lambda c, layer: gpa.gqa_paged_attention(
        c["q"], c["k_self"], c["v_self"], c["k_pages"], c["v_pages"],
        c["table"], c["lengths"], layer, heads=CFG.num_attention_heads,
        scale=CFG.attention_multiplier, window=window, interpret=True))


def through_the_kernel(c, layer, window=0):
    return _kernel(window)(c, layer)


def gap(a, b):
    return float(jnp.max(jnp.abs(a.astype(jnp.float32)
                                 - b.astype(jnp.float32))))


# The full group (a table of every page, no window): ragged lengths with
# idle slots between the live ones; at, one under and one over a page edge
# and a block edge; slots that hold nothing but their new token; a full
# slot.  The small ring (4 entries, window 48) before it wraps (up to 64
# positions: lengths at 0, at a page's edge, at exactly the window and one
# past it), wrapped once (65..128) and several times.  The ring of 129
# entries, two blocks and a page: unwrapped, exactly full, wrapped.
@pytest.mark.parametrize("entries,window,lengths", [
    (PPS, 0, (300, -1, 37, -1, -1, 600, 5)),
    (PPS, 0, (0, PAGE - 1, PAGE, PAGE + 1, -1, 2 * PAGE, 1, 0)),
    (PPS, 0, (BLOCK - 1, BLOCK, BLOCK + 1, -1, BLOCK + PAGE,
              PAGE * PPS - 1)),
    (RING, WINDOW, (0, 1, PAGE - 1, PAGE, PAGE + 1, -1, WINDOW - 1, WINDOW)),
    (RING, WINDOW, (WINDOW + 1, 63, 64, -1, 33, 2 * PAGE)),
    (RING, WINDOW, (65, 66, 80, 81, -1, 96, 127, 128)),
    (RING, WINDOW, (129, 200, 500, -1, 1000, 4097, 64 * 7, 64 * 7 + 1)),
    (WIDE_RING, WIDE, (700, -1, WIDE, WIDE + 1, WIDE + PAGE, 3)),
    (WIDE_RING, WIDE, (WIDE + PAGE + 1, 2100, -1, 5555, 129 * 16 * 3))])
def test_kernel_equals_attention_over_a_gathered_view(entries, window,
                                                      lengths):
    c = case(lengths, entries, seed=len(lengths) + entries)
    got = through_the_kernel(c, 1, window)
    want = over_a_gathered_view(c, 1, window)
    on = np.asarray(c["lengths"]) >= 0
    assert gap(got[on], want[on]) < TOL
    # Idle slots: exact zeros.
    assert not np.asarray(got)[~on].any()


@pytest.mark.parametrize("entries,window,lengths", [
    (PPS, 0, (300, 37, 600, 5)), (RING, WINDOW, (300, 37, 600, 50))])
def test_a_bfloat16_softmax_would_fail(entries, window, lengths):
    c = case(lengths, entries, seed=3)
    want = over_a_gathered_view(c, 1, window)
    assert gap(through_the_kernel(c, 1, window), want) < TOL
    assert gap(over_a_gathered_view(c, 1, window, jnp.bfloat16),
               want) > 100 * TOL


@pytest.mark.parametrize("entries,window,lengths", [
    (PPS, 0, (300, -1, 37, 600, BLOCK + 5)),
    (RING, WINDOW, (30, -1, 64, 65, 700))])
def test_bfloat16_stores_round_where_the_twin_rounds(entries, window,
                                                     lengths):
    """The served type: probabilities and the attended values are rounded
    to bfloat16 as the twin rounds them, one unit in the last place apart
    at most."""
    c = case(lengths, entries, seed=4, dtype=jnp.bfloat16)
    got = through_the_kernel(c, 2, window)
    want = over_a_gathered_view(c, 2, window)
    assert got.dtype == jnp.bfloat16
    scale = float(jnp.max(jnp.abs(want.astype(jnp.float32))))
    assert gap(got, want) <= scale * 2 ** -7


@pytest.mark.parametrize("entries,window,lengths", [
    (PPS, 0, (70, -1, BLOCK + 9, 33)),
    (RING, WINDOW, (70, -1, 20, 333))])
def test_a_consecutive_table_and_a_permuted_one_read_the_same_rows(
        entries, window, lengths):
    """The same rows behind another table: the kernel follows the table,
    not the page order."""
    a = case(lengths, entries, seed=5, consecutive=True)
    b = dict(a)
    perm = np.random.RandomState(5).permutation(a["k_pages"].shape[1])
    inverse = np.argsort(perm)
    b["k_pages"] = a["k_pages"][:, perm]       # page p of b is perm[p] of a
    b["v_pages"] = a["v_pages"][:, perm]
    b["table"] = jnp.asarray(inverse)[a["table"]]
    assert not np.array_equal(np.diff(np.asarray(b["table"])[0]),
                              np.ones(entries - 1))
    got = through_the_kernel(a, 0, window)
    assert gap(got, through_the_kernel(b, 0, window)) == 0.0
    assert gap(got, over_a_gathered_view(a, 0, window)) < TOL


@pytest.mark.parametrize("entries,window,lengths", [
    (PPS, 0, (100, -1, BLOCK + 3, -1, 17)),
    (RING, WINDOW, (40, -1, 64, -1, 777)),
    (WIDE_RING, WIDE, (100, -1, WIDE + 40, -1, 0))])
def test_a_traced_layer_reads_only_the_live_slots_entries_in_use(
        entries, window, lengths):
    """The whole stores and a ``layer`` under ``lax.scan``; every page that
    is no entry in use of a live slot (other slots', idle slots', the
    unmapped rest of a live slot's row, page 0) is NaN in every layer, and
    nothing of it arrives."""
    c = case(lengths, entries, seed=6, nan_elsewhere=True)
    assert bool(jnp.isnan(c["k_pages"]).any())
    order, n_live = gpa.live_first(c["lengths"])

    def one(carry, layer):
        return carry, gpa.gqa_paged_attention(
            c["q"], c["k_self"], c["v_self"], c["k_pages"], c["v_pages"],
            c["table"], c["lengths"], layer, heads=CFG.num_attention_heads,
            scale=CFG.attention_multiplier, window=window, order=order,
            n_live=n_live, interpret=True)

    _, got = jax.jit(lambda: jax.lax.scan(one, 0, jnp.arange(LAYERS)))()
    assert bool(jnp.isfinite(got).all())
    for layer in range(LAYERS):
        assert gap(got[layer], over_a_gathered_view(c, layer, window)) < TOL
    assert gap(got[0], got[1]) > 0.1           # the layers differ


@pytest.mark.parametrize("entries,window", [(PPS, 0), (RING, WINDOW)])
def test_nobody_alive_is_all_zeros(entries, window):
    c = case((-1, -1, -1), entries, seed=7, nan_elsewhere=True)
    got = through_the_kernel(c, 0, window)
    assert got.shape == (SLOTS, CFG.q_width)
    assert not np.asarray(got).any()


@pytest.mark.parametrize("entries,window", [(PPS, 0), (RING, WINDOW)])
def test_the_new_key_and_value_are_attended_though_the_store_lacks_them(
        entries, window):
    """A slot that caches nothing attends its new key alone (weight 1: the
    output is the new value, each query head its key/value head's); with
    rows cached, another new value moves the output and the twin
    agrees."""
    c = case((0, 40), entries, seed=8)
    got = through_the_kernel(c, 0, window)
    rep = CFG.num_attention_heads // CFG.num_key_value_heads
    alone = jnp.repeat(c["v_self"].reshape(SLOTS, -1, CFG.head_dim), rep,
                       axis=1).reshape(SLOTS, -1)
    assert gap(got[0], alone[0]) < TOL
    other = dict(c, v_self=c["v_self"] + 1.0, k_self=c["k_self"] * 2.0)
    moved = through_the_kernel(other, 0, window)
    assert gap(moved[1], got[1]) > 1e-3
    assert gap(moved[1], over_a_gathered_view(other, 0, window)[1]) < TOL


def test_the_window_hides_rows_the_ring_still_holds():
    """A ring holds a page more than the window: with the window the rows
    at ``cached - window`` and before are out; the same ring read without
    one attends them, and differs."""
    c = case((64, 100, 40), RING, seed=9)
    with_window = through_the_kernel(c, 0, WINDOW)
    without = through_the_kernel(c, 0, 0)
    assert gap(with_window[:2], without[:2]) > 1e-3
    # 40 positions: all of them within 48 of the new token.
    assert gap(with_window[2], without[2]) == 0.0
    assert gap(without, over_a_gathered_view(c, 0, 0)) < TOL


def test_the_block_and_the_copies_follow_from_the_shapes():
    # The cell's two groups (48 heads on 8 of 128, bfloat16, page 16): 32
    # pages, 512 tokens: the blocks' 4 MiB bind (the scores' tile would
    # allow 682).
    assert gpa.block_pages(16, 576, 48, 1024, 2) == 32
    assert gpa.block_pages(16, 257, 48, 1024, 2) == 32
    assert gpa.block_pages(16, RING, 32, 64, 4) == RING       # a row's all
    assert BLOCK == 1024                                      # the scores
    assert gpa.block_pages(16, 80, 8, 4096, 2) == 8           # the blocks
    # What is copied: the entries in use, whole pages; of a ring no more
    # than the ring; idle slots nothing.
    lengths = [-1, 0, 1, 16, 17, -1, 600, 4096, 5000]
    assert gpa.tokens_read(lengths, 576, 16) == (
        0 + 16 + 16 + 32 + 608 + 4096 + 5008)
    assert gpa.tokens_read(lengths, 257, 16) == (
        0 + 16 + 16 + 32 + 608 + 4096 + 257 * 16)
    assert gpa.tokens_read([-1, -1], 257, 16) == 0
    assert gpa.mapped_entries(np.asarray([0, 1, 16, 17, 5000]), 257,
                              16).tolist() == [0, 1, 1, 2, 257]


# -- the head map: differential attention through the same kernel -------------

# 16 query heads on 8 key heads of 8 (4 value pairs of 16), window 512 in
# pages of 16: the cell's ring of 33 entries, a table of 80 for the full
# group.  float32, so what differs is the order of float32 sums.
DIFF = hs.HybridSSMConfig(
    vocab_size=64, hidden_size=128, intermediate_size=64,
    num_hidden_layers=4, num_attention_heads=16, num_key_value_heads=8,
    sliding_window=512, d_state=4, dt_rank=4, dtype=jnp.float32)
DIFF_RING = af.ring_entries(DIFF.sliding_window, PAGE)
DIFF_LAYER = 3


def diff_case(lengths, entries, seed, **kw):
    hd = DIFF.head_dim
    c = case(lengths, entries, seed=seed, kw=DIFF.kv_width,
             qw=DIFF.num_attention_heads * hd, **kw)
    ks = jax.random.split(jax.random.PRNGKey(100 + seed), 5)
    ap = {n: 0.3 * jax.random.normal(k, (hd,)) for n, k in zip(
        ("lambda_q1", "lambda_k1", "lambda_q2", "lambda_k2"), ks)}
    ap["subln"] = 1.0 + 0.1 * jax.random.normal(ks[4], (2 * hd,))
    return c, ap


@functools.lru_cache(maxsize=None)
def _diff(which, window):
    def f(c, ap, layer):
        groups = {"g": (c["table"], window, c["k_pages"], c["v_pages"])}
        attend = (hs.paged_attend(c["lengths"], groups, DIFF, interpret=True)
                  if which == "kernel" else
                  hs.gathered_attend(c["lengths"], groups, DIFF))
        return attend("g", layer, DIFF_LAYER, ap, c["q"], c["k_self"],
                      c["v_self"])
    return jax.jit(f)


def over_the_positions(c, ap, layer, window):
    """``attend_view`` over the cached positions a slot attends, gathered
    ONE BY ONE from where the ring's rule puts them (logical page ``j`` in
    entry ``j mod entries``): no table order, no mask but the padding."""
    lengths = np.asarray(c["lengths"])
    table = np.asarray(c["table"])
    entries = table.shape[1]
    n = max(1, int(lengths.max()))
    page = np.zeros((SLOTS, n), np.int32)
    row = np.zeros((SLOTS, n), np.int32)
    mask = np.zeros((SLOTS, n), bool)
    for s, cached in enumerate(lengths):
        lo = max(0, cached - window + 1) if window else 0
        for i, pos in enumerate(range(lo, max(cached, 0))):
            page[s, i] = table[s, (pos // PAGE) % entries]
            row[s, i] = pos % PAGE
            mask[s, i] = True
    k_view, v_view = (x[layer][page, row] for x in (c["k_pages"],
                                                   c["v_pages"]))
    return jax.jit(hs.attend_view, static_argnums=(7, 8))(
        c["q"], c["k_self"], c["v_self"], k_view, v_view, jnp.asarray(mask),
        ap, DIFF_LAYER, DIFF)


# Both sides of the ring's wrap (33 entries of 16: 528 positions), and of
# the window: one under it, at it, one past it, at the ring's whole length,
# two rings and more; idle slots among them.
@pytest.mark.parametrize("entries,window", [(PPS, 0),
                                            (DIFF_RING, DIFF.sliding_window)])
@pytest.mark.parametrize("lengths", [
    (511, -1, 512, 513, -1, 528, 1100), (0, 1, 529, -1, 16, 1056, 1057)])
def test_the_differential_head_map_equals_attention_over_the_positions(
        entries, window, lengths):
    c, ap = diff_case(lengths, entries, seed=len(lengths) + entries)
    got = _diff("kernel", window)(c, ap, 1)
    want = over_the_positions(c, ap, 1, window)
    on = np.asarray(c["lengths"]) >= 0
    assert got.shape == (SLOTS, DIFF.num_attention_heads * DIFF.head_dim)
    assert gap(got[on], want[on]) < TOL
    # The twin off the TPU, a table row gathered whole under the kernel's
    # mask, is the same attention.
    twin = _diff("twin", window)(c, ap, 1)
    assert gap(twin[on], want[on]) < TOL
    # Idle slots: the kernel's exact zeros stay zeros through the pairs'
    # subtraction and norm.
    assert not np.asarray(got)[~on].any()


@pytest.mark.parametrize("entries,window", [(PPS, 0),
                                            (DIFF_RING, DIFF.sliding_window)])
def test_the_differential_map_under_a_traced_layer(entries, window):
    """The whole stores and a ``layer`` under ``lax.scan``, every page that
    is no entry in use of a live slot NaN in every layer."""
    c, ap = diff_case((600, -1, 513, -1, 40), entries, seed=11,
                      nan_elsewhere=True)
    assert bool(jnp.isnan(c["k_pages"]).any())
    groups = {"g": (c["table"], window, c["k_pages"], c["v_pages"])}

    def every_layer():
        attend = hs.paged_attend(c["lengths"], groups, DIFF, interpret=True)
        return jax.lax.scan(
            lambda carry, layer: (carry, attend(
                "g", layer, DIFF_LAYER, ap, c["q"], c["k_self"],
                c["v_self"])), 0, jnp.arange(LAYERS))[1]

    got = jax.jit(every_layer)()
    assert bool(jnp.isfinite(got).all())
    clean = dict(c, k_pages=jnp.nan_to_num(c["k_pages"]),
                 v_pages=jnp.nan_to_num(c["v_pages"]))
    on = np.asarray(c["lengths"]) >= 0
    for layer in range(LAYERS):
        want = over_the_positions(clean, ap, layer, window)
        assert gap(got[layer][on], want[on]) < TOL
    assert gap(got[0], got[1]) > 0.1           # the layers differ


def test_a_pair_reads_its_two_keys_and_keeps_its_double_width_value():
    """Straight from the equations, one slot, one cached row a head can
    tell apart: query head ``2 i + c`` of a pair scores key head ``2 j +
    c`` (``j`` its key/value pair) and both heads of the pair weigh the
    SAME double-width value ``[v_2j | v_2j+1]``."""
    cfg = DIFF
    assert hs.key_head_of(cfg).tolist() == [0, 1, 0, 1, 2, 3, 2, 3,
                                            4, 5, 4, 5, 6, 7, 6, 7]
    c, _ = diff_case((3,), DIFF_RING, seed=12)
    o = gpa.gqa_paged_attention(
        c["q"], c["k_self"], c["v_self"], c["k_pages"], c["v_pages"],
        c["table"], c["lengths"], 0, heads=cfg.num_attention_heads,
        scale=cfg.head_dim ** -0.5, window=cfg.sliding_window,
        key_head=hs.key_head_of(cfg),
        value_heads=cfg.num_key_value_heads // 2, interpret=True)
    h_n, hd = cfg.num_attention_heads, cfg.head_dim
    o = np.asarray(o).reshape(SLOTS, h_n, 2 * hd)
    table = np.asarray(c["table"])
    k = np.concatenate([np.asarray(c["k_pages"])[0, table[0, 0], :3],
                        np.asarray(c["k_self"])[:1]]).reshape(4, -1, hd)
    v = np.concatenate([np.asarray(c["v_pages"])[0, table[0, 0], :3],
                        np.asarray(c["v_self"])[:1]]).reshape(4, -1, 2 * hd)
    q = np.asarray(c["q"])[0].reshape(h_n, hd)
    for h in range(h_n):
        j = h // 4                               # 4 query heads a kv pair
        s = k[:, 2 * j + h % 2] @ q[h] * hd ** -0.5
        p = np.exp(s - s.max())
        assert np.abs(o[0, h] - (p / p.sum()) @ v[:, j]).max() < TOL


def test_the_default_head_map_is_bit_for_bit_the_grouped_one():
    """No map given: the queries laid by ``i // (heads / kv_heads)`` and a
    head keeping its own key/value head's lanes, as the wrapper always did
    (here laid by hand and handed to the kernel's own entry); the same map
    handed in by name changes nothing either."""
    c = case((300, -1, 37, 600, 5), PPS, seed=13)
    heads, kv_heads, hd = (CFG.num_attention_heads, CFG.num_key_value_heads,
                           CFG.head_dim)
    of_head = jnp.asarray(
        (np.arange(heads) // (heads // kv_heads))[:, None]
        == np.arange(kv_heads)[None, :], c["q"].dtype)
    laid = jnp.einsum("bhd,hg->bhgd", c["q"].reshape(SLOTS, heads, hd),
                      of_head).reshape(SLOTS, heads, kv_heads * hd)
    order, n_live = gpa.live_first(c["lengths"])
    by_hand = gpa._pallas_attend(
        laid, c["k_self"], c["v_self"], c["k_pages"], c["v_pages"],
        c["table"], c["lengths"], 1, order, n_live, kv_heads,
        float(CFG.attention_multiplier), 0, True).reshape(SLOTS, -1)
    default = through_the_kernel(c, 1)
    named = gpa.gqa_paged_attention(
        c["q"], c["k_self"], c["v_self"], c["k_pages"], c["v_pages"],
        c["table"], c["lengths"], 1, heads=heads,
        scale=CFG.attention_multiplier,
        key_head=np.arange(heads) // (heads // kv_heads),
        value_heads=kv_heads, out_dtype=c["q"].dtype, interpret=True)
    assert default.dtype == by_hand.dtype
    assert np.array_equal(np.asarray(default), np.asarray(by_hand))
    assert np.array_equal(np.asarray(default), np.asarray(named))


@pytest.mark.parametrize("entries,window", [(PPS, 0), (RING, WINDOW),
                                            (DIFF_RING, 512)])
def test_attended_rows_is_the_rings_rule_row_by_row(entries, window):
    """The mask a gathering twin uses, against the ring's rule written out:
    entry ``e`` holds logical page ``top - (top - e) mod entries``."""
    lengths = np.asarray([0, 1, 15, 16, 17, window or 40, (window or 40) + 1,
                          entries * PAGE - 1, entries * PAGE,
                          entries * PAGE + 1, 3 * entries * PAGE + 7])
    lengths = lengths[(lengths <= entries * PAGE) | (window > 0)]
    got = np.asarray(gpa.attended_rows(jnp.asarray(lengths, jnp.int32),
                                       entries, PAGE, window))
    for cached, seen in zip(lengths, got):
        top = max(cached - 1, 0) // PAGE
        want = np.zeros(entries * PAGE, bool)
        for e in range(min(-(-cached // PAGE), entries)):
            logical = top - (top - e) % entries
            for r in range(PAGE):
                pos = logical * PAGE + r
                want[e * PAGE + r] = pos < cached and (
                    not window or pos > cached - window)
        assert np.array_equal(seen, want), cached
        assert want.sum() == (min(cached, window - 1) if window else cached)


# -- granite-4.0-h-micro's layout: 32 heads on 8 of 64, scale 1/64 -----------

GRANITE = mh.Mamba2HybridConfig(dtype=jnp.float32)
G_BLOCK = PAGE * gpa.block_pages(PAGE, PPS, 32, GRANITE.kv_width, 4)


def softmax_written_out(c, layer, cfg):
    """Every live slot's attention over its cached positions, looked up one
    by one through the table, and its own new key and value: float64
    ``numpy``, nothing of the program.  Idle slots: zeros."""
    heads, hd = cfg.num_attention_heads, cfg.head_dim
    rep = heads // cfg.num_key_value_heads
    table, lengths = np.asarray(c["table"]), np.asarray(c["lengths"])
    kp, vp = (np.asarray(c[x][layer], np.float64)
              for x in ("k_pages", "v_pages"))
    out = np.zeros((len(lengths), heads, hd))
    for s, n in enumerate(lengths):
        if n < 0:
            continue
        at = np.arange(n)
        k, v = (np.concatenate([x[table[s, at // PAGE], at % PAGE],
                                np.asarray(c[own][s], np.float64)[None]])
                for x, own in ((kp, "k_self"), (vp, "v_self")))
        q = np.asarray(c["q"][s], np.float64).reshape(heads, hd)
        for h in range(heads):
            lanes = slice(h // rep * hd, (h // rep + 1) * hd)
            score = k[:, lanes] @ q[h] * cfg.attention_multiplier
            p = np.exp(score - score.max())
            out[s, h] = p @ v[:, lanes] / p.sum()
    return out.reshape(len(lengths), -1)


def granite_attend(c, layer, interpret):
    """``mamba2_hybrid.paged_attend``, the decode program's own: through
    the kernel in the interpreter, or the twin the rule gives here."""
    with pytest.MonkeyPatch.context() as patch:
        patch.setattr(mh, "PAGED_INTERPRET", interpret)
        return jax.jit(lambda c: mh.paged_attend(
            c["lengths"], c["table"], c["k_pages"], c["v_pages"], GRANITE)(
                layer, c["q"], c["k_self"], c["v_self"]))(c)


# Nothing cached, one position, a page's edge and one past it, a block's
# edge and one past it, several blocks (512 tokens each here), idle slots
# between; pages that are nobody's entries in use hold NaN.
@pytest.mark.parametrize("path", ["kernel", "twin"])
@pytest.mark.parametrize("lengths", [
    (0, 1, -1, PAGE, PAGE + 1, -1, 300, 5),
    (G_BLOCK, -1, G_BLOCK + 1, 2 * G_BLOCK + 40, -1, PAGE * PPS - 1)])
def test_granites_heads_attend_as_the_softmax_written_out(lengths, path):
    assert (GRANITE.num_attention_heads, GRANITE.num_key_value_heads,
            GRANITE.head_dim, GRANITE.attention_multiplier, G_BLOCK) == (
                32, 8, 64, 1 / 64, 512)
    kernel = path == "kernel"
    c = case(lengths, PPS, seed=47, nan_elsewhere=kernel,
             kw=GRANITE.kv_width, qw=2048)
    got = np.asarray(granite_attend(c, 2, True if kernel else None))
    on = np.asarray(c["lengths"]) >= 0
    want = softmax_written_out(c, 2, GRANITE)
    assert np.isfinite(got[on]).all()
    assert np.abs(got[on] - want[on]).max() < TOL
    if kernel:
        assert not got[~on].any()          # idle slots: exact zeros


def test_granites_decode_program_takes_the_kernel_by_the_backend_alone(
        monkeypatch):
    """Which of the two ``paged_attend`` builds is ``kernel_runs`` of the
    module's ``PAGED_INTERPRET`` (None off a test): the kernel on the TPU,
    the gathered rows elsewhere, and no argument says otherwise."""
    c = case((5, 40), PPS, seed=1, kw=GRANITE.kv_width, qw=2048)
    asked = []
    monkeypatch.setattr(gpa, "kernel_runs",
                        lambda interpret: asked.append(interpret) or False)
    monkeypatch.setattr(gpa, "gqa_paged_attention", None)    # never reached
    twin = granite_attend(c, 0, None)
    assert asked == [None]
    monkeypatch.undo()
    assert mh.PAGED_INTERPRET is None and not gpa.kernel_runs(None)
    assert gap(twin, granite_attend(c, 0, None)) == 0.0
