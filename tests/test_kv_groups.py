"""The paged store's layer GROUPS (serving/kv_cache.py): a full group that
keeps every position beside window groups whose table row is a ring of
pages, page pools the memory planner sizes from a byte budget, and
admission headroom counted in every pool.  Host-side bookkeeping only: the
programs that read the tables are tested with the model that declares them
(tests/test_afmoe.py)."""

import jax.numpy as jnp
import numpy as np
import pytest

from horovod_tpu.memory import planner
from horovod_tpu.serving.kv_cache import PagedKVCache
from horovod_tpu.serving.scheduler import (ContinuousBatchingScheduler,
                                           Request)
from test_hybrid_ssm import counter

PAGE, WINDOW, RING = 4, 8, 3           # ceil(8 / 4) + 1 ring entries
GROUPS = ({"name": "full", "n_layers": 1},
          {"name": "window", "n_layers": 4, "window": WINDOW})


def grouped(slots=4, pps=16, pool_pages=None, **kw):
    return PagedKVCache(1, 2, 8, slots, pps, PAGE, dtype=jnp.float32,
                        entry_widths=(16, 16), groups=GROUPS,
                        pool_pages=pool_pages, **kw)


def used(cache):
    return {name: n for name, (n, _) in cache.group_pages().items()}


# -- the ring of pages --------------------------------------------------------

@pytest.mark.parametrize("prompt", [1, 3, 4, 5, 8, 9, 12, 13, 30])
def test_a_slot_holds_its_lengths_pages_and_never_more_than_the_ring(prompt):
    """``ceil(L / page)`` pages in each group until the ring is full, then
    ``R`` in the window group while the full group grows on."""
    c = grouped()
    c.begin_slot(0, prompt)
    for length in range(prompt, 64):
        pages = -(-length // PAGE)
        assert used(c) == {"full": pages, "window": min(pages, RING)}
        c.ensure(0, length)            # room for the token at ``length``
    c.free_slot(0)
    assert used(c) == {"full": 0, "window": 0}
    assert c.free_pages() == c.total_pages == 4 * 16
    assert c.headroom().tolist() == [4 * 16, 4 * RING]


def test_a_logical_page_lies_in_entry_j_mod_r_and_is_written_over_in_place():
    c = grouped()
    reused = counter("serving.window_pages_reused")
    c.begin_slot(1, 2)
    ring = lambda: c.table_row(1)[0, 16:]
    assert c.table_row(1).shape == (1, 16 + RING) == (1, c.table_width)
    assert ring()[0] != 0 and not ring()[1:].any()
    first = ring().copy()
    c.ensure(1, 4)                     # logical page 1 -> entry 1
    c.ensure(1, 8)                     # logical page 2 -> entry 2
    filled = ring().copy()
    assert filled.all() and filled[0] == first[0]
    assert len(set(filled.tolist())) == RING
    assert counter("serving.window_pages_reused") == reused
    c.ensure(1, 12)                    # logical page 3 -> entry 0 again
    c.ensure(1, 16)                    # logical page 4 -> entry 1 again
    assert np.array_equal(ring(), filled)          # no new page taken
    assert counter("serving.window_pages_reused") - reused == 2
    # The full group took a page each time.
    assert np.count_nonzero(c.table_row(1)[0, :16]) == 5
    assert used(c) == {"full": 5, "window": RING}


def test_a_prompt_longer_than_the_ring_maps_its_last_pages_only():
    c = grouped()
    c.begin_slot(0, 30)                # logical pages 0..7; the ring: 5, 6, 7
    assert used(c) == {"full": 8, "window": RING}
    assert np.count_nonzero(c.table_row(0)[0, 16:]) == RING
    c.ensure(0, 32)                    # page 8 takes entry 2: page 5's
    assert used(c) == {"full": 9, "window": RING}


def test_the_groups_have_their_own_arrays_and_free_lists():
    c = grouped(slots=2)
    full_k, full_v, win_k, win_v = c.arrays
    assert full_k.shape == full_v.shape == (1, 1 + 2 * 16, PAGE, 16)
    assert win_k.shape == win_v.shape == (4, 1 + 2 * RING, PAGE, 16)
    c.begin_slot(0, 9)
    c.begin_slot(1, 9)
    a, b = c.table_row(0)[0], c.table_row(1)[0]
    # Page numbers are a group's own: both start at 1, and two slots never
    # share one within a group; page 0 is every group's trash page.
    assert set(a[16:]) == {1, 2, 3} and set(b[16:]) == {4, 5, 6}
    assert set(a[:3]) == {1, 2, 3} and not a[3:16].any()
    table, lengths = c.host_tables()
    assert table.shape == (2, 16 + RING) and lengths.tolist() == [9, 9]
    with pytest.raises(RuntimeError, match="out of pages"):
        c._extra[0].free.clear()
        c.free_slot(1)
        c._extra[0].free.clear()
        c.begin_slot(1, 3)
    swapped = tuple(x + 1 for x in c.arrays)
    c.replace_pages(*swapped)
    assert all(x is y for x, y in zip(c.arrays, swapped))
    with pytest.raises(ValueError, match="page arrays"):
        c.replace_pages(*swapped[:2])


def test_what_a_store_of_groups_refuses():
    with pytest.raises(ValueError, match="first layer group"):
        PagedKVCache(1, 2, 8, 2, 4, PAGE, groups=GROUPS[::-1])
    with pytest.raises(ValueError, match="first layer group"):
        PagedKVCache(1, 2, 8, 2, 4, PAGE, groups=GROUPS[:1] * 2)
    with pytest.raises(ValueError, match="shared-prefix"):
        grouped(prefix_cache=True)
    with pytest.raises(ValueError, match="page pools"):
        grouped(pool_pages=(10,))
    with pytest.raises(ValueError, match="window must be"):
        PagedKVCache(1, 2, 8, 2, 4, PAGE, groups=(
            GROUPS[0], {"name": "w", "n_layers": 1, "window": -1}))
    # A per-slot store's dimension is a number or the slot's "capacity":
    # no other name (the "view" the ladder's scratch store was sized by).
    with pytest.raises(ValueError, match='number or "capacity"'):
        grouped(slot_stores=({"name": "v", "kind": "scratch",
                              "shape": (2, "view", 16),
                              "dtype": jnp.float32},))


# -- pools and admission ------------------------------------------------------

def test_a_pooled_store_reserves_what_a_sequence_may_come_to_hold():
    c = grouped(pool_pages=(20, 7))
    assert [t for _, t in c.group_pages().values()] == [20, 7]
    assert c.arrays[0].shape[1] == 21 and c.arrays[2].shape[1] == 8
    # A prompt of 5 that may grow to 40: 10 full pages, the whole ring.
    assert c.admission_need([1] * 5, 35).tolist() == [10, RING]
    assert c.admission_need([1] * 5, 500).tolist() == [16, RING]  # capacity
    assert c.admission_need([1] * 2, 3).tolist() == [2, 2]
    c.begin_slot(0, 5, reserve_tokens=40)
    assert used(c) == {"full": 2, "window": 2}
    # Free pages less what slot 0 has reserved and not mapped yet.
    assert c.headroom().tolist() == [20 - 10, 7 - RING]
    for pos in range(5, 40):
        c.ensure(0, pos)
    assert used(c) == {"full": 10, "window": RING}
    assert c.headroom().tolist() == [10, 4]
    c.begin_slot(1, 20, reserve_tokens=24)
    assert c.headroom().tolist() == [4, 1]
    c.free_slot(0)
    assert c.headroom().tolist() == [14, 4]
    c.free_slot(1)
    assert c.headroom().tolist() == [20, 7] and c.free_pages() == 20


def test_admission_is_refused_when_either_pool_lacks_headroom():
    """The scheduler's page gate takes a vector, an entry a pool: a
    request of 24 positions needs 6 pages of the full group and the whole
    ring, and the second of two is deferred whichever pool is short."""
    for pools, lacking in (((40, 4), "window"), ((9, 12), "full")):
        c = grouped(pool_pages=pools)
        sched = ContinuousBatchingScheduler(4, c.capacity)
        a = sched.submit(Request([1] * 20, max_new_tokens=4))
        b = sched.submit(Request([2] * 20, max_new_tokens=4))
        deferred = counter("serving.admission_deferred")
        need = lambda req: c.admission_need(req.prompt, req.max_new_tokens)
        admitted = sched.admit(None, page_budget=c.headroom(),
                               pages_needed=need)
        assert [req for _, req in admitted] == [a], lacking
        assert counter("serving.admission_deferred") - deferred == 1
        (slot, _), = admitted
        c.begin_slot(slot, 20, reserve_tokens=24)
        assert sched.admit(None, page_budget=c.headroom(),
                           pages_needed=need) == []
        assert sched.pending() == [b]
        # The first leaves: its pages AND its reservation go back, and the
        # second fits.
        c.free_slot(slot)
        assert c.headroom().tolist() == list(pools)
        assert [req for _, req in sched.admit(
            None, page_budget=c.headroom(), pages_needed=need)] == [b]
    # A plain number still gates a store of one pool.
    sched = ContinuousBatchingScheduler(2, 64)
    sched.submit(Request([1] * 9, max_new_tokens=2))
    assert sched.admit(None, page_budget=2,
                       pages_needed=lambda req: 3) == []
    assert len(sched.admit(None, page_budget=3,
                           pages_needed=lambda req: 3)) == 1


def test_the_planner_splits_a_budget_so_that_neither_pool_runs_dry_first():
    token = 4096                       # the cell's: 2 x 8 x 128 bfloat16
    groups = ({"name": "full", "n_layers": 1},
              {"name": "window", "n_layers": 4, "window": 4096})
    assert planner.group_entries(groups[0], 576, 16) == 576
    assert planner.group_entries(groups[1], 576, 16) == 257
    pools = planner.size_page_pools(groups, token, 16, 576, 64,
                                    3_800_000_000, expected_tokens=5120)
    assert pools == (13763, 11053)
    # Both pools hold the same 43 sequences of 5120 tokens: 320 pages of
    # the full group and the whole ring of the window group each.
    assert pools[0] // 320 == pools[1] // 257 == 43
    spent = sum(g["n_layers"] * (1 + p) * 16 * token
                for g, p in zip(groups, pools))
    assert 3.79e9 < spent <= 3.8e9
    # Sequences shorter than the window take as many pages of each group.
    short = planner.size_page_pools(groups, token, 16, 576, 64,
                                    3_800_000_000, expected_tokens=2048)
    assert short[0] == short[1] == 11595
    # No pool exceeds every slot at its largest, nor falls under one slot.
    huge = planner.size_page_pools(groups, token, 16, 576, 64, 10**12)
    assert huge == (64 * 576, 64 * 257)
    with pytest.raises(ValueError, match="less than one slot"):
        planner.size_page_pools(groups, token, 16, 576, 64, 10**8)
    # The plan prices what the pools take, trash pages included, and a
    # per-slot store beside them by the slot's capacity, whatever the pools.
    plan = planner.plan_serving(
        1, 8, 128, 64, 576, 16, dtype=jnp.bfloat16, groups=groups,
        pool_pages=pools, slot_stores=(
            {"name": "paged_view", "kind": "scratch",
             "shape": (2, "capacity", 1024), "dtype": jnp.bfloat16},))
    assert plan.framework["serving.kv_pages"] == spent
    assert plan.framework["serving.slot_state"] == 2 * 64 * 9216 * 1024 * 2
    whole = planner.plan_serving(1, 8, 128, 64, 576, 16,
                                 dtype=jnp.bfloat16, groups=groups)
    assert whole.framework["serving.kv_pages"] == 16 * token * (
        (1 + 64 * 576) + 4 * (1 + 64 * 257))


# -- one group: the store the four served models build ------------------------

@pytest.mark.parametrize("stores", [(), "hybrid"])
def test_a_store_of_one_group_is_the_store_it_was(stores):
    """No ``groups``: the arrays, the table, the free list and the
    admission arithmetic of the store before layer groups (the four
    models served through it declare none)."""
    if stores:
        stores = ({"name": "ring", "kind": "window", "shape": (3, 8, 16),
                   "dtype": jnp.float32},
                  {"name": "state", "kind": "state", "shape": (2, 5),
                   "dtype": jnp.float32},
                  {"name": "view", "kind": "scratch",
                   "shape": (2, "capacity", 16), "dtype": jnp.float32})
    c = PagedKVCache(3, 2, 8, 4, 6, PAGE, dtype=jnp.float32,
                     entry_widths=(16, 16), slot_stores=stores)
    assert c.group_names == ("full",) and c.table_width == 6
    assert c.n_pages == 1 + 4 * 6 and c.total_pages == 24
    shapes = [a.shape for a in c.arrays]
    assert shapes[:2] == [(3, 25, PAGE, 16)] * 2
    assert shapes[2:] == ([(3, 4, 8, 16), (2, 4, 5), (2, 4, 24, 16)]
                          if stores else [])
    table, lengths = c.host_tables()
    assert table.shape == (4, 6) and table.dtype == np.int32
    assert lengths.tolist() == [-1] * 4
    c.begin_slot(2, 9)
    assert c.table_row(2).tolist() == [[1, 2, 3, 0, 0, 0]]
    assert c.ensure(2, 12) == 1 and c.table_row(2)[0, 3] == 4
    assert c.free_pages() == 20 == int(c.headroom()[0])
    assert c.headroom().shape == (1,)
    # Admission is priced by the prompt's pages, as admission_cost does.
    assert c.admission_need([1] * 9, 500).tolist() == [3]
    assert c.admission_cost([1] * 9) == 3
    assert c.group_pages() == {"full": (4, 24)}
    c.free_slot(2)
    assert c.free_pages() == 24 and not c.host_tables()[0].any()
    # No per-group gauge for a store that has one group.
    assert not c._group_gauges


# -- a window group beside per-slot stores: the hybrid's store ----------------

def test_a_window_group_and_slot_stores_live_in_one_manager():
    """What ``models/hybrid_ssm.py`` declares since its window layers are
    a group of pages: two groups AND per-slot state.  The arrays come
    group after group, then the stores; a slot's admission maps pages in
    both groups and counts one reset of its state; an eviction gives both
    groups' pages back and leaves the stores' rows (the next prefill
    replaces them); the executables' outputs go back in the same order."""
    stores = ({"name": "ssm_state", "kind": "state", "shape": (2, 5, 6),
               "dtype": jnp.float32},
              {"name": "conv_tail", "kind": "state", "shape": (2, 3, 6),
               "dtype": jnp.bfloat16})
    c = grouped(slot_stores=stores)
    shapes = [a.shape for a in c.arrays]
    assert shapes == [(1, 1 + 4 * 16, PAGE, 16)] * 2 \
        + [(4, 1 + 4 * RING, PAGE, 16)] * 2 + [(2, 4, 5, 6), (2, 4, 3, 6)]
    assert c.arrays[5].dtype == jnp.bfloat16
    assert c.slot_store_bytes() == {"state": 2 * 4 * 6 * (5 * 4 + 3 * 2)}
    resets = counter("serving.state_slot_resets")
    c.begin_slot(3, 30)                # 8 pages of 4; the ring keeps 3
    assert counter("serving.state_slot_resets") - resets == 1
    assert used(c) == {"full": 8, "window": RING}
    assert c.table_row(3).shape == (1, 16 + RING)
    c.ensure(3, 40)
    assert used(c) == {"full": 11, "window": RING}
    # A slot's rows of the stores are the model's to replace: a marker
    # written as an executable would return it survives the eviction.
    marked = tuple(jnp.ones_like(a) for a in c.arrays)
    c.replace_pages(*marked)
    assert [a.shape for a in c.arrays] == shapes
    assert c.slot_state[0].shape == (2, 4, 5, 6) and bool(
        c.slot_state[0].all())
    c.free_slot(3)
    assert used(c) == {"full": 0, "window": 0}
    assert c.headroom().tolist() == [4 * 16, 4 * RING]
    assert bool(c.slot_state[1].all())
    with pytest.raises(ValueError, match="page arrays"):
        c.replace_pages(*marked[:4])
    # Reserved pools and per-slot stores together, as admission counts
    # them: the stores take no page of either pool.
    pooled = grouped(slot_stores=stores, pool_pages=(20, 6))
    assert pooled.headroom().tolist() == [20, 6]
    pooled.begin_slot(0, 9, reserve_tokens=20)
    assert pooled.headroom().tolist() == [15, 3]
