"""The two kernels of ops/ssd.py (the Mamba-2 recurrence in chunks over a
prompt, and one step a slot in place) in the Pallas interpreter, against
their plain ``jnp`` twins and against the recurrence step by step."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from horovod_tpu.ops import ssd

H, P, N = 8, 32, 16          # pack 4: two rows of four heads in the state


def operands(t, seed=0, heads=H, p=P, n=N):
    k = jax.random.split(jax.random.PRNGKey(seed), 6)
    x = jax.random.normal(k[0], (t, heads, p))
    dt = jax.nn.softplus(jax.random.normal(k[1], (t, heads)) - 2.0)
    a = -jnp.exp(jax.random.normal(k[2], (heads,)))
    b, c = jax.random.normal(k[3], (t, n)), jax.random.normal(k[4], (t, n))
    s0 = jax.random.normal(k[5], (heads, p, n))
    return x, dt, a, b, c, s0


def test_the_kept_layout_packs_heads_into_lane_rows_and_back():
    assert ssd.head_pack(64, 64) == 2 and ssd.head_pack(H, P) == 4
    assert ssd.head_pack(4, 8) == 4 and ssd.head_pack(6, 32) == 3
    assert ssd.head_pack(3, 128) == 1
    s = jax.random.normal(jax.random.PRNGKey(0), (3, H, P, N))
    kept = ssd.pack_state(s)
    assert kept.shape == (3, H // 4, N, 4 * P)
    # Head h, channel p, state row n lies in row h // 4 at [n, (h % 4) P + p].
    assert float(kept[1, 1, 5, 2 * P + 7]) == float(s[1, 6, 7, 5])
    assert bool((ssd.unpack_state(kept, P) == s).all())
    assert ssd.pack_state(jnp.zeros((64, 64, 128))).shape == (32, 128, 128)


# A length that is no multiple of the chunk; padding past n_valid (in the
# last chunk, at a chunk's edge, chunks wholly past it); two and five chunks
# whose edges the state crosses; one chunk; a prompt shorter than a chunk.
@pytest.mark.parametrize("t,n_valid,chunk", [
    (37, 37, 8), (40, 21, 8), (40, 16, 8), (16, 16, 8), (16, 9, 8), (8, 8, 8),
    (5, 3, 8), (2, 1, 8), (48, 30, 16)])
@pytest.mark.parametrize("zero_state", [False, True])
def test_chunked_scan_equals_the_sequential_recurrence(t, n_valid, chunk,
                                                       zero_state):
    x, dt, a, b, c, s0 = operands(t, seed=t + n_valid)
    if zero_state:
        s0 = jnp.zeros_like(s0)
    y_seq, s_seq = ssd.ssd_sequential(x, dt, a, b, c, s0, n_valid)
    kept = ssd.pack_state(s0)
    y_twin, s_twin = ssd.ssd_chunk_scan_jnp(x, dt, a, b, c, kept, n_valid,
                                            chunk=chunk)
    y, s = ssd.ssd_chunk_scan(x, dt, a, b, c, kept, n_valid, chunk=chunk,
                              interpret=True)
    assert y.shape == (t, H, P) and bool(jnp.isfinite(y).all())
    for got_y, got_s in ((y_twin, s_twin), (y, s)):
        assert float(jnp.abs(got_y - y_seq)[:n_valid].max()) < 2e-5
        assert float(jnp.abs(ssd.unpack_state(got_s, P) - s_seq).max()) < 2e-5
    # The kernel is its twin, and the state after n_valid steps is the
    # state of the shorter scan.
    assert float(jnp.abs(y - y_twin)[:n_valid].max()) < 2e-5
    _, s_short = ssd.ssd_sequential(x[:n_valid], dt[:n_valid], a,
                                    b[:n_valid], c[:n_valid], s0, n_valid)
    assert float(jnp.abs(ssd.unpack_state(s, P) - s_short).max()) < 2e-5
    first_skipped = -(-n_valid // chunk) * chunk
    assert not np.asarray(y[first_skipped:]).any()


def test_off_the_tpu_the_twin_runs_unless_the_interpreter_is_asked_for(
        monkeypatch):
    x, dt, a, b, c, s0 = operands(12)
    calls = []
    monkeypatch.setattr(ssd, "_pallas_chunk_scan",
                        lambda *a, **k: calls.append(1) or (None, None))
    ssd.ssd_chunk_scan(x, dt, a, b, c, ssd.pack_state(s0), 12, chunk=8)
    assert calls == []
    ssd.ssd_chunk_scan(x, dt, a, b, c, ssd.pack_state(s0), 12, chunk=8,
                       interpret=True)
    assert calls == [1]


def step_operands(slots=6, layers=3, seed=3):
    x, dt, a, b, c, _ = operands(slots, seed)
    store = ssd.pack_state(jax.random.normal(
        jax.random.PRNGKey(seed + 1), (layers, slots, H, P, N)))
    d = jnp.linspace(0.5, 1.5, H)
    return store, x, dt, a, b, c, d


@pytest.mark.parametrize("alive", [
    [True, False, True, True, False, False], [False] * 5 + [True],
    [True] * 6, [False, True, False, True, False, True]])
@pytest.mark.parametrize("layer", [None, 0, 2])
def test_one_step_in_place_equals_the_recurrence(alive, layer):
    store, x, dt, a, b, c, d = step_operands()
    alive = jnp.asarray(alive)
    on = np.asarray(alive)
    state = store[1] if layer is None else store
    at = 1 if layer is None else layer
    y_twin, s_twin = ssd.ssd_step_jnp(state, x, dt, a, b, c, d, alive,
                                      layer=layer)
    y, s = ssd.ssd_step(state, x, dt, a, b, c, d, alive, layer=layer,
                        interpret=True)
    assert y.shape == x.shape and s.shape == state.shape
    assert float(jnp.abs(y - y_twin)[on].max()) < 1e-5
    new = s if layer is None else s[layer]
    for i in np.flatnonzero(on):
        y_seq, s_seq = ssd.ssd_sequential(
            x[i:i + 1], dt[i:i + 1], a, b[i:i + 1], c[i:i + 1],
            ssd.unpack_state(store[at, i], P), 1)
        assert float(jnp.abs(y[i] - (y_seq[0] + d[:, None] * x[i])).max()) \
            < 1e-5
        assert float(jnp.abs(ssd.unpack_state(new[i], P) - s_seq).max()) \
            < 1e-5
    # An idle slot's rows are bit for bit what they were, its y is zero,
    # and no other layer of the store is touched.
    assert np.array_equal(np.asarray(new)[~on], np.asarray(store[at])[~on])
    assert not np.asarray(y)[~on].any()
    if layer is not None:
        others = [l for l in range(store.shape[0]) if l != layer]
        assert np.array_equal(np.asarray(s)[others],
                              np.asarray(store)[others])
        assert np.array_equal(np.asarray(s_twin)[others],
                              np.asarray(store)[others])


def test_nobody_alive_leaves_the_store_as_it_is():
    store, x, dt, a, b, c, d = step_operands()
    y, s = ssd.ssd_step(store, x, dt, a, b, c, d, jnp.zeros((6,), bool),
                        layer=1, interpret=True)
    assert np.array_equal(np.asarray(s), np.asarray(store))
    assert not np.asarray(y).any()


def test_the_steps_output_aliases_its_input():
    """The kernel's state output IS its state input (operand 2: behind the
    two prefetched scalars), so the store is never copied; only the live
    slots' blocks of the layer are mapped."""
    store, x, dt, a, b, c, d = step_operands()
    alive = jnp.asarray([True, False, True, True, False, False])

    def step(store):
        return ssd.ssd_step(store, x, dt, a, b, c, d, alive, layer=1,
                            interpret=True)

    calls = [e for e in jax.make_jaxpr(step)(store).jaxpr.eqns
             if e.primitive.name == "pallas_call"]
    assert len(calls) == 1
    assert tuple(calls[0].params["input_output_aliases"]) == ((2, 1),)
    assert calls[0].params["name"] == "ssd_step"
    assert calls[0].outvars[1].aval.shape == store.shape


def test_twenty_steps_one_at_a_time_equal_the_chunked_scan():
    """The two forms give the same y and S: a prompt through the chunked
    kernel, then decode steps, equals the sequential recurrence over all
    of it."""
    t, more = 19, 6
    x, dt, a, b, c, s0 = operands(t + more, seed=7)
    d = jnp.zeros((H,))
    y_all, s_all = ssd.ssd_sequential(x, dt, a, b, c, s0, t + more)
    _, s = ssd.ssd_chunk_scan(x[:t], dt[:t], a, b[:t], c[:t],
                              ssd.pack_state(s0), t, chunk=8,
                              interpret=True)
    state = s[None]
    for i in range(t, t + more):
        y, state = ssd.ssd_step(state, x[i][None], dt[i][None], a, b[i][None],
                                c[i][None], d, jnp.asarray([True]),
                                interpret=True)
        assert float(jnp.abs(y[0] - y_all[i]).max()) < 2e-5
    assert float(jnp.abs(ssd.unpack_state(state[0], P) - s_all).max()) < 2e-5
