"""The gated delta rule (ops/gated_delta.py) in its three forms: the chunked
kernel (in the Pallas interpreter), its plain twin and the one-step kernel,
each against the recurrence step by step.  Toy widths (4 heads of 16 x 64:
two heads to a 128-lane row, as 96 x 192 packs two to 384), ``beta`` past
1 (the negative eigenvalues), decays from 1e-3 to near 1, an initial
state, ``n_valid`` short of the bucket, idle slots."""

import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from horovod_tpu.ops import gated_delta as gd

H, DK, DV = 4, 16, 64
# float32 on every side with float32 operands: what is left is the order of
# sums (a chunk's triangular system against 64 steps one by one).  Outputs
# are of order 1 and differences measure 2e-6; a state held in bfloat16
# moves them by 1e-2 (test_a_bfloat16_state_is_caught).
TOL = 2e-5


def inputs(t, seed=0, dtype=jnp.float32, heads=H, dk=DK, dv=DV):
    """Unit keys, scaled queries, decays log-uniform from exp(-7) (a step
    keeps 1e-3) to exp(-1e-3), ``beta`` in (0, 2) with a real share past
    1, a random initial state."""
    ks = jax.random.split(jax.random.PRNGKey(seed), 6)
    unit = lambda x: x / jnp.linalg.norm(x, axis=-1, keepdims=True)
    q = unit(jax.random.normal(ks[0], (t, heads, dk))) * dk ** -0.5
    k = unit(jax.random.normal(ks[1], (t, heads, dk)))
    v = jax.random.normal(ks[2], (t, heads, dv))
    g = -jnp.exp(jax.random.uniform(ks[3], (t, heads)) * np.log(7e3)
                 + np.log(1e-3))
    beta = 2.0 * jax.nn.sigmoid(2.0 * jax.random.normal(ks[4], (t, heads)))
    s0 = jax.random.normal(ks[5], (heads, dk, dv))
    return q.astype(dtype), k.astype(dtype), v.astype(dtype), g, beta, s0


def test_the_inputs_reach_what_the_tests_claim():
    _, k, _, g, beta, _ = inputs(256)
    assert float(jnp.mean(beta > 1.0)) > 0.3 and float(beta.max()) > 1.9
    decay = np.exp(np.asarray(g))
    assert decay.min() < 2e-3 and decay.max() > 0.998
    # The transition's eigenvalue along k, alpha (1 - beta), goes negative.
    assert float((jnp.exp(g) * (1 - beta)).min()) < -0.5
    assert np.allclose(np.linalg.norm(np.asarray(k), axis=-1), 1.0,
                       atol=1e-5)


def test_the_state_layout_packs_two_heads_to_a_lane_row():
    assert gd.head_pack(30, 192) == 2 and gd.head_pack(H, DV) == 2
    assert gd.head_pack(4, 128) == 1 and gd.head_pack(3, 16) == 1
    s = jax.random.normal(jax.random.PRNGKey(0), (3, 30, 96, 192))
    packed = gd.pack_state(s)
    assert packed.shape == (3, 15, 96, 384)
    assert np.array_equal(np.asarray(gd.unpack_state(packed, 192)),
                          np.asarray(s))
    # Head 2r on the row block's first 192 lanes, 2r + 1 on the rest.
    assert np.array_equal(np.asarray(packed[1, 4, :, 192:]),
                          np.asarray(s[1, 9]))


@pytest.mark.parametrize("n", [2, 8, 64])
def test_the_block_inverse_solves_the_unit_triangular_system(n):
    a = 0.4 * jax.random.normal(jax.random.PRNGKey(n), (n, n))
    a = a + 0.5          # keys that resemble each other: the series' worst
    t = gd.unit_lower_inverse(a)
    eye = np.eye(n, dtype=np.float32)
    lower = np.tril(np.asarray(a), -1)
    assert np.abs(np.asarray(t) @ (eye + lower) - eye).max() < 1e-4
    assert np.abs(np.triu(np.asarray(t), 1)).max() == 0.0


# One chunk short of full, an edge, several chunks, a bucket with padding,
# a single step.
@pytest.mark.parametrize("t,n_valid", [(100, 100), (128, 77), (64, 64),
                                       (20, 20), (8, 1), (256, 200)])
@pytest.mark.parametrize("form", ["twin", "kernel"])
def test_the_chunked_forms_equal_the_recurrence(form, t, n_valid):
    q, k, v, g, beta, s0 = inputs(t, seed=t + n_valid)
    want_o, want_s = gd.gated_delta_sequential(q, k, v, g, beta, s0, n_valid)
    scan = (gd.gated_delta_chunk_scan_jnp if form == "twin" else
            functools.partial(gd.gated_delta_chunk_scan, interpret=True))
    o, s = scan(q, k, v, g, beta, gd.pack_state(s0), n_valid, chunk=64)
    assert o.shape == (t, H, DV) and s.shape == (H // 2, DK, 2 * DV)
    assert float(jnp.abs(o[:n_valid] - want_o[:n_valid]).max()) < TOL
    assert float(jnp.abs(gd.unpack_state(s, DV) - want_s).max()) < TOL


def test_kernel_and_twin_are_one_arithmetic():
    q, k, v, g, beta, s0 = inputs(150, seed=3)
    a = gd.gated_delta_chunk_scan_jnp(q, k, v, g, beta, gd.pack_state(s0),
                                      120, chunk=32)
    b = gd.gated_delta_chunk_scan(q, k, v, g, beta, gd.pack_state(s0), 120,
                                  chunk=32, interpret=True)
    assert float(jnp.abs(a[0][:120] - b[0][:120]).max()) < 1e-6
    assert float(jnp.abs(a[1] - b[1]).max()) < 1e-6
    # A chunk wholly past n_valid: the kernel skips it and writes zeros.
    assert float(jnp.abs(b[0][128:]).max()) == 0.0
    with pytest.raises(ValueError, match="power of two"):
        gd.gated_delta_chunk_scan_jnp(q, k, v, g, beta, gd.pack_state(s0),
                                      120, chunk=48)


@pytest.mark.parametrize("form", ["twin", "kernel"])
def test_a_buckets_padding_leaves_the_state_bit_for_bit(form):
    """Steps at and past ``n_valid`` decay by exp(0) and write nothing:
    the state after a padded bucket IS the state after the real tokens."""
    q, k, v, g, beta, s0 = inputs(128, seed=5)
    scan = (gd.gated_delta_chunk_scan_jnp if form == "twin" else
            functools.partial(gd.gated_delta_chunk_scan, interpret=True))
    _, padded = scan(q, k, v, g, beta, gd.pack_state(s0), 70, chunk=64)
    _, exact = scan(q[:70], k[:70], v[:70], g[:70], beta[:70],
                    gd.pack_state(s0), 70, chunk=64)
    # Both sides run two chunks of 64: the bucket's 128 rows, and the 70
    # real tokens padded to 128 by the wrapper.
    assert np.array_equal(np.asarray(padded), np.asarray(exact))


def test_bfloat16_operands_accumulate_in_float32():
    """The served type: operands rounded, sums float32.  Against the
    recurrence on the SAME rounded operands the chunked form differs by
    the rounding of U and of the masked scores, 1e-2 of outputs of order
    1; the state products stay exact."""
    q, k, v, g, beta, s0 = inputs(128, seed=7, dtype=jnp.bfloat16)
    want_o, want_s = gd.gated_delta_sequential(q, k, v, g, beta, s0, 128)
    for scan in (gd.gated_delta_chunk_scan_jnp, functools.partial(
            gd.gated_delta_chunk_scan, interpret=True)):
        o, s = scan(q, k, v, g, beta, gd.pack_state(s0), 128)
        assert o.dtype == jnp.float32 and s.dtype == jnp.float32
        assert float(jnp.abs(o - want_o).max()) < 3e-2
        assert float(jnp.abs(gd.unpack_state(s, DV) - want_s).max()) < 5e-2


# -- one token a slot ---------------------------------------------------------

ALIVE = np.array([True, False, True, True, False, True])


def step_inputs(seed=0):
    q, k, v, g, beta, _ = inputs(len(ALIVE), seed=seed)
    store = jax.random.normal(jax.random.PRNGKey(seed + 50),
                              (3, len(ALIVE), H, DK, DV))
    return q, k, v, g, beta, store


@pytest.mark.parametrize("form", ["twin", "kernel"])
@pytest.mark.parametrize("layer", [None, 1])
def test_a_step_equals_the_recurrence_and_idle_slots_keep_every_bit(form,
                                                                    layer):
    q, k, v, g, beta, store = step_inputs(layer or 0)
    packed = gd.pack_state(store)
    state = packed if layer is not None else packed[1]
    kw = {"interpret": True} if form == "kernel" else {}
    o, new = gd.gated_delta_step(state, q, k, v, g, beta, jnp.asarray(ALIVE),
                                 layer=layer, **kw)
    assert o.shape == (len(ALIVE), H, DV) and new.shape == state.shape
    mine = new[1] if layer is not None else new
    for slot in range(len(ALIVE)):
        if not ALIVE[slot]:
            assert np.array_equal(np.asarray(mine[slot]),
                                  np.asarray(packed[1, slot]))
            assert float(jnp.abs(o[slot]).max()) == 0.0
            continue
        want_o, want_s = gd.gated_delta_sequential(
            q[slot:slot + 1], k[slot:slot + 1], v[slot:slot + 1],
            g[slot:slot + 1], beta[slot:slot + 1], store[1, slot], 1)
        assert float(jnp.abs(o[slot] - want_o[0]).max()) < 1e-5
        assert float(jnp.abs(gd.unpack_state(mine[slot], DV)
                             - want_s).max()) < 1e-5
    if layer is not None:
        # The other layers of the store are not touched at all.
        for other in (0, 2):
            assert np.array_equal(np.asarray(new[other]),
                                  np.asarray(packed[other]))


def test_nobody_alive_moves_nothing():
    q, k, v, g, beta, store = step_inputs(9)
    packed = gd.pack_state(store)
    for kw in ({}, {"interpret": True}):
        o, new = gd.gated_delta_step(packed, q, k, v, g, beta,
                                     jnp.zeros(len(ALIVE), bool), layer=2,
                                     **kw)
        assert np.array_equal(np.asarray(new), np.asarray(packed))
        assert float(jnp.abs(o).max()) == 0.0


def test_steps_after_a_chunked_prompt_continue_the_recurrence():
    """A prompt through the chunked form, then ten decode steps through the
    step kernel, equal the recurrence over all of it."""
    q, k, v, g, beta, s0 = inputs(90, seed=21)
    want_o, want_s = gd.gated_delta_sequential(q, k, v, g, beta,
                                               jnp.zeros_like(s0), 90)
    _, s = gd.gated_delta_chunk_scan(q[:80], k[:80], v[:80], g[:80],
                                     beta[:80], gd.pack_state(
                                         jnp.zeros_like(s0)), 80,
                                     interpret=True)
    state = jnp.stack([s, s])            # two slots, one of them idle
    alive = jnp.asarray([True, False])
    for i in range(80, 90):
        two = lambda x: jnp.stack([x[i], x[i]])
        o, state = gd.gated_delta_step(state, two(q), two(k), two(v),
                                       two(g), two(beta), alive,
                                       interpret=True)
        assert float(jnp.abs(o[0] - want_o[i]).max()) < TOL
    assert float(jnp.abs(gd.unpack_state(state[0], DV) - want_s).max()) < TOL
    assert np.array_equal(np.asarray(state[1]), np.asarray(s))


def test_a_bfloat16_state_is_caught():
    """The tolerance sees the state's type: the same steps with the state
    rounded to bfloat16 after each one leave it two hundred tolerances
    out."""
    q, k, v, g, beta, s0 = inputs(64, seed=33)
    want_o, _ = gd.gated_delta_sequential(q, k, v, g, beta, s0, 64)
    state = gd.pack_state(s0)[None]
    alive = jnp.asarray([True])
    worst = 0.0
    for i in range(64):
        o, state = gd.gated_delta_step(state, q[i:i + 1], k[i:i + 1],
                                       v[i:i + 1], g[i:i + 1],
                                       beta[i:i + 1], alive)
        state = state.astype(jnp.bfloat16).astype(jnp.float32)
        worst = max(worst, float(jnp.abs(o[0] - want_o[i]).max()))
    assert worst > 100 * TOL
