"""hvd-tune (ISSUE 18): the closed-loop online self-tuning subsystem.

Policy-engine unit tests run the pure rule table over seeded
WindowSnapshot sequences (no runtime, no clock): a rule fires exactly
once per sustained diagnosis, boundary-flapping input never accumulates
the hysteresis streak, and a planner veto is counted while the knob
stays untouched.  The actuation tests drive REAL eager traffic through
init so RETUNE markers ride the production response stream; the np=2
coherence leg runs a real controller+worker transport pair over
loopback and asserts both ranks log the same decision sequence at the
same stream positions.
"""

import os
import re
import socket
import threading
import time
from types import SimpleNamespace

import jax.numpy as jnp
import pytest

from horovod_tpu.tuning import policy as tuning_policy
from horovod_tpu.tuning.policy import (COMPRESSION_LADDER,
                                       KNOB_DCN_COMPRESS,
                                       KNOB_FUSION_THRESHOLD,
                                       KNOB_MAX_INFLIGHT,
                                       KNOB_PREFIX_PAGES,
                                       KNOB_SPEC_TOKENS, PolicyConfig,
                                       PolicyEngine, WindowSnapshot)

THRESHOLD = 1 << 20

DEFAULT_KNOBS = {
    KNOB_DCN_COMPRESS: "none",
    KNOB_MAX_INFLIGHT: 2,
    KNOB_FUSION_THRESHOLD: 64 << 20,
    "cycle_time": 0.005,
    KNOB_SPEC_TOKENS: 3,
}

FLAT_LEGS = {"host": 100.0, "collective": 100.0, "dcn": 10.0,
             "dispatch": 100.0, "dispatch-gap": 10.0}
DCN_LEGS = {"host": 50.0, "collective": 50.0, "dcn": 400.0,
            "dispatch": 50.0, "dispatch-gap": 10.0}
GAP_LEGS = {"host": 50.0, "collective": 50.0, "dcn": 10.0,
            "dispatch": 50.0, "dispatch-gap": 400.0}


def snap(index, legs=FLAT_LEGS, knobs=None, **kw):
    return WindowSnapshot(index=index, legs=dict(legs),
                          knobs=dict(knobs or DEFAULT_KNOBS), **kw)


# ---------------------------------------------------------------------------
# Policy engine: seeded-snapshot unit tests
# ---------------------------------------------------------------------------

def test_dcn_rule_fires_exactly_once_per_sustained_diagnosis():
    """sustain=2: window 0 arms the streak, window 1 fires ONE ladder
    escalation, window 2 is silenced by the post-fire streak reset and
    the knob cooldown — one decision per sustained diagnosis, not one
    per window the condition holds."""
    eng = PolicyEngine(PolicyConfig(sustain=2, cooldown=2))
    assert eng.step(snap(0, DCN_LEGS)) is None
    d = eng.step(snap(1, DCN_LEGS))
    assert d is not None
    assert (d.knob, d.value) == (KNOB_DCN_COMPRESS, "bf16")
    assert d.wire() == "dcn_compress=bf16"
    assert eng.step(snap(2, DCN_LEGS)) is None
    assert len(eng.decisions) == 1


def test_dcn_ladder_climbs_one_rung_per_decision():
    eng = PolicyEngine(PolicyConfig(sustain=1, cooldown=0))
    values = []
    knobs = dict(DEFAULT_KNOBS)
    for i in range(6):
        d = eng.step(snap(i, DCN_LEGS, knobs))
        if d is not None:
            values.append(d.value)
            knobs[KNOB_DCN_COMPRESS] = d.value  # the fleet applied it
    # Climbs none -> bf16 -> int8 -> int4 and then stops at the floor.
    assert values == list(COMPRESSION_LADDER[1:])


def test_hysteresis_boundary_flapping_never_fires():
    """A condition alternating true/false each window never reaches the
    sustain streak — the anti-thrash contract."""
    eng = PolicyEngine(PolicyConfig(sustain=2, cooldown=2))
    for i in range(12):
        legs = DCN_LEGS if i % 2 == 0 else FLAT_LEGS
        assert eng.step(snap(i, legs)) is None
    assert eng.decisions == []
    assert eng.vetoes == 0


def test_planner_veto_counts_and_leaves_knob_untouched():
    """A candidate whose priced byte delta exceeds the window's known
    headroom is vetoed: counted, logged, no decision, and the knob is
    cooled down so the doomed candidate is not re-priced every window."""
    eng = PolicyEngine(PolicyConfig(sustain=1, cooldown=3),
                       price=lambda knob, old, new, s: 10 << 30)
    s = snap(0, GAP_LEGS, headroom_frac=0.5, headroom_bytes=1 << 20)
    assert eng.step(s) is None
    assert eng.vetoes == 1
    assert eng.decisions == []
    assert eng.veto_log[0][1] == KNOB_MAX_INFLIGHT
    # Cooldown active: the next windows don't even re-price.
    assert eng.step(snap(1, GAP_LEGS, headroom_frac=0.5,
                         headroom_bytes=1 << 20)) is None
    assert eng.vetoes == 1


def test_cheap_candidate_passes_the_priced_veto():
    eng = PolicyEngine(PolicyConfig(sustain=1, cooldown=0),
                       price=lambda knob, old, new, s: 64)
    d = eng.step(snap(0, GAP_LEGS, headroom_frac=0.5,
                      headroom_bytes=1 << 20))
    assert d is not None and d.knob == KNOB_MAX_INFLIGHT
    assert d.value == 4  # widen 2 -> 4
    assert eng.vetoes == 0


def test_straggler_rule_rebuckets_after_persistence():
    """The straggler rule's hysteresis is its same-rank streak: two
    consecutive windows blaming rank 1 fire one fusion re-bucket."""
    eng = PolicyEngine(PolicyConfig(sustain=2, cooldown=2))
    assert eng.step(snap(0, straggler_rank=1)) is None
    d = eng.step(snap(1, straggler_rank=1))
    assert d is not None
    assert d.knob == KNOB_FUSION_THRESHOLD
    assert d.value == (64 << 20) // 2
    assert "rank 1" in d.reason


def test_straggler_rank_change_resets_persistence():
    eng = PolicyEngine(PolicyConfig(sustain=2, cooldown=2))
    assert eng.step(snap(0, straggler_rank=1)) is None
    assert eng.step(snap(1, straggler_rank=2)) is None  # new rank: restart
    assert eng.step(snap(2, straggler_rank=-1)) is None
    assert eng.decisions == []


def test_low_acceptance_shrinks_spec_tokens_to_floor():
    eng = PolicyEngine(PolicyConfig(sustain=1, cooldown=0))
    knobs = dict(DEFAULT_KNOBS)
    values = []
    for i in range(5):
        d = eng.step(snap(i, spec_acceptance=0.2, knobs=knobs))
        if d is not None:
            values.append(d.value)
            knobs[KNOB_SPEC_TOKENS] = d.value
    assert values == [2, 1]  # 3 -> 2 -> 1, then the floor holds


def test_prefix_reserve_grows_under_kv_pressure_with_hot_index():
    """hvd-route satellite: a HOT shared-prefix index (hit rate >= high)
    while KV admission headroom thrashes (kv_free_frac < floor) earns a
    dedicated page reserve, doubling up to the cap."""
    eng = PolicyEngine(PolicyConfig(sustain=1, cooldown=0))
    knobs = dict(DEFAULT_KNOBS)
    values = []
    for i in range(8):
        d = eng.step(snap(i, kv_free_frac=0.1, prefix_hit_rate=0.7,
                          knobs=knobs))
        if d is not None:
            assert d.knob == KNOB_PREFIX_PAGES
            values.append(d.value)
            knobs[KNOB_PREFIX_PAGES] = d.value
    assert values == [8, 16, 32, 64, 128, 256]  # then the cap holds
    assert "grow the prefix reserve" in eng.decisions[0].reason


def test_prefix_reserve_shrinks_when_index_goes_cold():
    eng = PolicyEngine(PolicyConfig(sustain=1, cooldown=0))
    knobs = dict(DEFAULT_KNOBS)
    knobs[KNOB_PREFIX_PAGES] = 32
    d = eng.step(snap(0, prefix_hit_rate=0.01, knobs=knobs))
    assert d is not None
    assert d.knob == KNOB_PREFIX_PAGES
    assert d.value == 16
    assert "shrink the prefix reserve" in d.reason


def test_prefix_rules_idle_in_dead_band_and_without_signal():
    eng = PolicyEngine(PolicyConfig(sustain=1, cooldown=0))
    # Dead band: hit rate between low and high never moves the knob.
    assert eng.step(snap(0, kv_free_frac=0.1,
                         prefix_hit_rate=0.3)) is None
    # Hot index but ample KV headroom: no pressure, no reserve.
    assert eng.step(snap(1, kv_free_frac=0.9,
                         prefix_hit_rate=0.9)) is None
    # Cold index with no reserve: nothing to give back.
    assert eng.step(snap(2, prefix_hit_rate=0.0)) is None
    # Unknown sensors (the -1.0 defaults) hold everything still.
    assert eng.step(snap(3)) is None
    assert eng.decisions == []


def test_prefix_grow_is_planner_priced():
    """The reserve's byte delta rides the same priced veto as every
    other knob — a grow the host cannot afford is refused."""
    eng = PolicyEngine(PolicyConfig(sustain=1, cooldown=3),
                       price=lambda knob, old, new, s: 10 << 30)
    assert eng.step(snap(0, kv_free_frac=0.1, prefix_hit_rate=0.7,
                         headroom_bytes=1 << 20)) is None
    assert eng.vetoes == 1
    assert eng.veto_log[0][1] == KNOB_PREFIX_PAGES


def test_headroom_pressure_outranks_speed_rules():
    """Safety first: under HBM pressure the byte-saving rule wins even
    when the dcn leg dominates the critical path."""
    eng = PolicyEngine(PolicyConfig(sustain=1, cooldown=0))
    d = eng.step(snap(0, DCN_LEGS, headroom_frac=0.05,
                      headroom_bytes=1 << 20))
    assert d is not None
    assert d.knob == KNOB_FUSION_THRESHOLD  # shrink buffers, not wire
    assert "headroom" in d.reason


def test_pinned_knob_is_never_touched():
    eng = PolicyEngine(PolicyConfig(
        sustain=1, cooldown=0, pinned=frozenset({KNOB_DCN_COMPRESS})))
    for i in range(4):
        assert eng.step(snap(i, DCN_LEGS)) is None
    assert eng.decisions == []


def test_decision_sequence_is_deterministic():
    """Same seeded snapshot sequence through two fresh engines: the
    decision sequences are identical: the engine reads no clock and
    draws no random number."""
    feed = ([snap(i, DCN_LEGS) for i in range(3)]
            + [snap(i, GAP_LEGS, straggler_rank=1) for i in range(3, 6)]
            + [snap(i, spec_acceptance=0.1) for i in range(6, 10)])

    def run():
        eng = PolicyEngine(PolicyConfig(sustain=2, cooldown=1))
        for s in feed:
            eng.step(s)
        return [(d.seq, d.window, d.knob, d.value) for d in eng.decisions]

    first = run()
    assert first  # the feed produces decisions
    assert run() == first


def test_closed_loop_reaches_the_hand_tuned_knobs_and_rests():
    """The rules composed: each decision is applied to the knobs the
    NEXT window's legs are synthesized from.  Started mis-tuned (no
    DCN compression, in-flight depth 1, six speculative tokens on a
    draft accepted 3 times in 10), the default-configured engine
    walks every ladder to its hand-tuned end and then stays silent."""
    dcn_us = {"none": 60e3, "bf16": 30e3, "int8": 14e3, "int4": 11e3}
    gap_us = {1: 40e3, 2: 24e3, 4: 14e3, 8: 2e3}
    knobs = dict(DEFAULT_KNOBS, **{KNOB_MAX_INFLIGHT: 1,
                                   KNOB_SPEC_TOKENS: 6})
    eng = PolicyEngine()
    for w in range(80):
        d = eng.step(WindowSnapshot(
            index=w, knobs=dict(knobs), spec_acceptance=0.3,
            headroom_frac=0.5, headroom_bytes=8 << 30,
            legs={"dispatch": 10e3, "host": 1e3,
                  "dcn": dcn_us[knobs[KNOB_DCN_COMPRESS]],
                  "dispatch-gap": gap_us[knobs[KNOB_MAX_INFLIGHT]]}))
        if d is not None:
            knobs[d.knob] = d.value
    assert (knobs[KNOB_DCN_COMPRESS], knobs[KNOB_MAX_INFLIGHT],
            knobs[KNOB_SPEC_TOKENS]) == ("int4", 8, 1)
    assert max(d.window for d in eng.decisions) <= 60


# ---------------------------------------------------------------------------
# Pricing + env validation
# ---------------------------------------------------------------------------

def test_retune_delta_bytes_formulas():
    from horovod_tpu.memory.planner import retune_delta_bytes

    knobs = {KNOB_FUSION_THRESHOLD: 4 << 20, "spec_token_bytes": 1024}
    assert retune_delta_bytes(KNOB_FUSION_THRESHOLD, 4 << 20, 8 << 20,
                              knobs) == 2 * (4 << 20)
    assert retune_delta_bytes(KNOB_FUSION_THRESHOLD, 8 << 20, 4 << 20,
                              knobs) == -2 * (4 << 20)
    assert retune_delta_bytes(KNOB_MAX_INFLIGHT, 2, 4,
                              knobs) == 2 * (4 << 20)
    assert retune_delta_bytes(KNOB_SPEC_TOKENS, 3, 2, knobs) == -1024
    # Non-numeric knobs (the compression ladder) price as free.
    assert retune_delta_bytes(KNOB_DCN_COMPRESS, "none", "int8",
                              knobs) == 0


def test_validate_env_rejects_unknown_pin(monkeypatch):
    from horovod_tpu import tuning

    monkeypatch.setenv("HVD_TPU_TUNE_PIN", "dcn_compress,flux_capacitor")
    with pytest.raises(ValueError, match="flux_capacitor"):
        tuning.validate_env()


def test_validate_env_rejects_bad_window(monkeypatch):
    from horovod_tpu import tuning

    monkeypatch.setenv("HVD_TPU_TUNE_WINDOW", "soon")
    with pytest.raises(ValueError, match="HVD_TPU_TUNE_WINDOW"):
        tuning.validate_env()


# ---------------------------------------------------------------------------
# Actuation: markers ride the production response stream
# ---------------------------------------------------------------------------

def _drive_until_applied(hvd, st, seq, deadline_s=20.0):
    deadline = time.monotonic() + deadline_s
    i = 0
    while st.tuner._applied_seq < seq and time.monotonic() < deadline:
        hvd.allreduce(jnp.ones((4,)), name=f"tune.drive.{i}",
                      average=False)
        i += 1
    assert st.tuner._applied_seq >= seq, "marker was never applied"


def test_retune_marker_applies_at_cycle_boundary(monkeypatch, capfd):
    """End to end on the real single-process runtime: an enqueued
    decision rides the next coordinator tick as a RETUNE marker and is
    applied by the response executor — env knob set, megakernels
    flushed, the apply line logged, tuning.applied incremented."""
    import jax

    import horovod_tpu as hvd
    from horovod_tpu import telemetry
    from horovod_tpu.core import state as _state

    monkeypatch.setenv("HVD_TPU_TUNE", "1")
    monkeypatch.setenv("HVD_TPU_DCN_COMPRESS", "none")
    monkeypatch.setenv("HVD_TPU_MAX_INFLIGHT", "2")
    hvd.init(devices=jax.devices())
    try:
        st = _state.global_state()
        assert st.tuner is not None
        assert st.tuner is st.autotuner
        seq = st.tuner._enqueue(["dcn_compress=int8", "max_inflight=4"])
        _drive_until_applied(hvd, st, seq)
        assert os.environ["HVD_TPU_DCN_COMPRESS"] == "int8"
        assert os.environ["HVD_TPU_MAX_INFLIGHT"] == "4"
        assert telemetry.metrics()["tuning.applied"]["value"] >= 2
        err = capfd.readouterr().err
        assert f"rank 0 applied seq={seq} " \
               f"dcn_compress=int8 max_inflight=4" in err
    finally:
        hvd.shutdown()


def test_malformed_retune_token_is_skipped_with_diagnostic(monkeypatch,
                                                           capfd):
    """A marker carrying garbage must not wedge the drain tick: the bad
    token is skipped with a named diagnostic, the good token applies."""
    import jax

    import horovod_tpu as hvd
    from horovod_tpu.core import state as _state

    monkeypatch.setenv("HVD_TPU_TUNE", "1")
    monkeypatch.setenv("HVD_TPU_DCN_COMPRESS", "none")
    hvd.init(devices=jax.devices())
    try:
        st = _state.global_state()
        before = st.tick_seconds
        seq = st.tuner._enqueue(["dcn_compress=bogus",
                                 "cycle_time=0.004"])
        _drive_until_applied(hvd, st, seq)
        assert os.environ["HVD_TPU_DCN_COMPRESS"] == "none"  # untouched
        assert st.tick_seconds == pytest.approx(0.004)
        assert before != 0.004
        err = capfd.readouterr().err
        assert "skipping malformed retune 'dcn_compress=bogus'" in err
    finally:
        hvd.shutdown()


def test_inflight_window_resize_is_live():
    from horovod_tpu.parallel.overlap import _InflightWindow
    from horovod_tpu.tuning import actuation

    w = _InflightWindow(4)
    assert w in list(actuation._inflight_windows)
    actuation._apply_max_inflight(None, 1)
    assert w._depth == 1
    assert os.environ["HVD_TPU_MAX_INFLIGHT"] == "1"
    os.environ.pop("HVD_TPU_MAX_INFLIGHT", None)


def test_install_is_inert_without_opt_in(monkeypatch):
    import jax

    import horovod_tpu as hvd
    from horovod_tpu.core import state as _state

    monkeypatch.delenv("HVD_TPU_TUNE", raising=False)
    monkeypatch.delenv("HOROVOD_AUTOTUNE", raising=False)
    hvd.init(devices=jax.devices())
    try:
        st = _state.global_state()
        assert st.tuner is None and st.autotuner is None
    finally:
        hvd.shutdown()


# ---------------------------------------------------------------------------
# np=2 decision coherence: both ranks, same sequence, same positions
# ---------------------------------------------------------------------------

APPLY_RE = re.compile(r"\[hvd-tune\] rank (\d+) applied seq=(\d+) (.+)")


def _fake_state(rank, coordinator=None, response_cache=None):
    return SimpleNamespace(process_index=rank, tuner=None,
                           multiprocess=True, transport=None,
                           coordinator=coordinator,
                           response_cache=response_cache,
                           fusion_threshold_bytes=64 << 20,
                           tick_seconds=0.005)


def test_np2_ranks_apply_identical_decision_sequence(monkeypatch, capfd):
    """The fleet-coherence contract over real loopback transports: the
    rank-0 policy's decisions, broadcast as RETUNE markers, are applied
    by BOTH ranks in the same order at the same stream positions — the
    per-rank apply logs carry identical (position, seq, knobs)
    sequences."""
    from horovod_tpu.ops import cache as hvd_cache
    from horovod_tpu.ops import transport as T
    from horovod_tpu.ops.coordinator import Coordinator
    from horovod_tpu.ops.wire import ResponseType
    from horovod_tpu.tuning import actuation

    if os.environ.get("HVD_TPU_NO_SOCKETS") == "1":
        pytest.skip("sandbox without loopback sockets")
    monkeypatch.setenv("HVD_TPU_DCN_COMPRESS", "none")
    monkeypatch.setenv("HVD_TPU_MAX_INFLIGHT", "2")
    s = socket.socket()
    s.bind(("127.0.0.1", 0))
    port = s.getsockname()[1]
    s.close()
    coord = Coordinator(size=2, fusion_threshold=THRESHOLD,
                        cache=hvd_cache.ResponseCache(rank=0))
    holder = {}
    th = threading.Thread(
        target=lambda: holder.__setitem__(
            "ctrl", T.ControllerTransport(coord, 2, port)),
        daemon=True)
    th.start()
    time.sleep(0.1)
    worker = T.WorkerTransport("127.0.0.1", port, 1)
    th.join(timeout=10.0)
    ctrl = holder["ctrl"]
    st0 = _fake_state(0, coordinator=coord)
    st1 = _fake_state(1, response_cache=hvd_cache.ResponseCache(rank=1))
    try:
        # The REAL rule table drives the decisions: a dcn-dominated
        # window feed, each decision broadcast the moment it fires.
        eng = PolicyEngine(PolicyConfig(sustain=2, cooldown=1))
        knobs = dict(DEFAULT_KNOBS)
        n_sent = 0
        for i in range(8):
            d = eng.step(snap(i, DCN_LEGS, knobs))
            if d is None:
                continue
            knobs[d.knob] = d.value
            marker = actuation.make_marker([d.wire()], d.seq)
            ctrl.broadcast_responses([marker])
            actuation.apply_marker(marker, st0)  # rank 0's executor
            n_sent += 1
        assert n_sent >= 2
        applied = 0
        deadline = time.monotonic() + 10.0
        while applied < n_sent and time.monotonic() < deadline:
            resps = worker.poll_responses()
            if resps is None:
                time.sleep(0.005)
                continue
            for r in resps:
                if r.response_type == ResponseType.RETUNE:
                    actuation.apply_marker(r, st1)  # rank 1's executor
                    applied += 1
        assert applied == n_sent, "worker missed a marker"
        err = capfd.readouterr().err
        by_rank = {0: [], 1: []}
        for line in err.splitlines():
            m = APPLY_RE.match(line.strip())
            if m:
                by_rank[int(m.group(1))].append(
                    (m.group(2), m.group(3)))
        assert len(by_rank[0]) == n_sent
        # Identical (seq, knob=value) sequences at identical positions.
        assert by_rank[0] == by_rank[1]
        # And the env digests agree after the full sequence (the gauge
        # the production controller's fleet verification compares).
        assert actuation.env_digest() == actuation.env_digest()
    finally:
        worker.close()
        ctrl.close()
        coord.close()
