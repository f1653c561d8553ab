"""hvd-trace: spans, clock alignment, fleet merge, analyzer, watcher.

Covers the ISSUE 10 tentpole in-process (the np=2 integration legs live
in tests/test_multiprocess.py) plus the satellites: the timeline's
strictly-valid-JSON close, the flight-recorder metrics tail, the trace
metrics on the exporter, and the clock-offset estimator under chaos
transport delay/dup with a reconnect re-convergence.
"""

import glob
import json
import os
import socket
import subprocess
import sys
import threading
import time

import pytest

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

THRESHOLD = 64 << 20


# ---------------------------------------------------------------------------
# Satellite: timeline emits strictly valid JSON; close() is idempotent
# under a concurrent instant() writer
# ---------------------------------------------------------------------------

def test_timeline_close_emits_strictly_valid_json(tmp_path):
    from horovod_tpu.utils.timeline import Timeline

    path = str(tmp_path / "tl.json")
    tl = Timeline(path)
    tl.negotiate_start("t0", "allreduce")
    tl.negotiate_rank_ready("t0", 1)
    tl.negotiate_end("t0")
    tl.cache_counter(3, 1)
    tl.close()
    events = json.loads(open(path).read())  # parse-it-back: no comma
    assert isinstance(events, list) and len(events) >= 5
    assert events[-1]["name"] == "shutdown"


def test_timeline_empty_file_is_valid_json(tmp_path):
    from horovod_tpu.utils.timeline import Timeline

    path = str(tmp_path / "tl.json")
    Timeline(path).close()
    events = json.loads(open(path).read())
    assert [e["name"] for e in events] == ["shutdown"]


def test_timeline_close_idempotent_under_concurrent_instant(tmp_path):
    from horovod_tpu.utils.timeline import Timeline

    path = str(tmp_path / "tl.json")
    tl = Timeline(path)
    stop = threading.Event()

    def hammer():
        i = 0
        while not stop.is_set():
            tl.instant("t", f"mark.{i}")  # post-close: silent no-op
            i += 1

    th = threading.Thread(target=hammer, daemon=True)
    th.start()
    time.sleep(0.05)
    tl.close()
    tl.close()  # idempotent
    stop.set()
    th.join(timeout=5.0)
    tl.instant("t", "after")  # still a no-op, still no crash
    events = json.loads(open(path).read())  # file stayed valid JSON
    assert events[-1]["name"] == "shutdown"


def test_timeline_events_carry_trace_context(tmp_path):
    import horovod_tpu.trace as trace
    from horovod_tpu.utils.timeline import Timeline

    trace.reset_run(rank=0)
    trace.set_step(7)
    path = str(tmp_path / "tl.json")
    tl = Timeline(path)
    tl.negotiate_start("t0", "allreduce")
    tl.negotiate_end("t0")
    tl.close()
    events = json.loads(open(path).read())
    starts = [e for e in events if e.get("ph") == "B"]
    assert starts and starts[0]["args"]["step"] == 7
    assert "cycle" in starts[0]["args"]


# ---------------------------------------------------------------------------
# Satellite: flight dumps carry a compact metrics tail
# ---------------------------------------------------------------------------

def test_flight_dump_appends_metrics_tail(tmp_path, monkeypatch):
    import horovod_tpu.telemetry as tel
    from horovod_tpu.telemetry import flight

    monkeypatch.setenv("HVD_TPU_FLIGHT_DIR", str(tmp_path))
    tel.counter("collective.submitted").inc(0)  # ensure key exists
    flight.record("unit", "metrics-tail")
    path = flight.dump("metrics-tail-test")
    assert path is not None
    payload = json.loads(open(path).read())
    tail = payload["metrics"]
    assert "collective.submitted" in tail
    # Histograms compact to count+sum; counters/gauges to bare values.
    for v in tail.values():
        assert isinstance(v, (int, float, dict))
        if isinstance(v, dict):
            assert set(v) == {"count", "sum"}


def test_flight_metrics_provider_failure_never_breaks_dump(tmp_path,
                                                           monkeypatch):
    from horovod_tpu.telemetry import flight

    monkeypatch.setenv("HVD_TPU_FLIGHT_DIR", str(tmp_path))
    old = flight._metrics_provider
    flight.set_metrics_provider(lambda: 1 / 0)
    try:
        path = flight.dump("provider-broken")
        assert path is not None
        assert "metrics" not in json.loads(open(path).read())
    finally:
        flight.set_metrics_provider(old)


# ---------------------------------------------------------------------------
# Span buffer + context propagation
# ---------------------------------------------------------------------------

def test_span_buffer_records_context_and_counts():
    import horovod_tpu.telemetry as tel
    import horovod_tpu.trace as trace

    trace.reset_run(rank=0)
    trace.set_step(3)
    before = tel.metrics().get("trace.spans", {}).get("value", 0)
    t0 = time.monotonic()
    trace.span("unit.work", "host", t0, t0 + 0.001, args={"k": 1})
    evs = trace.export_events()
    assert evs[-1]["name"] == "unit.work"
    assert evs[-1]["args"]["step"] == 3
    assert evs[-1]["args"]["cycle"] == 0
    assert evs[-1]["args"]["k"] == 1
    assert evs[-1]["dur"] == pytest.approx(1000.0, rel=0.2)
    assert tel.metrics()["trace.spans"]["value"] == before + 1


def test_span_buffer_is_bounded_and_gated():
    import horovod_tpu.trace as trace

    trace.reset_run(rank=0)
    cap = trace._state._events.maxlen
    for i in range(cap + 50):
        trace.instant(f"e{i}", "host")
    assert len(trace.export_events()) == cap
    trace.set_enabled(False)
    try:
        n = len(trace.export_events())
        trace.instant("off", "host")
        assert len(trace.export_events()) == n  # disabled = no record
    finally:
        trace.set_enabled(True)


def test_ctx_trailer_roundtrip_and_response_list_compat():
    import horovod_tpu.trace as trace
    from horovod_tpu.ops import wire

    trace.reset_run(rank=0, trace_id=77)
    trace.set_step(5)
    trace.observe_ctx(5, 9, 77)
    resps = [wire.Response(wire.ResponseType.ALLREDUCE, ["x"],
                           devices=[-1], tensor_sizes=[])]
    payload = wire.pack_response_list(resps) + trace.pack_ctx()
    # Old parser: the self-delimiting list ignores the trailer.
    got = wire.unpack_response_list(payload)
    assert got[0].tensor_names == ["x"]
    # New parser: reads the trailer after the consumed offset.
    got2, off = wire.unpack_response_list_ex(payload)
    step, cycle, tid = trace.unpack_ctx(payload, off)
    assert (step, cycle, tid) == (5, 9, 77)
    # A trailer-less payload parses as no context, not garbage.
    assert trace.unpack_ctx(wire.pack_response_list(resps), off) is None


# ---------------------------------------------------------------------------
# Clock-offset estimation (unit + under chaos over real sockets)
# ---------------------------------------------------------------------------

def test_offset_estimator_min_rtt_filter():
    from horovod_tpu.trace.clock import OffsetEstimator

    est = OffsetEstimator()
    # True offset +2.0 s; clean sample (rtt 1 ms) vs delayed samples
    # whose asymmetric queueing skews the midpoint estimate badly.
    assert est.offset() is None and est.error_bound() is None
    est.add(10.0, 12.0505, 10.101)            # delayed: rtt ~101 ms
    est.add(20.0, 22.0005, 20.001)            # clean:   rtt   1 ms
    est.add(30.0, 32.0805, 30.161)            # delayed: rtt ~161 ms
    assert est.offset() == pytest.approx(2.0, abs=1e-3)
    assert est.error_bound() == pytest.approx(0.0005, abs=1e-4)
    assert est.count == 3
    est.reset()
    assert est.offset() is None


def test_offset_estimator_rejects_causally_impossible_samples():
    from horovod_tpu.trace.clock import OffsetEstimator

    est = OffsetEstimator()
    assert est.add(10.0, 12.0, 9.9) is None  # t2 < t0: replay artifact
    assert est.offset() is None


@pytest.fixture()
def cp_pair():
    """Controller + worker transport over loopback (the test_chaos
    harness shape) — enough control plane for ping/pong and FRAME_TRACE
    without a jax runtime."""
    from horovod_tpu.ops import transport as T
    from horovod_tpu.ops.coordinator import Coordinator

    if os.environ.get("HVD_TPU_NO_SOCKETS") == "1":
        pytest.skip("sandbox without loopback sockets")
    s = socket.socket()
    s.bind(("127.0.0.1", 0))
    port = s.getsockname()[1]
    s.close()
    coord = Coordinator(size=2, fusion_threshold=THRESHOLD)
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
    yield ctrl, worker
    worker.close()
    ctrl.close()
    coord.close()


def _wait_offset(ctrl, rank=1, deadline=5.0):
    end = time.monotonic() + deadline
    while time.monotonic() < end:
        offs = ctrl.clock.offsets()
        if rank in offs:
            return offs[rank]
        time.sleep(0.01)
    raise AssertionError(f"no clock offset for rank {rank}: "
                         f"{ctrl.clock.sample_counts()}")


def test_clock_offset_same_process_is_near_zero(cp_pair):
    """Both transports share one monotonic clock, so the estimate must
    sit near zero — and the per-peer gauge must be exported."""
    import horovod_tpu.telemetry as tel

    ctrl, _worker = cp_pair
    ctrl.measure_clock_offsets(probes=4, timeout=5.0)
    off = _wait_offset(ctrl)
    assert abs(off) < 0.05, off
    g = tel.metrics().get("trace.clock_offset_seconds.rank1")
    assert g is not None and abs(g["value"]) < 0.05, g


def test_clock_offset_bounded_under_chaos_delay_dup_and_reconnects(
        cp_pair, monkeypatch):
    """ISSUE 10 satellite: with transport delay + dup clauses armed the
    min-RTT filter keeps the estimate within bounds (true offset ~0
    in-process, injected delays are 80 ms), and after a hard
    connection reset + session resume the estimator RE-CONVERGES from
    a fresh window."""
    import horovod_tpu.chaos as chaos
    import horovod_tpu.telemetry as tel
    from horovod_tpu.ops import transport as T

    ctrl, worker = cp_pair
    monkeypatch.setenv(
        "HVD_TPU_FAULTS",
        "transport.delay:p=0.5:count=1000:delay=0.08;"
        "transport.dup:p=0.3:count=1000@11")
    chaos.reload()
    try:
        for _ in range(6):
            ctrl.ping_peers()
            time.sleep(0.02)
        off = _wait_offset(ctrl)
        # An unfiltered mean over 80 ms asymmetric delays would sit
        # tens of ms out; the min-RTT sample keeps it tight.
        assert abs(off) < 0.02, off
        counts0 = ctrl.clock.sample_counts().get(1, 0)
        assert counts0 >= 1

        before = tel.metrics().get("transport.reconnects",
                                   {}).get("value", 0)
        T._hard_close(worker._sock)  # the fault
        deadline = time.monotonic() + 10.0
        while time.monotonic() < deadline:
            now = tel.metrics().get("transport.reconnects",
                                    {}).get("value", 0)
            if now > before:
                break
            time.sleep(0.02)
        else:
            raise AssertionError("worker never reconnected")
        # Resume reset the window; fresh probes re-converge it.
        for _ in range(6):
            ctrl.ping_peers()
            time.sleep(0.02)
        deadline = time.monotonic() + 5.0
        while time.monotonic() < deadline:
            if ctrl.clock.sample_counts().get(1, 0) >= 1 \
                    and 1 in ctrl.clock.offsets():
                break
            time.sleep(0.02)
        off2 = ctrl.clock.offsets()[1]
        assert abs(off2) < 0.02, off2
    finally:
        monkeypatch.delenv("HVD_TPU_FAULTS", raising=False)
        chaos.reload()


def test_controller_submit_gives_rank0_arrival_baseline(cp_pair):
    """The minimal real fleet (controller + ONE worker) must produce a
    live skew signal: rank 0's own submit stamps the cycle baseline,
    the worker's request-batch trailer stamps its arrival — without
    the rank-0 feed every cycle would have a single entry and
    StragglerWatch would be silently inert."""
    import horovod_tpu.trace as trace
    from horovod_tpu.ops import wire
    from horovod_tpu.trace import watch

    ctrl, worker = cp_pair
    trace.reset_run(rank=0)
    trace.set_step(2)
    watch.tracker.clear()

    def req(rank):
        return wire.Request(rank, wire.RequestType.ALLREDUCE,
                            wire.DataType.FLOAT32, "sk.x", -1, -1,
                            (4,), wire.ReduceOp.SUM, 0, ())

    ctrl.submit(req(0))           # rank 0: local, never on the wire
    worker.submit(req(1))
    worker.flush_requests()       # carries the trace trailer
    deadline = time.monotonic() + 5.0
    while time.monotonic() < deadline:
        skews = watch.tracker.skew_by_rank()
        if 0 in skews and 1 in skews:
            break
        time.sleep(0.01)
    else:
        raise AssertionError(watch.tracker.skew_by_rank())
    assert skews[0] == pytest.approx(0.0)   # rank 0 is the baseline
    assert skews[1] >= 0.0
    # Dedup: a second rank-0 submit in the same cycle adds nothing.
    assert watch.tracker.note(0, 2, 0, time.monotonic()) is False


def test_collect_traces_pulls_worker_buffer(cp_pair):
    import horovod_tpu.trace as trace

    ctrl, _worker = cp_pair
    trace.reset_run(rank=0)
    t0 = time.monotonic()
    trace.span("worker.side", "host", t0, t0 + 0.001)
    per_rank = ctrl.collect_traces([{"name": "ctrl.side"}], timeout=10.0)
    assert set(per_rank) == {0, 1}
    assert per_rank[0][0]["name"] == "ctrl.side"
    # The worker answered from ITS buffer (same process here, so the
    # span we just recorded is visible through the wire round trip).
    assert any(e.get("name") == "worker.side" for e in per_rank[1])


# ---------------------------------------------------------------------------
# Merge + analyzer
# ---------------------------------------------------------------------------

def _span(rank, name, cat, t0_us, dur_us, step, cycle, **extra):
    return {"name": name, "cat": cat, "ph": "X", "ts": float(t0_us),
            "dur": float(dur_us), "pid": rank,
            "args": {"step": step, "cycle": cycle, **extra}}


def _arrival(rank, t_us, step, cycle):
    return {"name": "BATCH_ARRIVAL", "cat": "negotiate", "ph": "i",
            "s": "t", "ts": float(t_us), "pid": 0,
            "args": {"step": step, "cycle": cycle, "rank": rank}}


def test_merge_events_applies_clock_offsets():
    from horovod_tpu.trace.merge import merge_events

    per_rank = {0: [{"name": "a", "cat": "dispatch", "ph": "X",
                     "ts": 1000.0, "dur": 10.0, "args": {}}],
                1: [{"name": "b", "cat": "dispatch", "ph": "X",
                     "ts": 501000.0, "dur": 10.0, "args": {}}]}
    merged = merge_events(per_rank, offsets={1: 0.5})  # rank1 +0.5 s
    xs = {e["pid"]: e for e in merged if e.get("ph") == "X"}
    assert xs[0]["ts"] == 1000.0
    assert xs[1]["ts"] == pytest.approx(1000.0)  # aligned onto rank 0
    names = [e for e in merged if e.get("ph") == "M"]
    assert any(e["name"] == "process_name" and e["pid"] == 1
               for e in names)


def _slow_rank_events():
    """Synthetic 2-rank fleet: rank 1 is input-bound — its prefetch
    wait delays every cycle's arrival."""
    evs = []
    for cycle in range(1, 4):
        step = 1
        base = cycle * 100_000.0
        # rank 1 stalls on its loader, then arrives late.
        evs.append(_span(1, "prefetch.wait", "host", base, 30_000.0,
                         step, cycle))
        evs.append(_arrival(0, base + 1_000.0, step, cycle))
        evs.append(_arrival(1, base + 31_000.0, step, cycle))
        for rank in (0, 1):
            evs.append(_span(rank, "negotiate.wait", "negotiate",
                             base + 1_000.0 + rank * 30_000.0,
                             31_000.0 - rank * 30_000.0, step, cycle))
            d0 = base + 32_000.0
            evs.append(_span(rank, "execute/allreduce", "dispatch",
                             d0, 5_000.0, step, cycle))
            evs.append(_span(rank, "megakernel/psum", "collective",
                             d0 + 1_000.0, 3_000.0, step, cycle,
                             wire_bytes=1000, dcn_bytes=250))
    return evs


def test_analyzer_names_slow_rank_and_category():
    from horovod_tpu.trace.analyze import analyze, render

    report = analyze(_slow_rank_events())
    assert report["ranks"] == [0, 1]
    # Every cycle's straggler is rank 1, blamed on its host leg.
    assert report["stragglers"] == {"1": 3}
    for c in report["cycles"]:
        assert c["straggler"] == 1, c
        assert c["blame"] == "host", c
        assert c["skew_us"] == pytest.approx(30_000.0)
    # The launch spans decompose: pack (1 ms) + unpack (1 ms) around a
    # 3 ms collective whose DCN share is 25%.
    attr = report["attribution_us"]
    assert attr["host"] == pytest.approx(3 * 30_000.0)
    assert attr["pack"] == pytest.approx(3 * 1_000.0)
    assert attr["unpack"] == pytest.approx(3 * 1_000.0)
    assert attr["dcn"] == pytest.approx(3 * 750.0)
    assert attr["collective"] == pytest.approx(3 * 2_250.0)
    text = render(report)
    assert "rank 1 led 3 cycle(s); dominant blame: host" in text


def test_analyzer_is_deterministic_across_replays(tmp_path):
    """The CI trace-analysis gate: two runs over one file are
    byte-identical."""
    from horovod_tpu.trace.analyze import analyze

    events = _slow_rank_events()
    a = json.dumps(analyze(events), sort_keys=True)
    b = json.dumps(analyze(list(events)), sort_keys=True)
    assert a == b


def test_analyzer_handles_bare_timeline_without_spans(tmp_path):
    from horovod_tpu.trace.analyze import analyze, load_trace

    path = tmp_path / "tl.json"
    path.write_text(json.dumps([{"ph": "B", "ts": 1, "pid": 0,
                                 "name": "NEGOTIATE_ALLREDUCE"}]))
    report = analyze(load_trace(str(path)))
    assert report["total_spans"] == 0
    assert report["cycles"] == []


def test_cli_reports_and_writes_json(tmp_path):
    trace_path = tmp_path / "fleet.json"
    trace_path.write_text(json.dumps(
        {"traceEvents": _slow_rank_events()}))
    out_json = tmp_path / "report.json"
    proc = subprocess.run(
        [sys.executable, "-m", "horovod_tpu.trace", str(trace_path),
         "--json", str(out_json)],
        capture_output=True, text=True,
        cwd=os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
    assert proc.returncode == 0, proc.stderr
    assert "dominant blame: host" in proc.stdout
    report = json.loads(out_json.read_text())
    assert report["stragglers"] == {"1": 3}


def test_cli_unparseable_file_exits_2(tmp_path):
    bad = tmp_path / "bad.json"
    bad.write_text("{nope")
    proc = subprocess.run(
        [sys.executable, "-m", "horovod_tpu.trace", str(bad)],
        capture_output=True, text=True,
        cwd=os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
    assert proc.returncode == 2
    assert "error:" in proc.stderr


# ---------------------------------------------------------------------------
# StragglerWatch
# ---------------------------------------------------------------------------

def test_straggler_watch_fires_after_n_consecutive_steps(capfd):
    import horovod_tpu.telemetry as tel
    from horovod_tpu.trace.watch import SkewTracker, StragglerWatch

    w = StragglerWatch(threshold=0.01, patience=3,
                       tracker_=SkewTracker())
    before = tel.metrics().get("trace.straggler_warnings",
                               {}).get("value", 0)
    skews = {1: 0.002, 2: 0.05}
    assert w.check(skews) is None
    assert w.check(skews) is None
    fired = w.check(skews)
    assert fired is not None and fired[0]["rank"] == 2
    err = capfd.readouterr().err
    assert "rank 2" in err and "horovod_tpu.trace" in err
    assert tel.metrics()["trace.straggler_warnings"]["value"] == \
        before + 1
    # A healthy step resets the streak.
    assert w.check(skews) is None
    assert w.check({1: 0.002, 2: 0.001}) is None
    assert w.check(skews) is None
    assert w.check(skews) is None


def test_straggler_watch_names_every_simultaneous_straggler(capfd):
    from horovod_tpu.trace.watch import SkewTracker, StragglerWatch

    w = StragglerWatch(threshold=0.01, patience=2,
                       tracker_=SkewTracker())
    skews = {2: 0.05, 5: 0.09}
    assert w.check(skews) is None
    fired = w.check(skews)
    assert [f["rank"] for f in fired] == [2, 5]  # BOTH named
    err = capfd.readouterr().err
    assert "rank 2" in err and "rank 5" in err


def test_straggler_watch_reads_the_arrival_tracker():
    from horovod_tpu.trace.watch import SkewTracker, StragglerWatch

    tr = SkewTracker()
    t0 = 100.0
    for cycle in range(8):
        tr.note(0, 1, cycle, t0 + cycle)
        tr.note(1, 1, cycle, t0 + cycle + 0.2)  # rank 1 lags 200 ms
    skews = tr.skew_by_rank()
    assert skews[1] == pytest.approx(0.2)
    assert skews[0] == pytest.approx(0.0)
    w = StragglerWatch(threshold=0.1, patience=2, tracker_=tr)
    assert w.check() is None
    assert w.check()[0]["rank"] == 1


def test_straggler_watch_rejects_nonsense():
    from horovod_tpu.trace.watch import StragglerWatch

    with pytest.raises(ValueError):
        StragglerWatch(threshold=0.0)
    with pytest.raises(ValueError):
        StragglerWatch(patience=0)


# ---------------------------------------------------------------------------
# Exporter surface + single-process end-to-end
# ---------------------------------------------------------------------------

def test_trace_metrics_render_in_prometheus_text():
    import horovod_tpu.telemetry as tel
    import horovod_tpu.trace as trace
    from horovod_tpu.telemetry.exporter import prometheus_text

    trace.reset_run(rank=0)
    t0 = time.monotonic()
    trace.span("unit", "host", t0, t0)
    tel.gauge("trace.clock_offset_seconds.rank1").set(0.001)
    text = prometheus_text(tel.metrics())
    assert "hvd_trace_spans" in text
    assert "hvd_trace_clock_offset_seconds_rank1" in text
    assert "hvd_trace_straggler_warnings" in text


def test_single_process_fleet_trace_end_to_end(hvd2, tmp_path):
    """dump_fleet_trace + analyzer over a REAL (single-process) run:
    spans land with step/cycle context, merge writes a loadable file,
    the analyzer attributes the cycles."""
    import jax.numpy as jnp

    import horovod_tpu.trace as trace
    from horovod_tpu.trace.analyze import analyze, load_trace

    trace.set_step(4)
    for i in range(2):
        hvd2.allreduce(jnp.ones(8), average=False, name=f"tr.{i}")
    path = hvd2.dump_fleet_trace(str(tmp_path / "fleet.json"))
    data = json.load(open(path))
    assert data["metadata"]["format"] == "hvd-fleet-trace-v1"
    every = [e for e in data["traceEvents"] if e.get("ph") == "X"]
    # Set-up's own region (init.megakernel_warm_start, step 0) rides
    # the same buffer; the analyzer's leg model leaves it out.
    assert {e["cat"] for e in every} >= {"negotiate", "dispatch", "init"}
    xs = [e for e in every if e["cat"] != "init"]
    assert xs and all(e["pid"] == 0 for e in xs)
    assert all(e["args"]["step"] == 4 for e in xs)
    report = analyze(load_trace(path))
    assert report["total_spans"] == len(xs)
    assert len(report["cycles"]) >= 1
    assert sum(report["attribution_us"].values()) > 0


# ---------------------------------------------------------------------------
# Regions: one span call, three sinks, one pair of clock reads (ISSUE 24)
# ---------------------------------------------------------------------------

class _CountedClock:
    """Stands in for the ``time`` module inside horovod_tpu.trace: every
    ``monotonic()`` read is counted and advances by one millisecond."""

    def __init__(self):
        self.reads = 0

    def monotonic(self):
        self.reads += 1
        return 100.0 + self.reads * 1e-3


class _RecordedAnnotation:
    """Stands in for jax.profiler.TraceAnnotation."""

    made = []
    recording = True

    @classmethod
    def is_enabled(cls):
        return cls.recording

    def __init__(self, name, **kw):
        self.name, self.kw, self.late = name, kw, {}
        type(self).made.append(self)

    def __enter__(self):
        return self

    def __exit__(self, *exc):
        return False

    def set_metadata(self, **kw):
        self.late.update(kw)


@pytest.fixture()
def region_sinks(monkeypatch):
    import horovod_tpu.trace as trace

    trace.reset_run(rank=0)
    trace.set_enabled(True)
    clock = _CountedClock()
    _RecordedAnnotation.made = []
    _RecordedAnnotation.recording = True
    monkeypatch.setattr(trace, "time", clock)
    monkeypatch.setattr(trace, "_TraceAnnotation", _RecordedAnnotation)
    yield trace, clock, _RecordedAnnotation.made
    trace.set_enabled(True)
    trace.clear()


def _hist(name):
    import horovod_tpu.telemetry as telemetry

    snap = telemetry.metrics().get("trace.span_seconds." + name, {})
    return snap.get("count", 0), snap.get("sum", 0.0)


def test_region_nests_and_feeds_three_sinks_from_one_pair_of_reads(
        region_sinks):
    trace, clock, made = region_sinks
    outer = trace.region("t24.outer", "step")
    inner = trace.region("t24.inner", "stream")
    n0, s0 = _hist("t24.outer")
    trace.set_step(7)
    with outer(bucket=3) as o:
        with inner() as i:
            pass
    assert clock.reads == 4              # two regions, one pair each
    child, parent = trace.export_events()[-2:]
    assert (child["name"], parent["name"]) == ("t24.inner", "t24.outer")
    assert child["args"]["parent"] == "t24.outer"
    assert "parent" not in parent["args"]
    assert parent["args"]["step"] == 7 and parent["args"]["bucket"] == 3
    assert parent["cat"] == "step" and parent["ph"] == "X"
    # The parent's duration covers the child's.
    assert parent["ts"] <= child["ts"]
    assert (parent["ts"] + parent["dur"]
            >= child["ts"] + child["dur"])
    # Ring, histogram and annotation read the SAME two stamps.
    assert parent["ts"] == pytest.approx(o.t0 * 1e6)
    assert parent["dur"] == pytest.approx((o.t1 - o.t0) * 1e6)
    n1, s1 = _hist("t24.outer")
    assert n1 == n0 + 1 and s1 - s0 == pytest.approx(o.seconds)
    ann = {a.name: a for a in made}
    assert set(ann) == {"hvd:t24.outer", "hvd:t24.inner"}
    assert ann["hvd:t24.outer"].kw["mono_us"] == int(o.t0 * 1e6)
    assert ann["hvd:t24.outer"].kw["step"] == 7
    assert ann["hvd:t24.outer"].kw["bucket"] == 3
    assert ann["hvd:t24.inner"].kw["parent"] == "t24.outer"
    assert ann["hvd:t24.inner"].kw["mono_us"] == int(i.t0 * 1e6)


def test_region_off_is_one_flag_check_and_builds_nothing(region_sinks):
    trace, clock, made = region_sinks
    plain = trace.region("t24.off", "step")
    timed = trace.region("t24.off_timed", "step", timed=True)
    n0, _ = _hist("t24.off")
    spans0 = len(trace.export_events())
    trace.set_enabled(False)
    with plain(bucket=1) as a:
        a.note(late=1)
        with plain() as b:
            b.cancel()
    assert a is b                        # the shared no-op, built once
    assert clock.reads == 0 and made == []
    assert len(trace.export_events()) == spans0
    assert _hist("t24.off")[0] == n0
    # A timed site still owes its own histogram a duration: the two
    # clock reads and nothing else.
    with timed() as t:
        pass
    assert clock.reads == 2 and made == []
    assert t.seconds == pytest.approx(1e-3)
    assert len(trace.export_events()) == spans0
    assert _hist("t24.off_timed")[0] == 0


def test_region_env_off_builds_nothing(monkeypatch):
    import horovod_tpu.trace as trace

    monkeypatch.setenv("HVD_TPU_TRACE", "0")
    trace.reset_run(rank=0)
    try:
        r = trace.region("t24.env_off", "step")
        with r() as a:
            pass
        assert a is trace._OFF and trace.export_events() == []
    finally:
        monkeypatch.delenv("HVD_TPU_TRACE")
        trace.reset_run(rank=0)


def test_region_note_and_cancel(region_sinks):
    trace, _clock, made = region_sinks
    tick = trace.region("t24.tick", "negotiate")
    n0, _ = _hist("t24.tick")
    with tick() as r:
        trace.next_cycle()               # the context as of the END
        r.note(responses=2)
    ev = trace.export_events()[-1]
    assert ev["args"]["responses"] == 2 and ev["args"]["cycle"] == 1
    assert made[-1].late == {"responses": 2}
    with tick() as r:
        r.cancel()                       # an empty tick keeps nothing
    assert trace.export_events()[-1] == ev
    assert _hist("t24.tick")[0] == n0 + 1
    assert len(made) == 2                # the profiler saw both
    assert all(type(e) is dict for e in trace.export_events())


def test_region_builds_no_annotation_while_no_profiler_records(
        region_sinks):
    """The default run (profiler off) pays for the span and the
    histogram only; the annotation exists while a session records."""
    trace, clock, made = region_sinks
    r = trace.region("t24.quiet", "step")
    n0, _ = _hist("t24.quiet")
    _RecordedAnnotation.recording = False
    with r(bucket=2) as o:
        o.note(late=1)
    assert made == [] and clock.reads == 2
    ev = trace.export_events()[-1]
    assert ev["name"] == "t24.quiet" and ev["args"]["bucket"] == 2
    assert ev["args"]["late"] == 1 and "mono_us" not in ev["args"]
    assert ev["ts"] == pytest.approx(o.t0 * 1e6)
    assert _hist("t24.quiet")[0] == n0 + 1
    _RecordedAnnotation.recording = True
    with r():
        pass
    assert [a.name for a in made] == ["hvd:t24.quiet"]


def test_region_parent_stack_is_per_thread(region_sinks):
    trace, _clock, _made = region_sinks
    outer = trace.region("t24.thread_outer", "step")
    inner = trace.region("t24.thread_inner", "step")
    seen = {}

    def other():
        with inner():
            pass
        seen["ev"] = trace.export_events()[-1]

    with outer():
        t = threading.Thread(target=other)
        t.start()
        t.join()
    assert seen["ev"]["name"] == "t24.thread_inner"
    assert "parent" not in seen["ev"]["args"]


def test_region_family_builds_each_name_once():
    import horovod_tpu.trace as trace

    fam = trace.RegionFamily("t24fam/", "dispatch", timed=True)
    a = fam["allreduce"]
    assert fam["allreduce"] is a and a.name == "t24fam/allreduce"
    assert a.timed and a.annotation == "hvd:t24fam/allreduce"


def test_analyzer_leaves_enclosing_regions_out_of_the_leg_model():
    """A step region ENCLOSES its cycle's spans: counted as a leg span
    it would stretch the cycle's wall time into a dispatch-gap."""
    from horovod_tpu.trace.analyze import analyze, window_legs

    evs = [_span(0, "execute/allreduce", "dispatch", 1000, 100, 1, 1),
           _span(0, "megakernel/allreduce", "collective", 1010, 80, 1, 1),
           _span(0, "step/stream", "step", 0, 50000, 1, 1),
           _span(0, "stream.drain", "stream", 900, 300, 1, 1)]
    legs = window_legs(evs)
    assert legs["dispatch-gap"] == 0.0
    assert legs["collective"] == 80.0
    assert analyze(evs)["total_spans"] == 2


def test_profiler_trace_holds_stream_step_and_engine_iteration(
        hvd, tmp_path):
    """On the profiler's clock: a trace taken on the CPU mesh around
    one stream step and one engine iteration holds the ``hvd:`` events
    in the same .xplane.pb, with ``step``/``iter`` and ``mono_us``."""
    import jax
    import jax.numpy as jnp
    import optax
    from jax.profiler import ProfileData

    import horovod_tpu.trace as trace
    from horovod_tpu.models.transformer import (TransformerConfig,
                                                init_transformer)
    from horovod_tpu.parallel.training import make_train_step, shard_batch
    from horovod_tpu.serving import InferenceEngine

    def loss(params, batch):
        x, y = batch
        return jnp.mean((x @ params["w"] - y) ** 2)

    params = {"w": jnp.ones((16, 16))}
    batch = shard_batch((jnp.ones((8 * hvd.size(), 16)),
                         jnp.zeros((8 * hvd.size(), 16))))
    opt = optax.sgd(0.1)
    step = make_train_step(loss, opt, donate=False, overlap="on")
    state = opt.init(params)
    out = step(params, state, batch)          # build + negotiate
    jax.block_until_ready(out[0])
    cfg = TransformerConfig(vocab_size=97, d_model=32, n_heads=2,
                            n_layers=1, d_ff=64, max_seq_len=32)
    eng = InferenceEngine(init_transformer(jax.random.PRNGKey(0), cfg),
                          cfg, max_slots=2, page_size=8, capacity=16)
    eng.warm_start()
    eng.generate([1, 2, 3], max_new_tokens=2)   # compile outside

    t_before = time.monotonic()
    jax.profiler.start_trace(str(tmp_path))
    try:
        out = step(out[0], out[1], batch)
        jax.block_until_ready(out[0])
        traced_step = trace.current_step()
        eng.submit([4, 5, 6], max_new_tokens=3)
        eng.step()
        traced_iter = eng._iter
    finally:
        jax.profiler.stop_trace()
    t_after = time.monotonic()
    eng.run_until_idle()

    files = glob.glob(str(tmp_path / "**" / "*.xplane.pb"), recursive=True)
    assert len(files) == 1
    found = {}
    for plane in ProfileData.from_file(files[0]).planes:
        if not plane.name.startswith("/host:"):
            continue
        for line in plane.lines:
            for ev in line.events:
                if ev.name.startswith("hvd:"):
                    found.setdefault(ev.name, []).append(dict(ev.stats))
    for name in ("hvd:step/stream", "hvd:stream.backward",
                 "hvd:stream.submit", "hvd:stream.drain",
                 "hvd:execute/allreduce", "hvd:megakernel/psum",
                 "hvd:stream.take", "hvd:stream.apply"):
        assert name in found, sorted(found)
        assert all(s["step"] == traced_step for s in found[name]), name
    for name in ("hvd:serve.iteration", "hvd:serve.admit",
                 "hvd:serve.prefill", "hvd:serve.ensure",
                 "hvd:serve.tables", "hvd:serve.launch",
                 "hvd:serve.logits_wait", "hvd:serve.sample"):
        assert name in found, sorted(found)
        assert all(s["iter"] == traced_iter for s in found[name]), name
    # mono_us places every event on time.monotonic.
    for stats in found.values():
        for s in stats:
            assert t_before * 1e6 - 1 <= s["mono_us"] <= t_after * 1e6
    assert found["hvd:stream.drain"][0]["parent"] == "step/stream"
    assert found["hvd:megakernel/psum"][0]["parent"] == "execute/allreduce"
