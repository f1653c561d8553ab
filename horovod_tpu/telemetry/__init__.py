"""hvd-telemetry: always-on metrics, cluster aggregation, flight recorder.

Three pieces (docs/metrics.md):

* :mod:`~horovod_tpu.telemetry.registry` — a lock-free-hot-path metrics
  registry every runtime layer publishes into; ``hvd.metrics()`` is the
  local snapshot.
* cluster aggregation — ``hvd.cluster_metrics()`` pulls every rank's
  snapshot over the control plane (FRAME_METRICS, ops/transport.py) and
  reports fleet min/max/mean/p50/p90/p99 per metric.  An optional
  Prometheus/JSON HTTP exporter (``HVD_TPU_METRICS_PORT``) serves
  ``/metrics`` and ``/healthz`` on rank 0.
* :mod:`~horovod_tpu.telemetry.flight` — a per-rank ring buffer of
  recent control-plane events dumped to ``HVD_TPU_FLIGHT_DIR`` on
  stalls, mismatches, dead peers and drain/receive-thread exceptions.
"""

from __future__ import annotations

from typing import Dict

from . import flight  # noqa: F401  (stdlib-only; safe to import first)
from .registry import (  # noqa: F401
    Counter,
    Gauge,
    Histogram,
    MetricsRegistry,
    aggregate,
    bucket_edges,
    metrics_enabled,
)

# Process-global default registry (module import order is unimportant:
# every layer that instruments itself asks this object for its metric
# handles at import time).
_default = MetricsRegistry()

# Metric-name prefixes worth carrying in a flight dump's compact tail:
# the control-plane, data-plane and host counters that contextualize a
# stall, PLUS (hvd-mem satellite) the gauge families — queue depths,
# occupancy, checkpoint backlog, memory watermarks — so every stall,
# dead-peer and OOM dump is self-contained forensics (docs/metrics.md
# "Dump format").  Push-fed gauges (serving.queue_depth,
# input.prefetch_queue_depth, checkpoint.pending, serving.kv_free_pages,
# memory.step_watermark_bytes) are current at dump time; collector-fed
# gauges carry their last-snapshot value (collectors still don't run
# here — a dump may fire from under runtime locks).
_FLIGHT_TAIL_PREFIXES = ("collective.", "transport.", "host.",
                        "events.", "input.", "trace.", "chaos.",
                        "serving.", "pipeline.", "overlap.",
                        "checkpoint.", "handles.", "memory.",
                        "analysis.", "tuning.")

# Extra tail providers (keyed, replace-on-reregister): subsystems whose
# dump-time truth lives OUTSIDE the registry (the hvd-mem ledger) merge
# a flat name->value dict into every tail.  Providers must be cheap and
# take only leaf locks — dumps fire from failure paths.
_extra_tails: Dict[str, object] = {}


def register_flight_tail(key: str, fn) -> None:
    _extra_tails[key] = fn


def unregister_flight_tail(key: str) -> None:
    _extra_tails.pop(key, None)


def _flight_metrics_tail() -> Dict[str, object]:
    """The compact snapshot appended to every flight dump (satellite of
    hvd-trace, extended by hvd-mem): counters AND gauges as bare
    values, histograms as count+sum.  Collectors are skipped — they
    read runtime structures and a dump may fire from under runtime
    locks; the striped leaves below are lock-free and the extra tail
    providers take only leaf locks."""
    out: Dict[str, object] = {}
    for name, m in _default.snapshot(run_collectors=False).items():
        if not name.startswith(_FLIGHT_TAIL_PREFIXES):
            continue
        if m.get("type") == "histogram":
            out[name] = {"count": m.get("count", 0),
                         "sum": m.get("sum", 0)}
        else:
            out[name] = m.get("value", 0)
    for fn in list(_extra_tails.values()):
        try:
            out.update(fn())
        except Exception:  # noqa: BLE001 — the dump must not mask
            pass           # the original failure
    return out


flight.set_metrics_provider(_flight_metrics_tail)


def _collect_analysis(reg: MetricsRegistry) -> None:
    """Pull the hvd-analyze runtime checkers' counts (docs/metrics.md
    "Analysis checkers").  Pull-side by design: the checkers run under
    arbitrary runtime locks — races._check fires INSIDE registry
    methods holding ``MetricsRegistry._lock`` — so they keep plain ints
    and this collector (which runs at snapshot time, outside the
    registry lock) publishes them as monotonic gauges."""
    from ..analysis import donation as _donation
    from ..analysis import races as _races
    from ..analysis import threads as _threads

    reg.gauge("analysis.race_checks",
              "lockset verifications by the data-race detector").set(
        _races.check_count())
    reg.gauge("analysis.thread_role_asserts",
              "dynamic thread-role contract verifications").set(
        _threads.assert_count())
    reg.gauge("analysis.donation_poisoned",
              "buffers registered as donated by guard_dispatch").set(
        _donation.poison_count())


_default.register_collector("analysis", _collect_analysis)


def registry() -> MetricsRegistry:
    return _default


def counter(name: str, help: str = "") -> Counter:
    return _default.counter(name, help)


def gauge(name: str, help: str = "") -> Gauge:
    return _default.gauge(name, help)


def histogram(name: str, kind: str = "seconds", help: str = "") -> Histogram:
    return _default.histogram(name, kind, help)


def enabled() -> bool:
    return _default.enabled


def set_enabled(v: bool) -> None:
    """Master switch for the whole telemetry subsystem (registry AND
    flight recorder).  Re-enabling restores
    the flight recorder's own env gate."""
    _default.set_enabled(v)
    flight.recorder.enabled = bool(v) and flight.flight_enabled_env()


def metrics() -> Dict[str, dict]:
    """This rank's local metrics snapshot (collectors included)."""
    return _default.snapshot()


def cluster_metrics(timeout: float = 10.0) -> Dict[str, dict]:
    """Fleet-level aggregation: rank 0 pulls every rank's snapshot over
    the control plane (FRAME_METRICS) and merges them — min/max/mean
    (+ per-rank values) for counters/gauges, merged buckets with
    p50/p90/p99 for histograms.  Rank-0-only in multi-process mode
    (workers answer the pull automatically from their receive thread —
    they should call :func:`metrics` for their own local view);
    single-process mode aggregates the one local snapshot."""
    from ..core import state as _state

    _state._check_initialized()
    st = _state.global_state()
    local = metrics()
    if not st.multiprocess:
        return aggregate({0: local})
    if st.process_index != 0:
        raise RuntimeError(
            "cluster_metrics() aggregates on the rank-0 controller; this "
            "rank answers the controller's FRAME_METRICS pull "
            "automatically — use hvd.metrics() for its local snapshot.")
    per_rank = st.transport.collect_metrics(local, timeout=timeout)
    return aggregate(per_rank)


# -- stall/dead-peer helpers shared by coordinator + collective ------------

_M_STALLS = counter(
    "events.stall_warnings",
    "stall-watch warnings (tensors pending past the threshold)")
_M_DEAD_PEERS = counter(
    "events.dead_peers", "peer processes that died without a handshake")
_M_DUMPS = counter("flight.dumps", "flight-recorder dumps written")


def stall_event(warnings) -> None:
    """One stall-watch firing: count it, append the full warning text
    (which names the tensor and the non-ready ranks) to the flight ring,
    and dump the ring — the 'what happened in the last 2000 events
    before the stall' forensic record."""
    ws = list(warnings)
    if not ws:
        return
    _M_STALLS.inc(len(ws))
    for w in ws:
        flight.record("stall", w)
    if flight.dump("stall", extra={"warnings": ws}) is not None:
        _M_DUMPS.inc()


def dead_peer_event(detail: str) -> None:
    _M_DEAD_PEERS.inc()
    flight.record("dead_peer", detail)
    if flight.dump("dead-peer", extra={"detail": detail}) is not None:
        _M_DUMPS.inc()


def error_event(message: str) -> None:
    flight.record("error", message)
    if flight.dump("error", extra={"message": message}) is not None:
        _M_DUMPS.inc()


def transport_fault_event(reason: str, detail: str) -> None:
    """A control-plane fault boundary fired (hvd-chaos hardening):
    a peer disconnect entering its grace window, a completed session
    resume, a frame deadline.  Recorded AND dumped — the ring's tail is
    the forensic record naming the fault (tests assert on it)."""
    flight.record("transport_fault", reason, detail)
    if flight.dump(reason, extra={"detail": detail}) is not None:
        _M_DUMPS.inc()


def exception_event(where: str, text: str) -> None:
    flight.record("exception", where, text)
    if flight.dump(f"exception-{where}",
                   extra={"where": where, "traceback": text}) is not None:
        _M_DUMPS.inc()


# -- hvd-pipeline events (PR 5): input prefetch + checkpoint writer --------

_M_PREFETCH_ERRORS = counter(
    "input.prefetch_errors", "loader exceptions captured by prefetchers")
_M_CKPT_ERRORS = counter(
    "checkpoint.errors", "background checkpoint writes that failed")


def prefetch_error_event(detail: str) -> None:
    """A prefetch loader raised on the stager thread: count it and dump
    the flight ring — the exception itself re-raises at the consuming
    step (parallel/input.py), this is the forensic side channel."""
    _M_PREFETCH_ERRORS.inc()
    flight.record("prefetch_error", detail)
    if flight.dump("prefetch-error", extra={"detail": detail}) is not None:
        _M_DUMPS.inc()


def checkpoint_error_event(path: str, detail: str) -> None:
    """A background checkpoint write failed: the handle carries the
    exception to ``wait()``; this records the failure even for callers
    that never wait (fire-and-forget saves must not fail silently)."""
    _M_CKPT_ERRORS.inc()
    flight.record("checkpoint_error", path, detail)
    if flight.dump("checkpoint-error",
                   extra={"path": path, "detail": detail}) is not None:
        _M_DUMPS.inc()


def overlap_fallback_event(reason: str, detail: str) -> None:
    """The backward/communication-overlap step fell back to the
    monolithic program (parallel/overlap.py): flight-record the NAMED
    reason (``adasum``/``sparse``/``sub-mesh``/...) with its
    human-readable detail.  The ``overlap.fallbacks`` counter is
    incremented by the caller in lockstep — one counter tick, one
    flight event, one warn line per fallback.  Recorded but NOT
    dumped: a fallback is a degraded mode, not a failure."""
    flight.record("overlap_fallback", reason, detail)


def install_runtime_collector() -> None:
    """Register the pull-side collector over the runtime's existing
    cheap stats structs (CacheStats, MegakernelStats, the handle pool).
    Idempotent: keyed registration replaces the previous instance on
    re-init.  Collectors run at snapshot time only — the steady-state
    hot path never touches these gauges."""

    def collect(reg: MetricsRegistry) -> None:
        from ..core import state as _state
        from ..ops import megakernel as _mk

        st = _state.global_state()
        cache = st.response_cache
        if cache is not None:
            s = cache.stats
            reg.gauge("cache.hits").set(s.hits)
            reg.gauge("cache.misses").set(s.misses)
            reg.gauge("cache.flushes").set(s.flushes)
            reg.gauge("cache.downgrades").set(s.downgrades)
            reg.gauge("cache.inserts").set(s.inserts)
            reg.gauge("cache.replayed_tensors").set(s.replayed_tensors)
            reg.gauge("cache.plan_hits").set(s.plan_hits)
            reg.gauge("cache.plan_misses").set(s.plan_misses)
            reg.gauge("cache.entries").set(cache.live_entries())
            reg.gauge("cache.epoch").set(cache.epoch)
        hm = st.handle_manager
        if hm is not None:
            reg.gauge("handles.live").set(hm.live_count())
        ms = _mk.stats
        reg.gauge("megakernel.builds").set(ms.builds)
        reg.gauge("megakernel.build_seconds").set(
            round(ms.build_seconds, 6))
        reg.gauge("megakernel.compile_seconds").set(
            round(ms.compile_seconds, 6))
        reg.gauge("megakernel.cache_hits").set(ms.cache_hits)
        reg.gauge("megakernel.flushes").set(ms.flushes)
        reg.gauge("megakernel.launches").set(ms.launches)
        reg.gauge("megakernel.hier_launches").set(ms.hier_launches)
        reg.gauge("megakernel.executables").set(_mk.cache_size())
        reg.gauge("megakernel.warm_starts").set(ms.warm_starts)
        # Quantized allreduce (docs/metrics.md "Quantized reduction"):
        # cumulative logical vs wire bytes and their ratio — with the
        # identity compressor the ratio sits at 1.0; int8 ≈ 3.97, int4
        # ≈ 7.9.  The per-launch distribution rides the
        # collective.wire_bytes histogram (fed at launch time by the
        # executor, not by this collector).
        reg.gauge("megakernel.quant_launches").set(ms.quant_launches)
        reg.gauge("megakernel.logical_bytes").set(ms.logical_bytes)
        reg.gauge("megakernel.wire_bytes").set(ms.wire_bytes)
        reg.gauge("megakernel.residual_tensors").set(_mk.residual_count())
        reg.gauge("compression.ratio").set(
            round(ms.logical_bytes / ms.wire_bytes, 4)
            if ms.wire_bytes else 1.0)

    _default.register_collector("runtime", collect)
