"""hvd-trace: fleet-wide distributed tracing over the runtime.

The timeline (utils/timeline.py) answers "what happened on rank 0";
the metrics registry (hvd-telemetry) answers "is the fleet healthy".
Neither can *explain a slow step*: each rank's Chrome timeline runs on
its own clock, so nobody can see that rank 5's input stall delayed the
whole fleet's allreduce, or which leg (host, pack, collective, DCN,
unpack, dispatch gap) owns the cycle.  hvd-trace closes that gap with
three pieces (docs/tracing.md):

1. **Span propagation** (this module) — every rank keeps a bounded
   in-memory buffer of *spans* (Chrome complete events on the rank's
   own monotonic clock).  A ``(step, cycle, trace_id)`` context rides
   the existing control frames — the worker's coalesced
   FRAME_REQUEST_BATCH carries its current context as a trailer, and
   every controller response broadcast carries rank 0's — so spans on
   different ranks are causally linkable: the same ``(step, cycle)``
   names the same fleet-wide negotiation cycle everywhere.  The same
   context is mirrored into the rank-0 Chrome timeline's event args
   (utils/timeline.set_context_provider).

2. **Clock alignment** (:mod:`~horovod_tpu.trace.clock`) — a
   ping/pong offset estimator over the TCP control plane (NTP-style
   min-RTT filter, re-measured on reconnect) lets rank 0 merge all
   ranks' span buffers into ONE ``chrome://tracing`` / Perfetto
   -loadable fleet trace: :func:`dump_fleet_trace`
   (:mod:`~horovod_tpu.trace.merge`, per-rank buffers pulled over
   FRAME_TRACE, the ``cluster_metrics`` round-keyed rendezvous
   pattern).

3. **Analysis** (:mod:`~horovod_tpu.trace.analyze`) — ``python -m
   horovod_tpu.trace <file>`` computes per-step critical-path
   attribution, names the straggler rank per cycle with its blame
   category, and emits a human report + JSON.  :class:`~horovod_tpu.trace.watch.StragglerWatch` warns
   live when one rank's skew exceeds a threshold for N consecutive
   steps.

4. **Regions** (:func:`region`) — the context-manager form of a span,
   and the one call that lands on the PROFILER's clock as well: the
   same pair of ``time.monotonic`` reads feeds the span buffer, a
   ``trace.span_seconds.<name>`` registry histogram and, while a
   profiler session records, a
   ``jax.profiler.TraceAnnotation("hvd:<name>")``, so the spans of the
   stream step, the serving iteration and set-up sit beside the device
   ops in the ``.xplane.pb`` a profiler run writes (docs/tracing.md,
   "On the profiler's clock").  Spans that start in the past on
   another thread (``negotiate.wait``, ``serving.request``) stay
   :func:`span`-only; every annotation carries ``mono_us`` so a reader
   can place them on the profiler's timeline.

Hot-path budget mirrors the flight recorder's: recording a span is one
flag check, two ``time.monotonic`` reads (taken by the caller, or by
the region) and one ``deque.append`` (atomic in CPython — no lock).
``HVD_TPU_TRACE=0`` opts out; ``set_enabled(False)`` is the runtime
switch (what tracing costs on the chip: PERF.md section 6, PR 24).

Env contract:
  HVD_TPU_TRACE=0           disable span recording (default on)
  HVD_TPU_TRACE_EVENTS      span buffer capacity per rank (default 20000)
  HVD_TPU_TRACE_PING        controller ping cadence seconds (default 1,
                            0 disables the periodic clock probes)
"""

from __future__ import annotations

import collections
import os
import struct
import threading
import time
from typing import Dict, List, Optional

from jax.profiler import TraceAnnotation as _TraceAnnotation

from .. import telemetry as _telemetry

DEFAULT_CAPACITY = 20000

_M_SPANS = _telemetry.counter(
    "trace.spans", "hvd-trace spans recorded into the local buffer")

# Wire layout of the propagated context: <u32 step><u32 cycle>
# <u64 trace_id>, appended as a TRAILER to existing control frames
# (FRAME_REQUEST_BATCH worker->controller; FRAME_RESPONSES /
# FRAME_RESPONSE_BATCH controller->worker).  A trailer keeps the frames
# parseable by pre-trace peers: every existing payload is
# self-delimiting, so 16 extra bytes after it are simply ignored by a
# parser that does not know them.
CTX_STRUCT = struct.Struct("<IIQ")


def trace_enabled_env() -> bool:
    return os.environ.get("HVD_TPU_TRACE", "1") != "0"


def _capacity() -> int:
    return int(os.environ.get("HVD_TPU_TRACE_EVENTS",
                              str(DEFAULT_CAPACITY)))


def ping_interval() -> float:
    return float(os.environ.get("HVD_TPU_TRACE_PING", "1"))


class TraceState:
    """Per-process span buffer + the propagated (step, cycle, trace_id)
    context.

    The context fields are plain ints mutated by single writers (step:
    the training thread; cycle: the drain tick / receive thread) and
    read racily by span recorders — a span that lands on the previous
    cycle's id is fine (the analyzer groups per cycle, and cycle
    boundaries ARE the drain tick), so no lock is taken anywhere on the
    record path."""

    def __init__(self) -> None:
        self.enabled = trace_enabled_env()
        self.step = 0
        self.cycle = 0
        self.trace_id = 0
        self._events: collections.deque = collections.deque(
            maxlen=_capacity())

    # -- hot path ----------------------------------------------------------
    def record(self, ev) -> None:
        """The one append path every event kind funnels through (the
        event-shape and accounting stay in one place).  ``ev`` is a
        Chrome event, or a closed region that builds its event when the
        buffer is exported."""
        self._events.append(ev)
        _M_SPANS.inc()

    def span(self, name: str, cat: str, t0: float, t1: float,
             args: Optional[dict] = None) -> None:
        """Record one complete span.  ``t0``/``t1`` are
        ``time.monotonic()`` seconds (the clock the offset estimator
        aligns); stored as Chrome-trace microseconds."""
        if not self.enabled:
            return
        self.record({"name": name, "cat": cat, "ph": "X",
                     "ts": t0 * 1e6, "dur": max(0.0, (t1 - t0)) * 1e6,
                     "args": {"step": self.step, "cycle": self.cycle,
                              **(args or {})}})

    def instant(self, name: str, cat: str,
                args: Optional[dict] = None) -> None:
        if not self.enabled:
            return
        self.record({"name": name, "cat": cat, "ph": "i", "s": "t",
                     "ts": time.monotonic() * 1e6,
                     "args": {"step": self.step, "cycle": self.cycle,
                              **(args or {})}})

    # -- cold paths --------------------------------------------------------
    def export(self) -> List[dict]:
        return [ev if type(ev) is dict else ev.event()
                for ev in list(self._events)]

    def clear(self) -> None:
        self._events.clear()


_state = TraceState()


def state() -> TraceState:
    return _state


def enabled() -> bool:
    return _state.enabled


def set_enabled(v: bool) -> None:
    """Runtime switch for span recording, like
    ``telemetry.set_enabled``.  Re-enabling restores the env gate."""
    _state.enabled = bool(v) and trace_enabled_env()


def span(name: str, cat: str, t0: float, t1: float,
         args: Optional[dict] = None) -> None:
    _state.span(name, cat, t0, t1, args)


def instant(name: str, cat: str, args: Optional[dict] = None) -> None:
    _state.instant(name, cat, args)


# -- regions: one span call, three sinks -------------------------------------

_tls = threading.local()     # .stack: names of this thread's open regions


class _Off:
    """What a region call returns with tracing off: one shared object,
    nothing read and nothing built."""

    __slots__ = ()

    def __enter__(self):
        return self

    def __exit__(self, *exc):
        return False

    def note(self, **args) -> None:
        pass

    def cancel(self) -> None:
        pass


_OFF = _Off()


class _Stopwatch(_Off):
    """A ``timed`` region with tracing off: the pair of clock reads the
    histogram beside the region (``collective.execute_seconds``, ...)
    still needs, and nothing else."""

    __slots__ = ("t0", "t1")

    def __enter__(self):
        self.t0 = time.monotonic()
        return self

    def __exit__(self, *exc):
        self.t1 = time.monotonic()
        return False

    @property
    def seconds(self) -> float:
        return self.t1 - self.t0


class _Open(_Stopwatch):
    """One entered region, and afterwards its record in the span buffer.
    ``t0``/``t1`` (``time.monotonic`` seconds) are the ONLY clock reads
    of the boundary: the call site feeds its own histogram from
    ``seconds`` instead of reading a second clock.

    The hot path builds as little as it can (measured in a serving run
    on the chip's host, where cold caches make a region five times its
    tight-loop cost): the annotation only while a profiler session is
    recording, and the Chrome event only when the buffer is exported."""

    __slots__ = ("_region", "_args", "_ann", "_keep")

    def __init__(self, region: "Region", args: dict) -> None:
        self._region = region
        self._args = args       # the call's own kwargs dict, reused
        self._ann = None
        self._keep = True

    def __enter__(self):
        args = self._args
        stack = getattr(_tls, "stack", None)
        if stack is None:
            stack = _tls.stack = []
        if stack:
            args["parent"] = stack[-1]
        stack.append(self._region.name)
        t0 = self.t0 = time.monotonic()
        if _TraceAnnotation.is_enabled():
            args["step"] = _state.step
            args["cycle"] = _state.cycle
            args["mono_us"] = int(t0 * 1e6)
            self._ann = _TraceAnnotation(self._region.annotation, **args)
            self._ann.__enter__()
        return self

    def __exit__(self, *exc):
        if self._ann is not None:
            self._ann.__exit__(*exc)
            self._ann = None
        self.t1 = time.monotonic()
        _tls.stack.pop()
        if self._keep:
            # Context as of the END, like span(): a tick's span carries
            # the cycle it opened.
            args = self._args
            args["step"] = _state.step
            args["cycle"] = _state.cycle
            _state.record(self)
            self._region.seconds.observe(self.t1 - self.t0)
        return False

    def event(self) -> dict:
        """The Chrome complete event :func:`span` would have appended."""
        region = self._region
        return {"name": region.name, "cat": region.cat, "ph": "X",
                "ts": self.t0 * 1e6, "dur": (self.t1 - self.t0) * 1e6,
                "args": self._args}

    def note(self, **args) -> None:
        """Arguments only known at the end (a tick's response count)."""
        self._args.update(args)
        if self._ann is not None:
            self._ann.set_metadata(**args)

    def cancel(self) -> None:
        """Keep this one out of the buffer and the histogram (an empty
        drain tick); the profiler's timeline has it already."""
        self._keep = False


class Region:
    """A span call site's handle, built once at import like the ``_M_*``
    metric handles.  ``with R(bucket=3) as r: ...`` reads
    ``time.monotonic`` once on each side and feeds three sinks from
    that pair: the span buffer (the same Chrome event :func:`span`
    appends, plus ``parent``, the enclosing region on this thread), the
    ``trace.span_seconds.<name>`` histogram, and — while a profiler
    session records — a ``jax.profiler.TraceAnnotation("hvd:<name>")``
    whose arguments are the span's (``step``, ``cycle``, the caller's)
    plus ``mono_us``, the region's start on ``time.monotonic``.  With
    ``HVD_TPU_TRACE=0``
    the call is one flag check and returns a shared no-op — unless the
    site is ``timed``: then a histogram of its own reads ``r.seconds``
    and the call still takes the two clock reads.

    Regions sit at per-step, per-bucket and per-iteration boundaries,
    never per tensor, slot or token: each costs a few microseconds."""

    __slots__ = ("name", "cat", "annotation", "seconds", "timed")

    def __init__(self, name: str, cat: str, timed: bool = False) -> None:
        self.name = name
        self.cat = cat
        self.annotation = "hvd:" + name
        self.timed = timed
        self.seconds = _telemetry.histogram(
            "trace.span_seconds." + name, "seconds",
            f"duration of the hvd-trace region {name}")

    def __call__(self, **args):
        if not _state.enabled:
            return _Stopwatch() if self.timed else _OFF
        return _Open(self, args)


region = Region      # the call sites' spelling: ``_trace.region(name, cat)``


class RegionFamily(dict):
    """Regions named ``<prefix><key>`` for a small closed set of keys
    (``execute/<op>``): each built at its first use, then a dict hit."""

    def __init__(self, prefix: str, cat: str, timed: bool = False) -> None:
        super().__init__()
        self._prefix, self._cat, self._timed = prefix, cat, timed

    def __missing__(self, key: str) -> Region:
        made = self[key] = Region(self._prefix + key, self._cat,
                                  self._timed)
        return made


def export_events() -> List[dict]:
    """This rank's local span buffer (Chrome complete events, local
    monotonic microseconds, no pid — the merge assigns ranks)."""
    return _state.export()


def clear() -> None:
    _state.clear()


# -- propagated context ----------------------------------------------------

def set_step(n: int) -> None:
    """Stamp the training step every subsequent span carries.  Called
    by the train-step wrapper (parallel/training.py) once per step;
    explicit calls override (serving loops, tests)."""
    _state.step = int(n)


def on_step() -> int:
    """Advance the step counter by one (the train-step wrapper's
    per-call hook); returns the new step."""
    _state.step += 1
    return _state.step


def current_step() -> int:
    return _state.step


def next_cycle() -> tuple:
    """Advance the negotiation-cycle counter (rank 0 / single-process
    only: one increment per response broadcast — the fleet-wide cycle
    id every rank's spans then share).  Returns the new context."""
    _state.cycle += 1
    return (_state.step, _state.cycle, _state.trace_id)


def observe_ctx(step: int, cycle: int, trace_id: int) -> None:
    """Adopt rank 0's broadcast context (worker side).  The STEP is
    deliberately not adopted: steps are a local training-loop notion
    each rank stamps itself (ranks run the same loop), while the cycle
    id must be the controller's so cross-rank spans line up."""
    _state.cycle = int(cycle)
    _state.trace_id = int(trace_id)


def current_ctx() -> tuple:
    return (_state.step, _state.cycle, _state.trace_id)


def current_args() -> Dict[str, int]:
    """The context dict mirrored into timeline event args
    (utils/timeline.set_context_provider)."""
    if not _state.enabled:
        return {}
    return {"step": _state.step, "cycle": _state.cycle}


def pack_ctx() -> bytes:
    """The 16-byte wire trailer (see CTX_STRUCT)."""
    return CTX_STRUCT.pack(_state.step & 0xFFFFFFFF,
                           _state.cycle & 0xFFFFFFFF, _state.trace_id)


def unpack_ctx(buf: bytes, off: int) -> Optional[tuple]:
    """Parse a context trailer at ``off`` when present (None when the
    payload predates the trace layer — old peer / tests poking raw
    frames)."""
    if len(buf) - off < CTX_STRUCT.size:
        return None
    return CTX_STRUCT.unpack_from(buf, off)


def reset_run(rank: int = 0, trace_id: Optional[int] = None) -> None:
    """Fresh trace for a (re-)init: new trace id on rank 0 (workers
    adopt it from the first broadcast), counters to zero, buffer
    cleared."""
    _state.step = 0
    _state.cycle = 0
    _state.enabled = trace_enabled_env()
    if trace_id is not None:
        _state.trace_id = int(trace_id)
    elif rank == 0:
        _state.trace_id = int.from_bytes(os.urandom(8), "little") or 1
    _state.clear()
    # The arrival tracker restarts with the counters: the new run
    # reuses the same (step, cycle) keys, and stale stamps would both
    # dedup away the new run's arrivals and poison its skew baseline.
    from . import watch as _watch

    _watch.tracker.clear()


def note_batch_arrival(rank: int, step: int, cycle: int) -> None:
    """Controller-side: one rank's negotiation traffic for a cycle
    arrived — a worker's coalesced request frame (with its trace
    trailer), or rank 0's own first local submit of the tick.  Feeds
    the live skew tracker (:mod:`~horovod_tpu.trace.watch`) and
    records an arrival instant — the analyzer's per-cycle straggler
    signal.  Deduplicated per (rank, step, cycle): rank 0 submits once
    per tensor but only the cycle's FIRST stamp is an arrival."""
    if not _state.enabled:
        return
    now = time.monotonic()
    from . import watch as _watch

    if not _watch.tracker.note(rank, step, cycle, now):
        return  # duplicate stamp for this (rank, step, cycle)
    _state.record({"name": "BATCH_ARRIVAL", "cat": "negotiate",
                   "ph": "i", "s": "t", "ts": now * 1e6,
                   "args": {"step": int(step), "cycle": int(cycle),
                            "rank": int(rank)}})


# Mirror the propagated context into rank 0's Chrome timeline events.
from ..utils import timeline as _timeline  # noqa: E402

_timeline.set_context_provider(current_args)


def __getattr__(name):
    # Lazy resolution for cycle safety: this package is imported by
    # low-level modules (ops/collective, ops/transport) while
    # watch/merge import back into higher layers (callbacks, core
    # state), so those submodules must not load at trace-import time.
    # horovod_tpu/__init__ re-exports both eagerly at the END of the
    # package import, when every layer exists.
    if name == "dump_fleet_trace":
        from .merge import dump_fleet_trace

        return dump_fleet_trace
    if name == "StragglerWatch":
        from .watch import StragglerWatch

        return StragglerWatch
    raise AttributeError(f"module {__name__!r} has no attribute {name!r}")
