"""The program's own spans on the profiler's clock: which of them the host
was in while a device stood idle.

``horovod_tpu.trace.region`` enters a ``jax.profiler.TraceAnnotation`` named
``hvd:<span>``, so a traced run's ``.xplane.pb`` holds the host's spans
beside the device's operations.  Like ``xtrace.py`` everything works on
plain event lists ``(name, start_ns, duration_ns)``, so the arithmetic is
checked on hand-made events without a chip.  An instant belongs to the
INNERMOST span that covers it.  A program without regions leaves no ``hvd:``
event: every gap then reads ``host:outside_spans``.
"""

from __future__ import annotations

from collections import defaultdict
from typing import Dict, List, Optional, Sequence

from benchmark import xtrace
from benchmark.xtrace import Event

PREFIX = "hvd:"
OUTSIDE = "host:outside_spans"
ROOTS = (PREFIX + "step/", PREFIX + "serve.iteration")


def load_host_spans(path: str) -> Dict[str, List[Event]]:
    """Per host thread (``<plane>/<line>#<n>``), its ``hvd:`` events."""
    from jax.profiler import ProfileData

    out = {}
    for plane in ProfileData.from_file(path).planes:
        if not plane.name.startswith("/host:"):
            continue
        for n, line in enumerate(plane.lines):
            spans = [(e.name, int(e.start_ns), int(e.duration_ns))
                     for e in line.events if e.name.startswith(PREFIX)]
            if spans:
                out[f"{plane.name}/{line.name}#{n}"] = spans
    return out


def _overlap_ns(segs: Sequence[Event], lo: int, hi: int) -> int:
    return sum(max(0, min(s + d, hi) - max(s, lo)) for _, s, d in segs)


def dispatching_thread(host_spans: Dict[str, Sequence[Event]],
                       lo: int, hi: int) -> Optional[str]:
    """The thread that holds the launches: of the threads with a step or a
    serving iteration (``ROOTS``) the one whose spans cover most of
    ``[lo, hi]``; of all threads where none has one.  Coverage alone is not
    enough: a training thread that runs ahead of the device is in its step
    call for milliseconds of a window the drain tick's thread ticks through."""
    rooted = {t: evs for t, evs in host_spans.items()
              if any(n.startswith(ROOTS) for n, _, _ in evs)}
    best, covered = None, 0
    for name, evs in sorted((rooted or host_spans).items()):
        c = _overlap_ns(xtrace.flatten(evs), lo, hi)
        if c > covered:
            best, covered = name, c
    return best


def _gaps(events: Sequence[Event]):
    """Idle intervals of one device's flattened line, each with the name
    ``xtrace.reduce_device`` gives it today."""
    prev_name, prev_end = None, None
    for name, start, dur in xtrace.flatten(events):
        if prev_end is not None and start > prev_end:
            yield (f"host:unattributed_{xtrace.op_class(prev_name)[:28]}-_"
                   f"{xtrace.op_class(name)[:28]}", prev_end, start)
        prev_name, prev_end = name, start + dur


def attribute_gaps(device_events: Dict[str, Sequence[Event]],
                   host_spans: Dict[str, Sequence[Event]],
                   thread: Optional[str] = None) -> dict:
    """Per device, the idle seconds of its flattened ``XLA Ops`` line by the
    innermost ``hvd:`` span of the dispatching thread that covers them
    (``host:outside_spans`` where none does).  ``by_gap`` splits the same
    seconds by the gap's name of today as well."""
    used = {k: v for k, v in device_events.items() if v}
    if not used:
        return {"thread": None, "devices": {}}
    lo = min(s for evs in used.values() for _, s, _ in evs)
    hi = max(s + d for evs in used.values() for _, s, d in evs)
    thread = thread or dispatching_thread(host_spans, lo, hi)
    segs = xtrace.flatten(host_spans.get(thread, ()))
    devices = {}
    for plane, events in sorted(used.items()):
        by_span: Dict[str, int] = defaultdict(int)
        by_gap: Dict[str, Dict[str, int]] = defaultdict(
            lambda: defaultdict(int))
        idle = k = 0
        for gap, a, b in _gaps(events):
            idle += b - a
            left = b - a
            while k < len(segs) and segs[k][1] + segs[k][2] <= a:
                k += 1
            j = k
            while j < len(segs) and segs[j][1] < b:
                name, s, d = segs[j]
                part = min(s + d, b) - max(s, a)
                if part > 0:
                    name = name[len(PREFIX):]
                    by_span[name] += part
                    by_gap[gap][name] += part
                    left -= part
                j += 1
            if left > 0:
                by_span[OUTSIDE] += left
                by_gap[gap][OUTSIDE] += left
        devices[plane] = {
            "idle_s": idle / 1e9,
            "by_span": {n: v / 1e9 for n, v in by_span.items()},
            "by_gap": {g: {n: v / 1e9 for n, v in spans.items()}
                       for g, spans in by_gap.items()}}
    return {"thread": thread, "devices": devices}


def named_share(attributed: dict) -> float:
    """Share of all devices' idle seconds that lies under a named span."""
    idle = sum(d["idle_s"] for d in attributed["devices"].values())
    outside = sum(d["by_span"].get(OUTSIDE, 0.0)
                  for d in attributed["devices"].values())
    return 1.0 - outside / idle if idle else 0.0
