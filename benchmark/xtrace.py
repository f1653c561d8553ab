"""Reduction of a profiler trace (``.xplane.pb``) to device numbers.

Everything works on plain event lists ``(name, start_ns, duration_ns)`` per
device, so the arithmetic is checked on a small recorded trace without a
chip.  An instant belongs to the innermost event that covers it: a ``while``
that spans its body does not count twice, and a collective's exposed time is
the time it is the innermost thing running on its core.
"""

from __future__ import annotations

import glob
import os
import re
from collections import defaultdict
from typing import Dict, List, Sequence, Tuple

Event = Tuple[str, int, int]

OPS_LINE = "XLA Ops"
_COLLECTIVE = re.compile(
    r"all-reduce|all-gather|reduce-scatter|collective-permute|all-to-all"
    r"|psum|ppermute", re.I)
_SUFFIX = re.compile(r"(\.\d+)+$")


def find_xplane(directory: str) -> str:
    files = sorted(glob.glob(os.path.join(directory, "**", "*.xplane.pb"),
                             recursive=True), key=os.path.getmtime)
    if not files:
        raise FileNotFoundError(f"no .xplane.pb under {directory}")
    return files[-1]


def load_device_events(path: str) -> Dict[str, List[Event]]:
    """Per device plane, the events of its ``XLA Ops`` line."""
    from jax.profiler import ProfileData

    out = {}
    for plane in ProfileData.from_file(path).planes:
        if not plane.name.startswith("/device:"):
            continue
        for line in plane.lines:
            if line.name == OPS_LINE:
                out[plane.name] = [(e.name, int(e.start_ns),
                                    int(e.duration_ns)) for e in line.events]
    return out


def op_class(name: str) -> str:
    """``fusion.123`` -> ``fusion``; ``%copy-done.2 = ...`` -> ``copy-done``."""
    name = name.split(" = ")[0].lstrip("%").strip()
    return _SUFFIX.sub("", name)


def is_collective(name: str) -> bool:
    return bool(_COLLECTIVE.search(name))


def flatten(events: Sequence[Event]) -> List[Event]:
    """Non-overlapping segments, each owned by the innermost event."""
    segs: List[Event] = []
    stack: List[Tuple[str, int]] = []          # (name, end), innermost last
    cursor = 0

    def close(upto=None) -> None:
        nonlocal cursor
        while stack and (upto is None or stack[-1][1] <= upto):
            name, end = stack.pop()
            if end > cursor:
                segs.append((name, cursor, end - cursor))
                cursor = end

    for name, start, dur in sorted(events, key=lambda e: (e[1], -e[2])):
        close(start)
        if stack and start > cursor:
            segs.append((stack[-1][0], cursor, start - cursor))
        cursor = max(cursor, start) if stack else start
        stack.append((name, start + dur))
    close()
    return segs


def reduce_device(events: Sequence[Event]) -> dict:
    segs = flatten(events)
    if not segs:
        return {"busy_ns": 0, "window_ns": 0, "ops": {}, "gaps": {},
                "exposed_coll_ns": 0}
    ops: Dict[str, int] = defaultdict(int)
    gaps: Dict[str, int] = defaultdict(int)
    busy = exposed = 0
    prev_name, prev_end = None, None
    for name, start, dur in segs:
        busy += dur
        ops[op_class(name)] += dur
        if is_collective(name):
            exposed += dur
        if prev_end is not None and start > prev_end:
            gaps[f"host:unattributed_{op_class(prev_name)[:28]}-_"
                 f"{op_class(name)[:28]}"] += start - prev_end
        prev_name, prev_end = name, start + dur
    return {"busy_ns": busy, "window_ns": prev_end - segs[0][1],
            "ops": dict(ops), "gaps": dict(gaps), "exposed_coll_ns": exposed}


def reduce_trace(per_device: Dict[str, Sequence[Event]], top: int = 10) -> dict:
    """Averages over the devices used: busy and window seconds, exposed
    collective seconds, per-op seconds, idle gaps by what stood around
    them."""
    reds = [reduce_device(evs) for _, evs in sorted(per_device.items())
            if evs]
    reds = [r for r in reds if r["window_ns"] > 0]
    if not reds:
        return {"devices": 0, "per_device": [], "busy_s": 0.0, "window_s": 0.0,
                "exposed_coll_s": 0.0, "ops": {}, "device_ops": [],
                "idle_gaps": []}
    n = len(reds)

    def mean_table(key):
        table: Dict[str, float] = defaultdict(float)
        for r in reds:
            for k, v in r[key].items():
                table[k] += v / n / 1e9
        return dict(table)

    ops, gaps = mean_table("ops"), mean_table("gaps")

    def ranked(t):
        return [[k, v] for k, v in sorted(t.items(), key=lambda kv: -kv[1])]

    return {
        "devices": n,
        "per_device": [[r["busy_ns"] / 1e9, r["window_ns"] / 1e9]
                       for r in reds],
        "busy_s": sum(r["busy_ns"] for r in reds) / n / 1e9,
        "window_s": max(r["window_ns"] for r in reds) / 1e9,
        "exposed_coll_s": sum(r["exposed_coll_ns"] for r in reds) / n / 1e9,
        "ops": ops,
        "device_ops": ranked(ops)[:top],
        "idle_gaps": ranked(gaps)[:top],
    }


def matched_seconds(reduced: dict, pattern: str) -> float:
    """Device seconds (mean over devices) of the op classes ``pattern``
    matches."""
    rx = re.compile(pattern)
    return sum(v for k, v in reduced["ops"].items() if rx.search(k))
