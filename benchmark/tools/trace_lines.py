"""Every line of every device plane of a profiler trace, with its events
counted and its busy union: what ``xtrace`` reads (the ``XLA Ops`` line)
beside what it leaves (modules, steps, asynchronous operations).

    python -m benchmark.tools.trace_lines <trace.xplane.pb>
"""

from __future__ import annotations

import json
import sys

from benchmark import xtrace


def lines(path: str) -> list:
    from jax.profiler import ProfileData

    out = []
    for plane in ProfileData.from_file(path).planes:
        if not plane.name.startswith("/device:"):
            continue
        for line in plane.lines:
            evs = [(e.name, int(e.start_ns), int(e.duration_ns))
                   for e in line.events]
            if not evs:
                continue
            red = xtrace.reduce_device(evs)
            out.append({"plane": plane.name, "line": line.name,
                        "events": len(evs),
                        "first_ns": min(e[1] for e in evs),
                        "last_ns": max(e[1] + e[2] for e in evs),
                        "busy_s": red["busy_ns"] / 1e9,
                        "window_s": red["window_ns"] / 1e9})
    return out


def main(argv=None) -> int:
    for row in lines((argv or sys.argv[1:])[0]):
        print(json.dumps(row))
    return 0


if __name__ == "__main__":
    sys.exit(main())
