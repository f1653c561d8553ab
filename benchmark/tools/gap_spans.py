"""Idle seconds of each device by the program's span the host was in.

    python -m benchmark.tools.gap_spans <dir or .xplane.pb> [--top N] [--json]

On a trace kept from a traced run (``BENCHMARK_KEEP_TRACE=<dir>``): per
device, the idle seconds of its ``XLA Ops`` line by the innermost ``hvd:``
span of the dispatching thread that covers them, and the same seconds
beside the ``host:unattributed_*`` names the result line's
``breakdown.idle_gaps`` gives the gaps today.
"""

from __future__ import annotations

import argparse
import json
import os
import sys

from benchmark import hostspans, xtrace


def render(att: dict, top: int) -> str:
    named = 100 * hostspans.named_share(att)
    lines = [f"dispatching thread: {att['thread']}",
             f"idle under a named span: {named:.1f}%"]
    for plane, d in att["devices"].items():
        idle = d["idle_s"] or 1.0
        lines.append(f"{plane}: idle {d['idle_s']:.6f} s")
        for name, s in sorted(d["by_span"].items(),
                              key=lambda kv: -kv[1])[:top]:
            lines.append(f"  {s:10.6f} s {100 * s / idle:5.1f}%  {name}")
        for gap, spans in sorted(d["by_gap"].items(),
                                 key=lambda kv: -sum(kv[1].values()))[:top]:
            lines.append(f"  {gap}: {sum(spans.values()):.6f} s")
            for name, s in sorted(spans.items(),
                                  key=lambda kv: -kv[1])[:top]:
                lines.append(f"    {s:10.6f} s  {name}")
    return "\n".join(lines)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("path")
    ap.add_argument("--top", type=int, default=8)
    ap.add_argument("--json", action="store_true")
    args = ap.parse_args(argv)
    path = (xtrace.find_xplane(args.path) if os.path.isdir(args.path)
            else args.path)
    att = hostspans.attribute_gaps(xtrace.load_device_events(path),
                                   hostspans.load_host_spans(path))
    print(json.dumps(att) if args.json else render(att, args.top))
    return 0


if __name__ == "__main__":
    sys.exit(main())
