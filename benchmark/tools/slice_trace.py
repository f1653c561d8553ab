"""Cut a profiler trace down to a small recorded one for the tests.

    python -m benchmark.tools.slice_trace <in.xplane.pb> <out.xplane.pb> \
        --from-ms 0 --ms 120

Keeps, for every device plane, the ``XLA Ops`` events that start inside the
slice, with their names cut to the operation's own name (the HLO text after
`` = `` is most of a trace's bytes).  Writes a real ``.xplane.pb`` (the
XSpace wire format, encoded by hand: no protobuf schema is installed), so
the tests exercise the loader as well as the arithmetic.
"""

from __future__ import annotations

import argparse
import sys

from benchmark import xtrace


def _varint(n: int) -> bytes:
    n &= (1 << 64) - 1
    out = bytearray()
    while True:
        b = n & 0x7F
        n >>= 7
        out.append(b | (0x80 if n else 0))
        if not n:
            return bytes(out)


def _field(num: int, wire: int, payload: bytes) -> bytes:
    return _varint(num << 3 | wire) + payload


def _int(num: int, value: int) -> bytes:
    return _field(num, 0, _varint(value))


def _bytes(num: int, value: bytes) -> bytes:
    return _field(num, 2, _varint(len(value)) + value)


def encode_xspace(planes: dict) -> bytes:
    """``{plane name: [(event name, start_ns, duration_ns), ...]}`` as an
    XSpace with one ``XLA Ops`` line per plane."""
    out = b""
    for pid, (plane_name, events) in enumerate(sorted(planes.items())):
        ids = {}
        for name, _, _ in events:
            ids.setdefault(name, len(ids) + 1)
        t0 = min((s for _, s, _ in events), default=0)
        line = _int(1, 1) + _bytes(2, xtrace.OPS_LINE.encode()) + _int(3, t0)
        for name, start, dur in sorted(events, key=lambda e: e[1]):
            line += _bytes(4, _int(1, ids[name])
                           + _int(2, (start - t0) * 1000)
                           + _int(3, dur * 1000))
        plane = _int(1, pid + 1) + _bytes(2, plane_name.encode()) \
            + _bytes(3, line)
        for name, mid in ids.items():
            meta = _int(1, mid) + _bytes(2, name.encode())
            plane += _bytes(4, _int(1, mid) + _bytes(2, meta))
        out += _bytes(1, plane)
    return out


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("source")
    ap.add_argument("target")
    ap.add_argument("--from-ms", type=float, default=0.0)
    ap.add_argument("--ms", type=float, default=120.0)
    args = ap.parse_args(argv)
    per_device = xtrace.load_device_events(args.source)
    t0 = min(e[1] for evs in per_device.values() for e in evs)
    lo = t0 + int(args.from_ms * 1e6)
    hi = lo + int(args.ms * 1e6)
    kept = {plane: [(e[0].split(" = ")[0].lstrip("%"), e[1], e[2])
                    for e in evs if lo <= e[1] < hi]
            for plane, evs in per_device.items()}
    with open(args.target, "wb") as f:
        f.write(encode_xspace(kept))
    print({p: len(v) for p, v in kept.items()})
    return 0


if __name__ == "__main__":
    sys.exit(main())
