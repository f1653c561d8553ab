"""The device as jax reports it, the table of published peaks, and the
rule that a measurement never falls back to the CPU.

The benchmark's own copy (bench.py has the original, see PERF.md Open
questions): later PRs may change the program and may not change the
yardstick.
"""

from __future__ import annotations

# Published peaks of one chip, keyed by the exact
# ``jax.devices()[0].device_kind``.  Source: Google Cloud documentation,
# "TPU v5e": 197 TFLOP/s bf16, 393 TOP/s int8, 16 GB HBM at 819 GB/s,
# 1600 Gbit/s chip-to-chip.  A v5e reports itself as "TPU v5 lite".  A kind
# that is not here is an error, not a default: add it with its source.
PEAKS = {
    "TPU v5 lite": {"bf16_flops": 197e12, "hbm_bytes_per_s": 819e9,
                    "hbm_bytes": 16e9,
                    "source": "Google Cloud documentation, TPU v5e"},
}


class NoChip(SystemExit):
    """Raised (exit code 4) when the run cannot be a chip run."""

    def __init__(self, msg: str) -> None:
        super().__init__(4)
        self.msg = msg


def peaks(device_kind: str) -> dict:
    try:
        return PEAKS[device_kind]
    except KeyError:
        raise RuntimeError(
            f"device_kind {device_kind!r} is not in benchmark.device.PEAKS "
            f"(known: {sorted(PEAKS)}); add its published peaks with the "
            f"source before measuring on it") from None


def device_info() -> dict:
    import jax

    dev = jax.devices()[0]
    return {"platform": dev.platform, "kind": dev.device_kind,
            "count": jax.device_count()}


def require_tpu(chips: int) -> dict:
    """:func:`device_info`, or exit non-zero naming what was found: no TPU,
    fewer chips than the cell asks for, or a kind with no published peak."""
    import sys

    info = device_info()
    if info["platform"] != "tpu":
        print(f"benchmark: no TPU: jax found platform {info['platform']!r} "
              f"({info['kind']!r} x {info['count']}); a cell runs on the "
              f"chip and does not fall back", file=sys.stderr)
        raise NoChip("no TPU")
    if info["count"] < chips:
        print(f"benchmark: the cell needs {chips} chips, jax found "
              f"{info['count']}", file=sys.stderr)
        raise NoChip("too few chips")
    peaks(info["kind"])
    return info


def memory_peak_bytes(devices) -> int:
    """Peak bytes held on the fullest of ``devices``: live buffers plus what
    the runtime reserved for the compiled programs' temporaries (on a TPU
    the two are disjoint: ``largest_free_block`` is the limit less both; a
    training step's activations are all in the second).  0 where the
    backend keeps no statistics, i.e. the CPU rehearsal."""
    def held(d):
        s = d.memory_stats() or {}
        return (int(s.get("peak_bytes_in_use", 0))
                + int(s.get("peak_bytes_reserved", 0)))

    return max(held(d) for d in devices)
