"""What every kind of cell shares: the run record the per-layer readers
read, the profiler window, and the result line."""

from __future__ import annotations

import json
import os
import shutil
import sys
import time
from typing import Optional

from benchmark import cells, xtrace


class Run:
    """Everything one run measured; a per-layer reader takes what it needs
    and returns ``None`` where there is nothing to read."""

    def __init__(self, resolved: dict, chips: int, peaks: Optional[dict],
                 cache) -> None:
        self.cell = resolved["cell"]
        self.config = resolved["config"]
        self.traffic = resolved["traffic"]
        self.flops = resolved["flops"]
        self.chips = chips
        self.peaks = peaks
        self.cache = cache
        self.trace = None            # xtrace.reduce_trace(...) of a traced run
        self.counters_before = {}
        self.counters_after = {}
        self.notes = {}

    def counter_delta(self, name: str, field: str = "value") -> float:
        a = self.counters_after.get(name, {}).get(field, 0)
        b = self.counters_before.get(name, {}).get(field, 0)
        return a - b


def say(tag: str, payload: dict) -> None:
    """An earlier stdout line (never the last): ``tag`` then JSON."""
    print(f"benchmark:{tag} {json.dumps(payload)}", flush=True)


class TraceWindow:
    """A few seconds of the profiler inside the measured window."""

    def __init__(self, enabled: bool, directory: str) -> None:
        self.enabled = enabled
        self.dir = directory
        self.started_at = None
        self.stopped = False

    def start(self) -> None:
        import jax

        shutil.rmtree(self.dir, ignore_errors=True)
        os.makedirs(self.dir, exist_ok=True)
        jax.profiler.start_trace(self.dir)
        self.started_at = time.perf_counter()

    def stop(self) -> None:
        import jax

        jax.profiler.stop_trace()
        self.stopped = True

    def reduce(self) -> Optional[dict]:
        if not self.stopped:
            return None
        path = xtrace.find_xplane(self.dir)
        reduced = xtrace.reduce_trace(xtrace.load_device_events(path))
        keep = os.environ.get("BENCHMARK_KEEP_TRACE")
        if keep:
            os.makedirs(keep, exist_ok=True)
            shutil.copy(path, keep)
        shutil.rmtree(self.dir, ignore_errors=True)
        return reduced


def per_layer_metrics(run: Run, wanted: list) -> dict:
    out = {}
    for m in wanted:
        reader = cells.load_module("metrics", m["name"])
        value = reader.read(run)
        if value is not None:
            out[m["name"]] = {"value": float(value), "unit": m["unit"]}
    return out


def result_line(correct: bool, attempted: int, failed: int, metrics: dict,
                device: dict, breakdown: Optional[dict]) -> str:
    line = {"correct": bool(correct), "attempted": int(attempted),
            "failed": int(failed), "metrics": metrics, "device": device}
    if breakdown is not None:
        line["breakdown"] = breakdown
    return json.dumps(line)


def trace_dir() -> str:
    return os.path.join(cells.ROOT, ".benchmark_trace")


def fail(msg: str) -> None:
    print(f"benchmark: {msg}", file=sys.stderr, flush=True)
    raise SystemExit(1)
