"""Run one cell of the benchmark once.

    python -m benchmark.run --workload <name> --seed <n> --seconds <s> --trace <0|1>

One process: load, warm up only this cell's shapes, measure for
``--seconds``, check the timed path against the plain reference, print the
result as the last line of stdout.  Exits non-zero, with no result line,
when jax finds no TPU, fewer chips than the cell asks for, or a chip whose
``device_kind`` is not in the benchmark's own peak table.
"""

from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402

_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if _ROOT not in sys.path:       # ``python benchmark/run.py`` as well as -m
    sys.path.insert(0, _ROOT)

from benchmark import cells, device, harness  # noqa: E402
from benchmark.cachecount import CacheCounter  # noqa: E402


def measure(workload: str, seed: int, seconds: float, trace: bool,
            info: dict, t_start: float, **kw) -> str:
    """Everything after the look for a chip; returns the result line."""
    import jax

    import horovod_tpu as hvd

    bench = kw.pop("bench", None) or cells.load_benchmark()
    resolved = cells.resolve(bench, workload, kw.pop("base", None))
    chips = resolved["cell"]["chips"]
    cache = CacheCounter().install()
    hvd.init()
    try:
        peaks = device.PEAKS.get(info["kind"])
        run = harness.Run(resolved, chips, peaks, cache)
        # The traffic file names what drives it (``package.module:function``).
        out = cells.resolve_callable(resolved["traffic"]["runner"])(
            resolved, seed, seconds, trace, run, t_start, **kw)
    finally:
        hvd.shutdown()
    dev = dict(info, memory_peak_bytes=out["memory_peak_bytes"])
    breakdown = None
    if trace:
        if not run.trace or not run.trace["busy_s"]:
            harness.fail("the traced window holds no device operation")
        dev["busy_s"] = run.trace["busy_s"]
        dev["window_s"] = run.trace["window_s"]
        breakdown = {"device_ops": run.trace["device_ops"],
                     "idle_gaps": run.trace["idle_gaps"]}
        metrics = harness.per_layer_metrics(run, resolved["per_layer"])
        harness.say("trace", {
            "devices": run.trace["devices"],
            "busy_window_s_per_device": run.trace["per_device"],
            "traced_steps": getattr(run, "traced_steps", None)})
        if run.notes:
            harness.say("notes", run.notes)
    else:
        metrics = {m["name"]: {"value": float(out["end_to_end"][m["name"]]),
                               "unit": m["unit"]}
                   for m in resolved["end_to_end"]
                   if m["name"] in out["end_to_end"]}
    return harness.result_line(out["correct"], out["attempted"],
                               out["failed"], metrics, dev, breakdown)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    cell = cells.find_cell(cells.load_benchmark(), args.workload)
    info = device.require_tpu(cell["chips"])
    line = measure(args.workload, args.seed, args.seconds, bool(args.trace),
                   info, T_START)
    print(line, flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
