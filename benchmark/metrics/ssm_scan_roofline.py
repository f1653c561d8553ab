"""The selective-scan kernel against its roofline: the least time the chip
could take for the bytes ``ssm_scan_bytes`` of benchmark/flops gives (the
real prompt tokens the window prefilled, in every state-space layer) at the
peak bytes a second, over the device time the trace shows for the kernel.

The peak table has no rate for the vector unit, and the recurrence is
sequential in time: each step is a few vector operations on a ``[d_state,
lanes]`` state that wait for the step before.  Against memory bandwidth
alone this reads LOW, and a kernel that reads 15% is not seven times off
its best.  It is here so that a change to the kernel shows.

The trace covers a few seconds of the window and the counters all of it, so
the window's least time is scaled by the traced share of the window;
prefills come in bursts, so the share moves with which of them the traced
seconds caught."""
from benchmark import xtrace
from benchmark.cells import load_module

_moe = load_module("metrics", "moe_ffn_time_pct")
LAYER = "state-space layers"
UNIT = "%"
BETTER = "higher"
SOURCE = "device_trace"
MOVES = "tpot_p90_ms"


def read(run):
    t, k = run.trace, _moe.kernel(run, "ssm_scan")
    requests = [r for r in getattr(run, "requests", ()) if r.ok]
    tokens = run.counter_delta("serving.prefill_tokens")
    if not t or not k or not requests or not run.peaks or not tokens:
        return None
    secs = xtrace.matched_seconds(t, k["match"])
    if not secs:
        return None
    model, flops = run.config["model"], run.flops
    layers = flops.layer_counts(model)["ssm"]
    least = flops.ssm_scan_bytes(
        model, layers * tokens,
        layers * run.counter_delta("serving.prefills"))
    window = (max(r.responded for r in requests)
              - min(r.due for r in requests))
    return (100.0 * least / run.peaks["hbm_bytes_per_s"]
            * t["window_s"] / window / secs)
