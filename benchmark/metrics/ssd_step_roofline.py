"""The one-step state-space kernel against its roofline: the least time the
chip could take for the bytes ``ssd_step_bytes`` of benchmark/flops gives
(the float32 state of every LIVE slot in every state-space layer, read and
written; an idle slot's state is no work, so a kernel that moves it reads
lower for it) at the peak bytes a second, over the device time the trace
shows for the kernel.  The live (slot, layer) pairs come from the program's
own count, ``serving.state_bytes_moved`` (the host's lengths of every
launch), not from a guess at the mix.

The trace covers a few seconds of the window and the counter all of it, so
the window's least time is scaled by the traced share of the window, as
``ssm_scan_roofline`` is.  The window holds the drain after the last
arrival, when fewer slots are alive than in the traced seconds (a quarter
of the way in), so the scaled count is UNDER what the traced iterations
moved and the share reads low, never over what the kernel did (PERF.md
section 6, PR 39, has three traced runs)."""
from benchmark import xtrace
from benchmark.cells import load_module

_moe = load_module("metrics", "moe_ffn_time_pct")
LAYER = "state-space layers"
UNIT = "%"
BETTER = "higher"
SOURCE = "device_trace"
MOVES = "tpot_p90_ms"


def traced_share(run):
    """The traced seconds over the window's (first due to last response),
    or None where the run has no trace or no finished request."""
    requests = [r for r in getattr(run, "requests", None) or () if r.ok]
    if not run.trace or not requests:
        return None
    window = (max(r.responded for r in requests)
              - min(r.due for r in requests))
    return run.trace["window_s"] / window if window > 0 else None


def read(run):
    k, share = _moe.kernel(run, "ssd_step"), traced_share(run)
    moved = run.counter_delta("serving.state_bytes_moved")
    if not k or not share or not run.peaks or not moved:
        return None
    secs = xtrace.matched_seconds(run.trace, k["match"])
    if not secs:
        return None
    model, flops = run.config["model"], run.flops
    slot_layers = moved / (2 * flops.slot_state_bytes(model))
    least = flops.ssd_step_bytes(model, slot_layers)
    return 100.0 * least / run.peaks["hbm_bytes_per_s"] * share / secs
