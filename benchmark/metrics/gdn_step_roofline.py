"""The one-step gated delta-rule kernel against its roofline: the least
time the chip could take for the bytes ``gdn_step_bytes`` of
benchmark/flops gives (the float32 state of every LIVE slot in every
linear-attention layer, read and written; an idle slot's state is no work,
so a kernel that moves it reads lower for it) at the peak bytes a second,
over the device time the trace shows for the kernel.  The live (slot,
layer) pairs come from the program's own count,
``serving.state_bytes_moved`` (the host's lengths of every launch), not
from a guess at the mix.

Scaled to the traced share of the window as ``ssd_step_roofline`` is (its
docstring has why the share reads low and never over what the kernel did:
the window holds the drain, when fewer slots are alive than in the traced
seconds)."""
from benchmark import xtrace
from benchmark.cells import load_module

_moe = load_module("metrics", "moe_ffn_time_pct")
_ssd = load_module("metrics", "ssd_step_roofline")
LAYER = "linear attention"
UNIT = "%"
BETTER = "higher"
SOURCE = "device_trace"
MOVES = "tpot_p90_ms"


def read(run):
    k, share = _moe.kernel(run, "gdn_step"), _ssd.traced_share(run)
    moved = run.counter_delta("serving.state_bytes_moved")
    if not k or not share or not run.peaks or not moved:
        return None
    secs = xtrace.matched_seconds(run.trace, k["match"])
    if not secs:
        return None
    model, flops = run.config["model"], run.flops
    slot_layers = moved / (2 * flops.slot_state_bytes(model))
    least = flops.gdn_step_bytes(model, slot_layers)
    return 100.0 * least / run.peaks["hbm_bytes_per_s"] * share / secs
