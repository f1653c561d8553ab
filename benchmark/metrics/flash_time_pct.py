"""Share of the device's busy time spent in the hand-written flash
attention kernels (the Mosaic custom calls of the trace)."""
from benchmark import xtrace

LAYER = "kernels"
UNIT = "%"
BETTER = "lower"
SOURCE = "device_trace"
MOVES = "train_rate"


def read(run):
    t = run.trace
    kernels = [k for k in run.flops.KERNELS if k["name"] == "flash_attn"]
    if not t or not t["busy_s"] or not kernels:
        return None
    secs = xtrace.matched_seconds(t, kernels[0]["match"])
    return 100.0 * secs / t["busy_s"] if secs else None
