"""The flash kernels' share of their roofline: the least time the chip
could take for the traced steps' attention (the larger of operations over
peak FLOP/s and bytes over peak bytes/s, from benchmark/flops) over the
device time the kernels took.  ``run.notes`` says which bound it is."""
from benchmark import xtrace

LAYER = "kernels"
UNIT = "%"
BETTER = "higher"
SOURCE = "device_trace"
MOVES = "train_rate"


def read(run):
    t = run.trace
    kernels = [k for k in run.flops.KERNELS if k["name"] == "flash_attn"]
    steps = getattr(run, "traced_steps", 0)
    if not t or not kernels or not steps:
        return None
    secs = xtrace.matched_seconds(t, kernels[0]["match"])
    if not secs:
        return None
    work = kernels[0]["work"](run.config["model"], run.traffic,
                              run.traffic["per_chip_batch"])
    by_flops = work["flops"] / run.peaks["bf16_flops"]
    by_bytes = work["bytes"] / run.peaks["hbm_bytes_per_s"]
    run.notes["flash_attn_roofline_bound"] = (
        "compute" if by_flops >= by_bytes else "memory")
    return 100.0 * max(by_flops, by_bytes) * steps / secs
