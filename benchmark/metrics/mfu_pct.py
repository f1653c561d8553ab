"""Model FLOP/s utilisation, from the device trace: the analytic count of
benchmark/flops for the steps the trace holds, over the traced window's
length times the chip's published bf16 peak.  Not cut at 100: a reading
above it means the count is too high or the window leaves out work."""
LAYER = "DP step builders"
UNIT = "%"
BETTER = "higher"
SOURCE = "device_trace"
MOVES = "train_rate"


def read(run):
    t = run.trace
    steps = getattr(run, "traced_steps", 0)
    if not t or not t["window_s"] or not steps:
        return None
    per_item = run.flops.train_flops_per_item(run.config["model"],
                                              run.traffic)
    items_a_chip = run.items_per_step / run.chips
    return (100.0 * per_item * items_a_chip * steps
            / (t["window_s"] * run.peaks["bf16_flops"]))
