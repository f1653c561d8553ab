"""Median sub-window duration over the steps in a sub-window: the steady
step, which a stall inside the window does not move (the rate does)."""
from benchmark import rates

LAYER = "DP step builders"
UNIT = "ms"
BETTER = "lower"
SOURCE = "host_clock"
MOVES = "train_rate"


def read(run):
    if not getattr(run, "stamps", None):
        return None
    return rates.median_step_s(run.stamps, run.log_every) * 1e3
