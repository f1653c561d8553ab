"""Collective time no compute covers, as a share of the traced window
(several chips only: one chip runs no collective)."""
LAYER = "DP step builders"
UNIT = "%"
BETTER = "lower"
SOURCE = "device_trace"
MOVES = "dp_train_rate"


def read(run):
    t = run.trace
    if not t or not t["window_s"] or run.chips < 2:
        return None
    return 100.0 * t["exposed_coll_s"] / t["window_s"]
