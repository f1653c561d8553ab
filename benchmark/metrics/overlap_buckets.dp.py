"""Gradient buckets the stream schedule dispatched per step in the window
(several chips only).  ``overlap.fallbacks`` must not move: the driver of
the cell fails the run if it does."""
LAYER = "DP step builders"
UNIT = "count"
BETTER = "lower"
SOURCE = "program_counter"
MOVES = "dp_train_rate"


def read(run):
    moved = run.counter_delta("overlap.buckets_dispatched")
    if not moved or not getattr(run, "steps_in_window", 0):
        return None
    return moved / run.steps_in_window
