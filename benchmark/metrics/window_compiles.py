"""Compile requests inside the measured window: there should be none."""
LAYER = "compile cache"
UNIT = "count"
BETTER = "lower"
SOURCE = "program_counter"
MOVES = "setup_s"


def read(run):
    return float(run.cache.window["requests"])
