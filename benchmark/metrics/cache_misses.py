"""Programs that set-up had to compile because the persistent cache did not
hold them (0 on every run of a checkout after its first)."""
LAYER = "compile cache"
UNIT = "count"
BETTER = "lower"
SOURCE = "program_counter"
MOVES = "setup_s"


def read(run):
    return float(run.cache.setup["misses"])
