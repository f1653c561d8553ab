"""90th percentile of time from a request's due instant to its first
token (the tail beside `ttft_p50_ms`)."""
from benchmark import loadgen

LAYER = "serving"
UNIT = "ms"
BETTER = "lower"
SOURCE = "host_clock"
MOVES = "tpot_p50_ms"


def read(run):
    v = getattr(run, "ttft_ms", None)
    return loadgen.percentile(v, 90) if v else None
