"""Median time from a request's due instant to its first token.  Named as
ISSUE 23 names the end-to-end metric it was meant to be: over PR 23's runs
it spread by 6.0% of its median, with the order of arrivals permuted per
seed at 40 s and again with the order fixed at 51 s (the median wait swings
on where each arrival lands in the decode iteration), more than a bound of
at most 10% can be five times of, so it is read here until a later
``benchmark`` issue can promote it."""
from benchmark import loadgen

LAYER = "serving"
UNIT = "ms"
BETTER = "lower"
SOURCE = "host_clock"
MOVES = "tpot_p50_ms"


def read(run):
    v = getattr(run, "ttft_ms", None)
    return loadgen.percentile(v, 50) if v else None
