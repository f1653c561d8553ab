"""One STEADY decode pass against the memory roofline: the cell's
``decode_hbm_roofline`` (or, where that reads nothing,
``hybrid_decode_hbm_roofline``) with ``steady_pass_ms`` in the place of
``decode_iter_ms``.  Both are bytes over peak over seconds, so the share is
rescaled by the ratio of the two times.  ``decode_iter_ms`` averages passes
of every kind, so an admission that a pass waits out lowers the older share
though the device does no less; this one has no admission in its
denominator.  Host time still is: it is the share a request feels."""
from benchmark.cells import load_module

LAYER = "serving"
UNIT = "%"
BETTER = "higher"
SOURCE = "program_counter"
MOVES = "tpot_p90_ms"


def read(run):
    steady = load_module("metrics", "steady_pass_ms").read(run)
    whole = load_module("metrics", "decode_iter_ms").read(run)
    if not steady or not whole:
        return None
    share = load_module("metrics", "decode_hbm_roofline").read(run)
    if share is None:
        share = load_module("metrics", "hybrid_decode_hbm_roofline").read(run)
    if share is None:
        return None
    return share * whole / steady
