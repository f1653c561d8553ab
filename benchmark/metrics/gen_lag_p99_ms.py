"""How late the load generator ran: 99th percentile of (sent - due).  A
starved generator must not be read as a fast server."""
from benchmark import loadgen

LAYER = "load generator"
UNIT = "ms"
BETTER = "lower"
SOURCE = "host_clock"
MOVES = "tpot_p50_ms"


def read(run):
    reqs = getattr(run, "requests", None)
    if not reqs:
        return None
    return loadgen.percentile([1e3 * (r.sent - r.due) for r in reqs], 99)
