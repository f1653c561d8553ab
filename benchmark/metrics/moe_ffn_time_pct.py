"""Share of the device's busy time spent in the expert products the
trace can name (``KERNELS`` ``moe_ffn`` of benchmark/flops: the routed
experts' grouped products; the shared expert's are plain matmul fusions
like the attention's and cannot be told apart by name)."""
from benchmark import xtrace

LAYER = "expert layer"
UNIT = "%"
BETTER = "lower"
SOURCE = "device_trace"
MOVES = "tpot_p90_ms"


def kernel(run, name):
    found = [k for k in getattr(run.flops, "KERNELS", ()) if k["name"] == name]
    return found[0] if found else None


def busy_share(run, name):
    """Busy-time share of the ops ``KERNELS[name]`` matches, or None."""
    t, k = run.trace, kernel(run, name)
    if not t or not t["busy_s"] or not k:
        return None
    secs = xtrace.matched_seconds(t, k["match"])
    return 100.0 * secs / t["busy_s"] if secs else None


def read(run):
    return busy_share(run, "moe_ffn")
