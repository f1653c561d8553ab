"""Share of the device's busy time spent choosing the ``index_topk`` best
of a slot's scores in the decode iterations (``KERNELS`` ``dsa_select`` of
benchmark/flops: the threshold kernel, a slot a grid step).  A prompt's
selection is XLA's and has no name of its own in the trace: not in it."""
from benchmark.cells import load_module

_moe = load_module("metrics", "moe_ffn_time_pct")
LAYER = "sparse attention"
UNIT = "%"
BETTER = "lower"
SOURCE = "device_trace"
MOVES = "tpot_p90_ms"


def read(run):
    return _moe.busy_share(run, "dsa_select")
