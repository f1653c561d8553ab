"""Share of the device's busy time spent in the one-step gated delta-rule
kernel (``KERNELS`` ``gdn_step`` of benchmark/flops: every live slot's
state in every linear-attention layer read, decayed, corrected, read out
and written in place, once a decode iteration)."""
from benchmark.cells import load_module

_moe = load_module("metrics", "moe_ffn_time_pct")
LAYER = "linear attention"
UNIT = "%"
BETTER = "lower"
SOURCE = "device_trace"
MOVES = "tpot_p90_ms"


def read(run):
    return _moe.busy_share(run, "gdn_step")
