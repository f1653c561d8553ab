"""Share of the device's busy time spent in the selective-scan kernel
(``KERNELS`` ``ssm_scan`` of benchmark/flops: the state-space layers'
recurrence over a prompt; decode's one-step update is plain fusions and is
not in it)."""
from benchmark.cells import load_module

_moe = load_module("metrics", "moe_ffn_time_pct")
LAYER = "state-space layers"
UNIT = "%"
BETTER = "lower"
SOURCE = "device_trace"
MOVES = "tpot_p90_ms"


def read(run):
    return _moe.busy_share(run, "ssm_scan")
