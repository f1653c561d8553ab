"""Share of the device's busy time spent in the chunked state-space scan
(``KERNELS`` ``ssd_chunk_scan`` of benchmark/flops: a prompt's recurrence
in chunks as matrix products, once a state-space layer a prefill)."""
from benchmark.cells import load_module

_moe = load_module("metrics", "moe_ffn_time_pct")
LAYER = "state-space layers"
UNIT = "%"
BETTER = "lower"
SOURCE = "device_trace"
MOVES = "tpot_p90_ms"


def read(run):
    return _moe.busy_share(run, "ssd_chunk_scan")
