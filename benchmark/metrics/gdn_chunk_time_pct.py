"""Share of the device's busy time spent in the chunked gated delta-rule
scan (``KERNELS`` ``gdn_chunk_scan`` of benchmark/flops: a prompt's
recurrence in chunks of 64 as matrix products and one triangular inverse a
chunk a head, once a linear-attention layer a prefill)."""
from benchmark.cells import load_module

_moe = load_module("metrics", "moe_ffn_time_pct")
LAYER = "linear attention"
UNIT = "%"
BETTER = "lower"
SOURCE = "device_trace"
MOVES = "tpot_p90_ms"


def read(run):
    return _moe.busy_share(run, "gdn_chunk_scan")
