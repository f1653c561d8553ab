"""Share of the device's busy time spent in the decode's latent attention
over the indexer's selection (``KERNELS`` ``dsa_sparse_attn`` of
benchmark/flops)."""
from benchmark.cells import load_module

_moe = load_module("metrics", "moe_ffn_time_pct")
LAYER = "sparse attention"
UNIT = "%"
BETTER = "lower"
SOURCE = "device_trace"
MOVES = "tpot_p90_ms"


def read(run):
    return _moe.busy_share(run, "dsa_sparse_attn")
