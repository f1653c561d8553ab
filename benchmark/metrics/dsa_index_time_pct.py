"""Share of the device's busy time spent scoring cached tokens for the
lightning indexer (``KERNELS`` ``dsa_index_score`` of benchmark/flops: the
kernel that walks the page table over the indexer's keys, every cached
position of every live slot in every layer, once a decode iteration).  A
prompt's index scores are plain matmul fusions and are not in it."""
from benchmark.cells import load_module

_moe = load_module("metrics", "moe_ffn_time_pct")
LAYER = "sparse attention"
UNIT = "%"
BETTER = "lower"
SOURCE = "device_trace"
MOVES = "tpot_p90_ms"


def read(run):
    return _moe.busy_share(run, "dsa_index_score")
