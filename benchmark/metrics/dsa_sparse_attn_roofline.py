"""The decode's attention over the selection against its roofline, IN THE
FORM THE TREE HOLDS: the kernel copies the live slots' whole pages and
masks what the indexer did not select (PERF.md section 6, PR 49: a transfer
a selected row cost more than the page around it at these contexts), so the
least bytes are the 1280-byte padded latent row of EVERY cached position of
every live slot in every layer (``sparse_attn_bytes`` of benchmark/flops
over ``serving.dsa_scored_tokens_traced``, the iterations retired while
the trace recorded), at the peak bytes a second, over the device time the
trace shows for ``dsa_sparse_attn``.  A form that read the
selected rows alone would be held to ``serving.dsa_selected_tokens`` x
1280 B: ``dsa_selected_share_pct`` of these bytes."""
from benchmark.cells import load_module

_index = load_module("metrics", "dsa_index_roofline")
LAYER = "sparse attention"
UNIT = "%"
BETTER = "higher"
SOURCE = "device_trace"
MOVES = "tpot_p90_ms"


def read(run):
    return _index.share(run, "dsa_sparse_attn", "sparse_attn_bytes")
