"""Share of the device's busy time in the part of the latent attention
the trace can NAME (``KERNELS`` ``latent_attn`` of benchmark/flops): the
masked softmax, the view's relayout and the ladder's conditional itself.
The view's gather and the two absorbed products are plain ``fusion``s and
are not in it, so this is a floor of the layer's share; it falls to
nothing when a kernel takes the layer over."""
from benchmark.cells import load_module

_moe = load_module("metrics", "moe_ffn_time_pct")
LAYER = "latent attention"
UNIT = "%"
BETTER = "lower"
SOURCE = "device_trace"
MOVES = "tpot_p90_ms"


def read(run):
    return _moe.busy_share(run, "latent_attn")
