"""Share of the device's busy time in the part of the gated grouped-query
attention the trace can NAME (``KERNELS`` ``gqa_attn`` of benchmark/flops):
the op classes that occur in the decode and prefill programs' attention and
nowhere else in them (the masked softmax, the view's relayout, the rung's
conditional, the rotation).  The projections, the gathers into the view and
the two products of a rung are plain ``fusion``s like every other matmul
and copy, so this is a floor of the layer's share."""
from benchmark.cells import load_module

_moe = load_module("metrics", "moe_ffn_time_pct")
LAYER = "grouped-query attention"
UNIT = "%"
BETTER = "lower"
SOURCE = "device_trace"
MOVES = "tpot_p90_ms"


def read(run):
    return _moe.busy_share(run, "gqa_attn")
