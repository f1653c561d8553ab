"""The chunked gated delta-rule scan against its roofline: the least time
the chip could take for ``gdn_chunk_flops`` of benchmark/flops (the chunked
form's operations at chunks of 64 for the REAL prompt tokens the program
counted, ``serving.prefill_tokens``, in every linear-attention layer; a
bucket's padding is not among them) at the bfloat16 peak, over the device
time the trace shows for the kernel.  The kernel's products are 64 rows
tall, its state products and its triangular inverse run in float32 (six
passes of the matrix unit each) and the inverse is not counted at all, so
this reads LOW by construction; it is here so that a change to the kernel
shows.

Scaled to the traced share of the window as ``gdn_step_roofline`` is;
prefills come in bursts, so the share moves with which of them the traced
seconds caught."""
from benchmark import xtrace
from benchmark.cells import load_module

_moe = load_module("metrics", "moe_ffn_time_pct")
_ssd = load_module("metrics", "ssd_step_roofline")
LAYER = "linear attention"
UNIT = "%"
BETTER = "higher"
SOURCE = "device_trace"
MOVES = "tpot_p90_ms"


def read(run):
    k, share = _moe.kernel(run, "gdn_chunk_scan"), _ssd.traced_share(run)
    tokens = run.counter_delta("serving.prefill_tokens")
    if not k or not share or not run.peaks or not tokens:
        return None
    secs = xtrace.matched_seconds(run.trace, k["match"])
    if not secs:
        return None
    model, flops = run.config["model"], run.flops
    layers = flops.layer_counts(model)[flops.LINEAR]
    least = (flops.gdn_chunk_flops(model, layers * tokens)
             / run.peaks["bf16_flops"])
    return 100.0 * least * share / secs
