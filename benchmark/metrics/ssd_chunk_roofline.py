"""The chunked state-space scan against its roofline: the least time the
chip could take for ``ssd_chunk_work`` of benchmark/flops (the real prompt
tokens the window prefilled, in every state-space layer): the LARGER of its
operations at the bfloat16 peak and its bytes at the peak bytes a second,
over the device time the trace shows for the kernel.  Most of a chunk is
vector work the peak table has no rate for (the decay matrix: an
exponential and three products a pair of steps a head), so this reads LOW
by construction; it is here so that a change to the kernel shows.

Scaled to the traced share of the window as ``ssd_step_roofline`` is;
prefills come in bursts, so the share moves with which of them the traced
seconds caught."""
from benchmark import xtrace
from benchmark.cells import load_module

_moe = load_module("metrics", "moe_ffn_time_pct")
_step = load_module("metrics", "ssd_step_roofline")
LAYER = "state-space layers"
UNIT = "%"
BETTER = "higher"
SOURCE = "device_trace"
MOVES = "tpot_p90_ms"


def read(run):
    k, share = _moe.kernel(run, "ssd_chunk_scan"), _step.traced_share(run)
    tokens = run.counter_delta("serving.prefill_tokens")
    if not k or not share or not run.peaks or not tokens:
        return None
    secs = xtrace.matched_seconds(run.trace, k["match"])
    if not secs:
        return None
    model, flops = run.config["model"], run.flops
    layers = flops.layer_counts(model)["mamba"]
    work = flops.ssd_chunk_work(
        model, layers * tokens,
        layers * run.counter_delta("serving.prefills"))
    least = max(work["flops"] / run.peaks["bf16_flops"],
                work["bytes"] / run.peaks["hbm_bytes_per_s"])
    return 100.0 * least * share / secs
