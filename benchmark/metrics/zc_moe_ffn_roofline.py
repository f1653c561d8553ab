"""The held real experts' grouped products, in a layer whose router also
has zero-compute outputs, against their roofline: the least time the chip
could take for them (the larger of operations over peak FLOP/s and bytes
over peak bytes/s, benchmark/flops ``moe_ffn_work``; the note says which)
over the device time the trace shows for them (``KERNELS`` ``moe_ffn``).

``moe_ffn_roofline`` reckons a prefill's pairs on held experts as
``k x held / published``; with zero-compute outputs beside the real ones
the pairs spread over ALL of the router's outputs, so this reader takes
the share from the configuration's own ``flops`` file (``held_pair_share``)
and reads nothing where the file has none.  Decode iterations are counted
from the counters (pairs and touched experts as the program counted them);
prefills, which the counters do not see, at the mix's mean prompt.  The
trace covers a few seconds of the window and the counters all of it, so
the window's least time is scaled by the traced share of the window."""
from benchmark import xtrace
from benchmark.cells import load_module

_moe = load_module("metrics", "moe_ffn_time_pct")
LAYER = "expert layer"
UNIT = "%"
BETTER = "higher"
SOURCE = "device_trace"
MOVES = "tpot_p90_ms"


def window_work(run):
    """Least operations and bytes of the held experts' products over the
    whole measured window, or None."""
    model, flops = run.config["model"], run.flops
    share = getattr(flops, "held_pair_share", None)
    if share is None or not run.counter_delta("serving.decode_iterations"):
        return None
    _, layers = flops.layer_counts(model)
    decode = flops.moe_ffn_work(
        model, run.counter_delta("serving.moe_assignments"),
        run.counter_delta("serving.moe_experts_touched"))
    prefills = run.counter_delta("serving.prefills")
    tokens = flops.mean_prompt_tokens(run.traffic)
    prefill = flops.moe_ffn_work(
        model, prefills * layers * tokens * share(model),
        prefills * layers * flops.expected_touched(model, tokens))
    return {k: decode[k] + prefill[k] for k in decode}


def read(run):
    t, k = run.trace, _moe.kernel(run, "moe_ffn")
    requests = [r for r in getattr(run, "requests", ()) if r.ok]
    if not t or not k or not requests or not run.peaks:
        return None
    secs = xtrace.matched_seconds(t, k["match"])
    work = window_work(run) if secs else None
    if not work:
        return None
    window = (max(r.responded for r in requests)
              - min(r.due for r in requests))
    by_flops = work["flops"] / run.peaks["bf16_flops"]
    by_bytes = work["bytes"] / run.peaks["hbm_bytes_per_s"]
    run.notes["zc_moe_ffn_roofline_bound"] = (
        "compute" if by_flops >= by_bytes else "memory")
    return (100.0 * max(by_flops, by_bytes) * t["window_s"] / window
            / secs)
