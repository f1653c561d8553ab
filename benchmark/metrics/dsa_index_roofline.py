"""The index-scoring kernel against its roofline: the least time the chip
could take for the bytes ``index_score_bytes`` of benchmark/flops gives
(the 256-byte key of every position scored) at the peak bytes a second,
over the device time the trace shows for the kernel.  The kernel's
operations (64 heads x 128 x 2 a key) are 16 us a million keys at the
chip's peak against 312 us for their bytes: memory bounds it.

The positions are those of the decode iterations retired WHILE THE TRACE
RECORDED (``serving.dsa_scored_tokens_traced``: the host's lengths of
those launches, live slots and layers summed), not the window's count
scaled to the traced share of the window as the older rooflines are: in
this traffic a prompt pass holds the device for one to three seconds, so a
three-second trace holds anything from a fifth to all of its time in
decode iterations, and the scaled share read 10% in one run and 104% in
the next (PERF.md section 6, PR 49).  The loop retires an iteration one
behind the device, so one iteration at each edge of the trace is counted
on the wrong side: a hundredth or two of the count."""
from benchmark import xtrace
from benchmark.cells import load_module

_moe = load_module("metrics", "moe_ffn_time_pct")
LAYER = "sparse attention"
UNIT = "%"
BETTER = "higher"
SOURCE = "device_trace"
MOVES = "tpot_p90_ms"


def share(run, kernel: str, least_bytes: str):
    """``flops.<least_bytes>(model, positions scored while the trace
    recorded)`` at the peak bytes a second over ``kernel``'s traced
    seconds, or None."""
    k = _moe.kernel(run, kernel)
    scored = run.counter_delta("serving.dsa_scored_tokens_traced")
    count = getattr(run.flops, least_bytes, None)
    if not k or not run.trace or not run.peaks or not scored or not count:
        return None
    secs = xtrace.matched_seconds(run.trace, k["match"])
    if not secs:
        return None
    least = count(run.config["model"], scored)
    return 100.0 * least / run.peaks["hbm_bytes_per_s"] / secs


def read(run):
    return share(run, "dsa_index_score", "index_score_bytes")
