"""One decode iteration of the decoder-hybrid-decoder against the memory
roofline: the bytes it must move (benchmark/flops ``decode_iteration_bytes``:
every matrix once, the shared store's cached positions once a reader, the
window rings, the recurrent state read and written) at the lengths the
program's counters give (``serving.shared_kv_tokens``,
``serving.window_tokens``: the host's own lengths of every launch, not a
guess from the mix), over the peak bytes a second, over ``decode_iter``
seconds (``serving.token_seconds``).  The iteration's host time is in the
denominator: this is the share of the roofline a request feels."""
LAYER = "serving"
UNIT = "%"
BETTER = "higher"
SOURCE = "program_counter"
MOVES = "tpot_p90_ms"


def read(run):
    iters = run.counter_delta("serving.decode_iterations")
    shared = run.counter_delta("serving.shared_kv_tokens")
    n = run.counter_delta("serving.token_seconds", "count")
    if not iters or not shared or not n or not run.peaks:
        return None
    seconds = run.counter_delta("serving.token_seconds", "sum") / n
    alive = (run.counter_delta("serving.tokens_generated")
             - run.counter_delta("serving.prefills")) / iters
    least = run.flops.decode_iteration_bytes(
        run.config["model"], shared / iters,
        run.counter_delta("serving.window_tokens") / iters, alive)
    return 100.0 * least / run.peaks["hbm_bytes_per_s"] / seconds
