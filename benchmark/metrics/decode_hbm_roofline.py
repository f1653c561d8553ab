"""One decode iteration against the memory roofline: the bytes it must
read (benchmark/flops: every matrix of attention, dense layers, shared
experts, routers and head once, the routed experts that got a token by
the program's counter, and the cached entries of the slots alive, each
counted at the mix's shortest prompt because the counters give no
lengths) over the peak bytes a second, over ``decode_iter_ms``.  The
iteration's host time is in the denominator: this is the share of the
roofline a request feels, not the kernels'."""
LAYER = "serving"
UNIT = "%"
BETTER = "higher"
SOURCE = "program_counter"
MOVES = "tpot_p90_ms"


def read(run):
    iters = run.counter_delta("serving.decode_iterations")
    touched = run.counter_delta("serving.moe_experts_touched")
    n = run.counter_delta("serving.token_seconds", "count")
    if not iters or not touched or not n or not run.peaks:
        return None
    seconds = run.counter_delta("serving.token_seconds", "sum") / n
    alive = (run.counter_delta("serving.tokens_generated")
             - run.counter_delta("serving.prefills")) / iters
    least = run.flops.decode_iteration_bytes(
        run.config["model"], touched / iters,
        alive * run.traffic["prompt_tokens"]["min"], alive)
    return 100.0 * least / run.peaks["hbm_bytes_per_s"] / seconds
