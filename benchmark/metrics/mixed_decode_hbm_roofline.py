"""One STEADY decode pass of a model with window and full layers and held
experts against the memory roofline: the bytes the pass MUST move
(benchmark/flops ``decode_pass_bytes``: every matrix outside the routed
experts once, the held experts that got a token by the program's counter
``serving.moe_experts_touched``, the full group's cached positions by
``serving.shared_kv_tokens`` and the window group's by
``serving.window_tokens``, each layer of its group once) over the peak
bytes a second, over ``steady_pass_ms`` (``serving.pass_seconds.steady``).
What the program moves beside that (the gather into the view, written and
read again) is not counted, so the share stays under 100; host time is in
the denominator: it is the share a request feels."""
from benchmark.cells import load_module

LAYER = "serving"
UNIT = "%"
BETTER = "higher"
SOURCE = "program_counter"
MOVES = "tpot_p90_ms"


def read(run):
    iters = run.counter_delta("serving.decode_iterations")
    full = run.counter_delta("serving.shared_kv_tokens")
    window = run.counter_delta("serving.window_tokens")
    touched = run.counter_delta("serving.moe_experts_touched")
    steady = load_module("metrics", "steady_pass_ms").read(run)
    least = getattr(run.flops, "decode_pass_bytes", None)
    if (not iters or not full or not window or not touched or not steady
            or least is None or not run.peaks):
        return None
    alive = (run.counter_delta("serving.tokens_generated")
             - run.counter_delta("serving.prefills")) / iters
    nbytes = least(run.config["model"], touched / iters, full / iters,
                   window / iters, alive)
    return 100.0 * nbytes / run.peaks["hbm_bytes_per_s"] / (steady / 1e3)
