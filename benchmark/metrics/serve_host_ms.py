"""Host time of one serving iteration that is neither waiting for the device
nor prefilling: (``serve.iteration`` - ``serve.logits_wait`` -
``serve.prefill``) over the window's decode iterations: admit, page
``ensure``, device tables, launch, Python sampling and ``_feed``."""
from benchmark.cells import load_module

_base = load_module("metrics", "step_host_ms")
LAYER = "serving"
UNIT = "ms"
BETTER = "lower"
SOURCE = "program_counter"
MOVES = "tpot_p50_ms"


def per_iteration_ms(run, secs):
    iters = run.counter_delta("serving.decode_iterations")
    if secs is None or not iters:
        return None
    return 1e3 * secs / iters


def read(run):
    whole = _base.span_sum_s(run, "serve.iteration")
    if whole is None:
        return None
    away = _base.span_sum_s(run, "serve.logits_wait", "serve.prefill") or 0.0
    return per_iteration_ms(run, whole - away)
