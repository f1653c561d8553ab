"""Prefill time per thousand real prompt tokens: the ``serve.prefill``
regions' seconds (each admission's prefill and first sample, which every
decoding slot waits out) over ``serving.prefill_tokens``.  Where the scan
kernel, the window mask and the last-token second half show."""
from benchmark.cells import load_module

_base = load_module("metrics", "step_host_ms")
LAYER = "serving"
UNIT = "ms"
BETTER = "lower"
SOURCE = "program_counter"
MOVES = "tpot_p90_ms"


def read(run):
    secs = _base.span_sum_s(run, "serve.prefill")
    tokens = run.counter_delta("serving.prefill_tokens")
    if secs is None or not tokens:
        return None
    return 1e6 * secs / tokens
