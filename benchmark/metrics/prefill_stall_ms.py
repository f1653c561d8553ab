"""Prefill time per decode iteration: the ``serve.prefill`` regions' ``sum``
(each admitted request's prefill and first sample, which every decoding slot
waits out) over the window's decode iterations: what ``tpot_p50_ms`` lies
above ``decode_iter_ms`` by."""
from benchmark.cells import load_module

_base = load_module("metrics", "step_host_ms")
_serve = load_module("metrics", "serve_host_ms")
LAYER, UNIT, BETTER, SOURCE, MOVES = (_serve.LAYER, _serve.UNIT,
                                      _serve.BETTER, _serve.SOURCE,
                                      _serve.MOVES)


def read(run):
    return _serve.per_iteration_ms(run,
                                   _base.span_sum_s(run, "serve.prefill"))
