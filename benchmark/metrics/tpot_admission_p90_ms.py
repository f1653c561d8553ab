"""The part of a request's time per output token owed to gaps that waited
out a prefill: over the window's ``serving.request`` spans (taken as
``itl_p99_ms`` takes them), a request's ``itl_ms`` summed over the gaps whose
``itl_admissions`` is above 0 (the loop fetched some other request's first
token between the two stamps), over (tokens - 1); the 90th percentile over
requests.  Beside ``tpot_p90_ms`` it is what chunked prefill can claim.  A
program whose spans carry no ``itl_admissions`` reads nothing."""
from benchmark import loadgen
from benchmark.cells import load_module

_spans = load_module("metrics", "itl_p99_ms")
LAYER = "serving"
UNIT = "ms"
BETTER = "lower"
SOURCE = "program_span"
MOVES = "tpot_p90_ms"


def read(run, spans=None):
    reqs = getattr(run, "requests", None)
    if not reqs:
        return None
    spans = _spans.request_spans() if spans is None else spans
    owed = []
    for e in spans[-len(reqs):]:
        args = e.get("args") or {}
        gaps, held = args.get("itl_ms"), args.get("itl_admissions")
        if not gaps or held is None:
            continue
        owed.append(sum(g for g, n in zip(gaps, held) if n > 0) / len(gaps))
    return loadgen.percentile(owed, 90) if owed else None
