"""99th percentile of the gaps between two tokens of one request, over every
gap of the window's requests: ``itl_ms`` of the program's ``serving.request``
spans (per-token stamps on ``scheduler.Request``).  The tail ``tpot``, a
per-request mean, cannot show.  The span buffer holds a serving window
several times over; a program whose spans carry no ``itl_ms`` reads
nothing."""
from benchmark import loadgen

LAYER = "serving"
UNIT = "ms"
BETTER = "lower"
SOURCE = "program_span"
MOVES = "tpot_p50_ms"


def request_spans():
    from horovod_tpu import trace

    return [e for e in trace.export_events()
            if e.get("name") == "serving.request"]


def read(run, spans=None):
    reqs = getattr(run, "requests", None)
    if not reqs:
        return None
    spans = request_spans() if spans is None else spans
    gaps = [g for e in spans[-len(reqs):]
            for g in (e.get("args") or {}).get("itl_ms") or ()]
    return loadgen.percentile(gaps, 99) if gaps else None
