"""Share of the serve loop's time spent in ``admission`` and ``start``
passes: their seconds over the seconds of the passes of every kind
(``serving.pass_seconds.<kind>``).  An ``admission`` pass enqueued a prefill
behind the iteration in flight; a ``start`` found nothing in flight, which
in the cells means behind an admission (every request alive is greedy, so
the pipeline only drains when nobody is left), though the engine also names
so a loop that resumes after a depth-0 stretch and admits nothing.  It is
the share of the loop a chunked prefill acts on."""
from benchmark.cells import load_module

_base = load_module("metrics", "steady_pass_ms")
LAYER = "serving"
UNIT = "%"
BETTER = "lower"
SOURCE = "program_counter"
MOVES = "tpot_p90_ms"


def read(run):
    whole, _ = _base.family(run, "serving.pass_seconds.")
    if not whole:
        return None
    part, _ = _base.family(run, "serving.pass_seconds.",
                           ("admission", "start"))
    return 100.0 * part / whole
