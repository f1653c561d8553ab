"""Kilobytes (of 1000 bytes) a pass copies to the device for the iteration it
launches: the page table, the lengths and, where the host holds a token the
device does not, the override (``serving.tables_h2d_bytes``), over the passes
that planned a launch (the ``serve.tables`` regions the window closed:
``trace.span_seconds.serve.tables``).  Today it is the shapes of the table
and the lengths, the same every pass: the number that tables living on the
device, which would send the few rows that changed, are to bring down."""
LAYER = "serving"
UNIT = "KB"
BETTER = "lower"
SOURCE = "program_counter"
MOVES = "tpot_p90_ms"


def read(run):
    passes = run.counter_delta("trace.span_seconds.serve.tables", "count")
    if not passes or "serving.tables_h2d_bytes" not in run.counters_after:
        return None
    return run.counter_delta("serving.tables_h2d_bytes") / 1e3 / passes
