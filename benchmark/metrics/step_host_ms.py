"""Host time of one step call in the window: the program's ``step/monolithic``
or ``step/parallel`` region (``horovod_tpu.trace.region``: dispatch of the one
step program, throttle and bookkeeping), its registry histogram's ``sum``
over the window's steps.  The registry, not the span buffer: the buffer
wraps inside a window.  A program without the region reads nothing."""
LAYER = "DP step builders"
UNIT = "ms"
BETTER = "lower"
SOURCE = "program_counter"
MOVES = "train_rate"


def span_sum_s(run, *names):
    """Seconds the named regions took inside the window (the deltas of
    their ``trace.span_seconds.<name>`` sums), ``None`` if none moved."""
    moved = [run.counter_delta("trace.span_seconds." + n, "sum")
             for n in names
             if run.counter_delta("trace.span_seconds." + n, "count")]
    return sum(moved) if moved else None


def per_step_ms(run, *names):
    secs = span_sum_s(run, *names)
    steps = getattr(run, "steps_in_window", 0)
    if secs is None or not steps:
        return None
    return 1e3 * secs / steps


def read(run):
    return per_step_ms(run, "step/monolithic", "step/parallel")
