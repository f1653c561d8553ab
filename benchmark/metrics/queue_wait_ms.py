"""Mean time a request waited for a decode slot in the window: the
``serving.queue_wait_seconds`` histogram (observed when the scheduler grants
the slot), ``sum`` over ``count``.  With the mean prefill it makes up the
time to the first token."""
LAYER = "serving"
UNIT = "ms"
BETTER = "lower"
SOURCE = "program_counter"
MOVES = "tpot_p50_ms"


def read(run):
    n = run.counter_delta("serving.queue_wait_seconds", "count")
    if not n:
        return None
    return 1e3 * run.counter_delta("serving.queue_wait_seconds", "sum") / n
