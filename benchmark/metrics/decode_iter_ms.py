"""Mean time of one continuous-batching decode iteration in the window
(the ``serving.token_seconds`` histogram's sum over its count)."""
LAYER = "serving"
UNIT = "ms"
BETTER = "lower"
SOURCE = "program_counter"
MOVES = "tpot_p50_ms"


def read(run):
    n = run.counter_delta("serving.token_seconds", "count")
    if not n:
        return None
    return 1e3 * run.counter_delta("serving.token_seconds", "sum") / n
