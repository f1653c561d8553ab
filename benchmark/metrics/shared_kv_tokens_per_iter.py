"""Cached positions of the one shared key/value store that a decode
iteration attends, summed over its slots (``serving.shared_kv_tokens`` over
``serving.decode_iterations``): how much of the store the traffic makes
every reader layer read.  It moves when the knee or the rate does."""
LAYER = "serving"
UNIT = "tokens"
BETTER = "lower"
SOURCE = "program_counter"
MOVES = "tpot_p90_ms"


def read(run):
    iters = run.counter_delta("serving.decode_iterations")
    shared = run.counter_delta("serving.shared_kv_tokens")
    if not iters or not shared:
        return None
    return shared / iters
