"""Cached positions of the WINDOW layer group that a decode iteration
attends, summed over its slots (``serving.window_tokens`` over
``serving.decode_iterations``): a live slot's ``min(length, window)``, its
new token's own included, by the host's lengths of every launch.  Where the
window layers live in pages (``serving/kv_cache.py`` layer groups) this is
what a window layer reads; a ring a slot would read ``slots x window``
whatever the sequences hold.  A program without the counter reads
nothing."""
LAYER = "serving"
UNIT = "tokens"
BETTER = "lower"
SOURCE = "program_counter"
MOVES = "tpot_p90_ms"


def read(run):
    iters = run.counter_delta("serving.decode_iterations")
    window = run.counter_delta("serving.window_tokens")
    if not iters or not window:
        return None
    return window / iters
