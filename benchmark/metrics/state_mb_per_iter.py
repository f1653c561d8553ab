"""Recurrent state a decode iteration has to move, in MB
(``serving.state_bytes_moved`` over ``serving.decode_iterations``: every
live slot's state and convolution tail in every state-space layer, read
and written, by the host's lengths of each launch): how much state the
traffic makes an iteration move.  It moves when the knee or the rate
does."""
LAYER = "serving"
UNIT = "MB"
BETTER = "lower"
SOURCE = "program_counter"
MOVES = "tpot_p90_ms"


def read(run):
    iters = run.counter_delta("serving.decode_iterations")
    moved = run.counter_delta("serving.state_bytes_moved")
    if not iters or not moved:
        return None
    return moved / iters / 1e6
