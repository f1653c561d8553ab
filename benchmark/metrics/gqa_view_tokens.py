"""Positions of the paged key/value store a decode iteration attended, per
slot and per layer (idle slots in the mean, the two layer groups weighted by
their layers): the window's ``serving.decode_view_tokens`` over its
``serving.decode_iterations``.  On the view ladder that is the rung each
group rode; where a kernel walks the groups' page tables
(``ops/gqa_paged_attention.py``) it is what the kernel copied, the live
slots' entries in use, whole pages, of a ring at most the ring.  A program
that keeps no such counter has nothing to read."""
LAYER = "grouped-query attention"
UNIT = "tokens"
BETTER = "lower"
SOURCE = "program_counter"
MOVES = "tpot_p90_ms"


def read(run):
    view = run.counter_delta("serving.decode_view_tokens")
    iters = run.counter_delta("serving.decode_iterations")
    if not view or not iters:
        return None
    return view / iters
