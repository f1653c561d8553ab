"""Positions of the paged key/value store a decode iteration read, per slot
and per attention layer (idle slots in the mean; each of the four attention
layers reads its own paged layer, at the same lengths): the window's
``serving.decode_view_tokens`` over its ``serving.decode_iterations``.  Where
a kernel walks the page table (``ops/gqa_paged_attention.py`` under
``models/mamba2_hybrid.py``) that is what the kernel copied: the live slots'
entries in use, whole pages.  On the chunk list before it the counter held
the rung of the shared view (384 or 768 positions a slot a layer at this
cell's load, whatever was alive).  A program that keeps no such counter has
nothing to read."""
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
