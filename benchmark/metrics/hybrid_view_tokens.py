"""Positions of the paged key/value stores a decode iteration read, per slot
and per attending layer (idle slots in the mean; the full group's one layer
counted once for each of its eight readers, the window group's once for each
of its eight layers): the window's ``serving.decode_view_tokens`` over its
``serving.decode_iterations``.  Where a kernel walks the groups' page tables
(``ops/gqa_paged_attention.py`` under ``models/hybrid_ssm.py``) that is what
the kernel copied: the live slots' entries in use, whole pages, of a ring at
most the ring.  On the chunk ladder before it the counter held the rung of
the shared view alone (768 positions a slot a reader whatever was alive; a
ring's 512 a slot a window layer were in no counter).  A program that keeps
no such counter has nothing to read."""
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
