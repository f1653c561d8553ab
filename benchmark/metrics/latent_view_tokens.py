"""Tokens of the latent store a decode iteration attended in one cache
layer, per slot (idle slots in the mean): the window's
``serving.decode_view_tokens`` over its ``serving.decode_iterations``.  On
the view ladder that is the rung each group of slots rode, weighted by the
groups' sizes; where a kernel walks the page table
(``ops/latent_paged_attention.py``) it is what the kernel copied, the live
slots' lengths rounded up to the page.  A program that keeps no such
counter has nothing to read."""
LAYER = "latent attention"
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
