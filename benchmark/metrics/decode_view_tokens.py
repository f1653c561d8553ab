"""Mean length of the KV view a decode iteration attended, in tokens per
slot: the ladder rung each iteration rode (``serving.decode_view_tokens``)
over the window's decode iterations.  The capacity where the program has no
ladder; such a program has no such counter, and nothing is read."""
LAYER = "serving"
UNIT = "tokens"
BETTER = "lower"
SOURCE = "program_counter"
MOVES = "tpot_p50_ms"


def read(run):
    view = run.counter_delta("serving.decode_view_tokens")
    iters = run.counter_delta("serving.decode_iterations")
    if not view or not iters:
        return None
    return view / iters
