"""Decode slots generating per iteration, as a share of the engine's
slots: tokens the decode iterations produced (all tokens less one per
prefill) over iterations times slots."""
LAYER = "serving"
UNIT = "%"
BETTER = "higher"
SOURCE = "program_counter"
MOVES = "tpot_p50_ms"


def read(run):
    iters = run.counter_delta("serving.decode_iterations")
    if not iters:
        return None
    tokens = (run.counter_delta("serving.tokens_generated")
              - run.counter_delta("serving.prefills"))
    return 100.0 * tokens / (iters * run.slots)
