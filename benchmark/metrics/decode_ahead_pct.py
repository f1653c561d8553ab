"""Share of the window's decode iterations that were launched while the
previous one's tokens were still unfetched (``serving.decode_ahead`` over
``serving.decode_iterations``): how much of the decode loop ran one
iteration ahead, the host's work under the device's.  The loop restarts
after every park and holds still while a sampled request is alive.  A
program whose loop fetches before it launches has no such counter, and
nothing is read."""
LAYER = "serving"
UNIT = "%"
BETTER = "higher"
SOURCE = "program_counter"
MOVES = "tpot_p50_ms"


def read(run):
    if "serving.decode_ahead" not in run.counters_after:
        return None
    iters = run.counter_delta("serving.decode_iterations")
    if not iters:
        return None
    return 100.0 * run.counter_delta("serving.decode_ahead") / iters
