"""Token-expert pairs one decode iteration computes on the experts held
here, a layer: ``serving.moe_assignments`` over decode iterations and
expert layers.  A program without the counter reads nothing."""
LAYER = "expert layer"
UNIT = "pairs"
BETTER = "higher"
SOURCE = "program_counter"
MOVES = "tpot_p90_ms"


def read(run):
    pairs = run.counter_delta("serving.moe_assignments")
    iters = run.counter_delta("serving.decode_iterations")
    if not pairs or not iters:
        return None
    _, layers = run.flops.layer_counts(run.config["model"])
    return pairs / (iters * layers)
