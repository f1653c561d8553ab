"""How unevenly the router loads the experts held here: the fullest held
expert's pairs over the mean held expert's, a ratio of the two counters'
deltas (``serving.moe_expert_load_max`` sums each layer's fullest expert,
``serving.moe_assignments`` all of them).  1 is even."""
LAYER = "expert layer"
UNIT = "ratio"
BETTER = "lower"
SOURCE = "program_counter"
MOVES = "tpot_p90_ms"


def read(run):
    pairs = run.counter_delta("serving.moe_assignments")
    fullest = run.counter_delta("serving.moe_expert_load_max")
    if not pairs or not fullest:
        return None
    return fullest * run.config["model"]["n_routed_experts"] / pairs
