"""Share of the decode iterations' token-expert pairs that went to
zero-compute experts: ``serving.moe_zero_assignments`` over
``serving.moe_routed_pairs`` (all pairs of live tokens).  Such a pair
costs no matmul, so this is what makes a token's compute vary; under
balanced routing it is the zero-compute outputs' share of the router
(256 of 768: a third).  A program without the counters reads nothing."""
LAYER = "expert layer"
UNIT = "%"
BETTER = "higher"
SOURCE = "program_counter"
MOVES = "tpot_p90_ms"


def read(run):
    routed = run.counter_delta("serving.moe_routed_pairs")
    if not routed:
        return None
    return 100.0 * run.counter_delta("serving.moe_zero_assignments") / routed
