"""How sparse the traffic made the attention: positions the decode
iterations' queries attended after the indexer's selection over positions
it scored (``serving.dsa_selected_tokens`` over
``serving.dsa_scored_tokens``, both from the host's lengths of every
launch).  100 while every context is under ``index_topk``; a half to an
eighth at contexts of 4k to 17k tokens."""
LAYER = "sparse attention"
UNIT = "%"
BETTER = "lower"
SOURCE = "program_counter"
MOVES = "tpot_p90_ms"


def read(run):
    scored = run.counter_delta("serving.dsa_scored_tokens")
    selected = run.counter_delta("serving.dsa_selected_tokens")
    if not scored or not selected:
        return None
    return 100.0 * selected / scored
