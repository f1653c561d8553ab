"""Share of the window's admission prefills whose successor decode was
launched before the host fetched the prefill's token
(``serving.prefill_ahead`` over ``serving.prefills``): how many admissions
joined the run-ahead decode loop, the first token going from the prefill
program to the decode behind it on the device, and did not drain it.  An
admission holds synchronous while a sampled request is alive, and one whose
first token is its last by count has no decode behind it.  A program whose
admission waits for its logits row has no such counter, and nothing is
read."""
LAYER = "serving"
UNIT = "%"
BETTER = "higher"
SOURCE = "program_counter"
MOVES = "tpot_p90_ms"


def read(run):
    if "serving.prefill_ahead" not in run.counters_after:
        return None
    prefills = run.counter_delta("serving.prefills")
    if not prefills:
        return None
    return 100.0 * run.counter_delta("serving.prefill_ahead") / prefills
