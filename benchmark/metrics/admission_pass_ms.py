"""A pass that carried an admission behind the iteration in flight: the mean
``serve.iteration`` of an ADMISSION pass (``serving.pass_seconds.admission``):
the prefill's enqueue, the next decode's tables and launch, the wait for the
iteration in flight, and the fetch of the first token, which waits out the
prefill program.  Beside ``steady_pass_ms`` it is what one admission costs
every decoding slot.  A pipeline start is another kind (``start``)."""
from benchmark.cells import load_module

_base = load_module("metrics", "steady_pass_ms")
LAYER = "serving"
UNIT = "ms"
BETTER = "lower"
SOURCE = "program_counter"
MOVES = "tpot_p90_ms"


def read(run):
    return _base.mean_ms(run, "serving.pass_seconds.", "admission")
