"""Host time of one stream-schedule step call in the window: the program's
``step/stream`` region, histogram ``sum`` over the window's steps.  Beside
``step_ms_p50.dp`` it says whether the step is host-paced: a step call that
takes the whole step is."""
from benchmark.cells import load_module

_base = load_module("metrics", "step_host_ms")
LAYER, UNIT, BETTER, SOURCE = (_base.LAYER, _base.UNIT, _base.BETTER,
                               _base.SOURCE)
MOVES = "dp_train_rate"


def read(run):
    return _base.per_step_ms(run, "step/stream")
