"""``step_ms_p50`` of the host-scheduled four-chip cell, which reports its rate as
``dp_train_rate`` (one reader, another end-to-end metric to move)."""
from benchmark.cells import load_module

_base = load_module("metrics", "step_ms_p50")
LAYER, UNIT, BETTER, SOURCE = (_base.LAYER, _base.UNIT, _base.BETTER,
                               _base.SOURCE)
MOVES = "dp_train_rate"
read = _base.read
