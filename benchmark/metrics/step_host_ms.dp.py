"""Host time of one monolithic step call in the window on several chips: the
base reader of ``step_host_ms`` (the ``step/monolithic`` region), moving the
four-chip cell's rate.  Where the stream schedule runs, the region never
opens and this reads nothing (``stream_host_ms.dp`` reads that schedule)."""
from benchmark.cells import load_module

_base = load_module("metrics", "step_host_ms")
LAYER, UNIT, BETTER, SOURCE = (_base.LAYER, _base.UNIT, _base.BETTER,
                               _base.SOURCE)
MOVES = "dp_train_rate"
read = _base.read
