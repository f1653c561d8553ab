"""1 - union of device op intervals / traced window, serving cells (one
reader with ``device_idle_pct.train``, another end-to-end metric to move)."""
from benchmark.cells import load_module

_base = load_module("metrics", "device_idle_pct.train")
LAYER, UNIT, BETTER, SOURCE = (_base.LAYER, _base.UNIT, _base.BETTER,
                               _base.SOURCE)
MOVES = "tpot_p50_ms"
read = _base.read
