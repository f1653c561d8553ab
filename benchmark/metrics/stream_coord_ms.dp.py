"""Host time a stream step spends coordinating its buckets: the
``stream.submit`` regions (``grouped_allreduce_async`` under the drain lock)
plus the ``stream.drain`` regions (the drain tick after each: negotiation
replay, ``execute/*`` and ``megakernel/*`` launches nest inside), histogram
``sum`` over the window's steps."""
from benchmark.cells import load_module

_base = load_module("metrics", "step_host_ms")
LAYER, UNIT, BETTER, SOURCE = (_base.LAYER, _base.UNIT, _base.BETTER,
                               _base.SOURCE)
MOVES = "dp_train_rate"


def read(run):
    return _base.per_step_ms(run, "stream.submit", "stream.drain")
