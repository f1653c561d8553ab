"""Seconds of set-up spent rebuilding recorded executables: the
``init.megakernel_warm_start`` region of ``hvd.init()`` plus the
``serve.warm_start`` region of the engine, their histograms' ``sum`` since
the process started (set-up is before the window, so not a delta).  The
``init.*`` regions run on every ``hvd.init()``, also with nothing to warm."""
LAYER = "compile cache"
UNIT = "s"
BETTER = "lower"
SOURCE = "program_counter"
MOVES = "setup_s"

_INIT = "trace.span_seconds.init.megakernel_warm_start"
_SERVE = "trace.span_seconds.serve.warm_start"


def read(run):
    after = run.counters_after
    if not after.get(_INIT, {}).get("count"):
        return None
    return after[_INIT]["sum"] + after.get(_SERVE, {}).get("sum", 0.0)
