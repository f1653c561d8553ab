"""The most pages of the FULL layer group that live slots mapped at once,
over the pages of its pool: ``window_pages_peak_pct``'s reading for the
group that keeps every position (``serving.kv_group_pages_peak.full`` over
``serving.kv_group_pages_total.full``)."""
from benchmark.cells import load_module

LAYER = "serving"
UNIT = "%"
BETTER = "lower"
SOURCE = "program_counter"
MOVES = "tpot_p90_ms"


def read(run):
    return load_module("metrics", "window_pages_peak_pct").peak_pct(run,
                                                                    "full")
