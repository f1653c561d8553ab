"""The reader ISSUE 30 adds, on hand-made run records: the share of the
window's decode iterations launched ahead, 0 where the loop has the counter
and never ran ahead, and ``None`` where the program has no such counter (the
parent commit) or no decode ran."""

import pytest

from benchmark import cells
from benchmark.tests.test_span_metrics import _reader, _run

BENCH = cells.load_benchmark()
NAME = "decode_ahead_pct"


def test_it_is_declared_for_the_serving_cell_with_the_files_own_words():
    (entry,) = [m for m in BENCH["per_layer"] if m["name"] == NAME]
    mod = _reader(NAME)
    assert entry == {"name": NAME, "unit": mod.UNIT, "better": mod.BETTER,
                     "source": mod.SOURCE, "layer": mod.LAYER,
                     "moves": mod.MOVES, "workloads": ["gpt2m-serve-chat"]}
    assert (mod.UNIT, mod.BETTER, mod.SOURCE, mod.LAYER, mod.MOVES) == (
        "%", "higher", "program_counter", "serving", "tpot_p50_ms")
    for cell in (w["name"] for w in BENCH["workloads"]):
        listed = [m["name"] for m in cells.resolve(BENCH, cell)["per_layer"]]
        assert (NAME in listed) == (cell == "gpt2m-serve-chat")


@pytest.mark.parametrize("ahead,iters,want", [
    (430, 500, 86.0),
    (0, 500, 0.0),          # a sampled request alive all window
])
def test_it_is_the_ahead_counter_over_the_iterations(ahead, iters, want):
    run = _run(before={"serving.decode_ahead": {"value": 7},
                       "serving.decode_iterations": {"value": 5}},
               after={"serving.decode_ahead": {"value": 7 + ahead},
                      "serving.decode_iterations": {"value": 5 + iters}})
    assert _reader(NAME).read(run) == pytest.approx(want)


@pytest.mark.parametrize("before,after", [
    # The parent: iterations counted, no such counter at all.
    ({"serving.decode_iterations": {"value": 5}},
     {"serving.decode_iterations": {"value": 55}}),
    # The counter is registered but no decode ran in the window.
    ({"serving.decode_ahead": {"value": 40},
      "serving.decode_iterations": {"value": 50}},
     {"serving.decode_ahead": {"value": 40},
      "serving.decode_iterations": {"value": 50}}),
    # A training cell: neither.
    ({}, {}),
])
def test_nothing_to_read_reads_nothing(before, after):
    assert _reader(NAME).read(_run(before=before, after=after)) is None
