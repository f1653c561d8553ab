"""The reader ISSUE 34 adds, on hand-made run records: the share of the
window's admission prefills whose successor decode was launched with their
token unfetched, 0 where the program has the counter and every admission
waited, and ``None`` where the program has no such counter (the parent
commit) or no prefill ran."""

import pytest

from benchmark import cells
from benchmark.tests.test_span_metrics import _reader, _run

BENCH = cells.load_benchmark()
NAME = "prefill_ahead_pct"
CELLS = ["gpt2m-serve-chat", "axk1-serve-decode", "phi4flash-serve-reason",
         "longcat-serve-turns"]


def test_it_is_declared_for_the_serving_cells_with_the_files_own_words():
    (entry,) = [m for m in BENCH["per_layer"] if m["name"] == NAME]
    mod = _reader(NAME)
    assert entry == {"name": NAME, "unit": mod.UNIT, "better": mod.BETTER,
                     "source": mod.SOURCE, "layer": mod.LAYER,
                     "moves": mod.MOVES, "workloads": CELLS}
    assert (mod.UNIT, mod.BETTER, mod.SOURCE, mod.LAYER, mod.MOVES) == (
        "%", "higher", "program_counter", "serving", "tpot_p90_ms")
    for cell in (w["name"] for w in BENCH["workloads"]):
        resolved = cells.resolve(BENCH, cell)
        listed = [m["name"] for m in resolved["per_layer"]]
        assert (NAME in listed) == (cell in CELLS)
        if cell in CELLS:       # each reports the metric it moves
            assert mod.MOVES in [m["name"] for m in resolved["end_to_end"]]


@pytest.mark.parametrize("ahead,prefills,want", [
    (470, 490, 100.0 * 470 / 490),
    (0, 160, 0.0),          # a sampled request alive all window
    (160, 160, 100.0),
])
def test_it_is_the_ahead_counter_over_the_prefills(ahead, prefills, want):
    run = _run(before={"serving.prefill_ahead": {"value": 3},
                       "serving.prefills": {"value": 5}},
               after={"serving.prefill_ahead": {"value": 3 + ahead},
                      "serving.prefills": {"value": 5 + prefills}})
    assert _reader(NAME).read(run) == pytest.approx(want)


@pytest.mark.parametrize("before,after", [
    # The parent: prefills counted, no such counter at all.
    ({"serving.prefills": {"value": 5}},
     {"serving.prefills": {"value": 55}}),
    # The counter is registered but no prefill ran in the window.
    ({"serving.prefill_ahead": {"value": 40},
      "serving.prefills": {"value": 50}},
     {"serving.prefill_ahead": {"value": 40},
      "serving.prefills": {"value": 50}}),
    # A training cell: neither.
    ({}, {}),
])
def test_nothing_to_read_reads_nothing(before, after):
    assert _reader(NAME).read(_run(before=before, after=after)) is None
