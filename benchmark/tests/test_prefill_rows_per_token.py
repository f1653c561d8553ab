"""The reader ISSUE 50 adds, on hand-made run records: the rows the window's
admission prefills' programs computed over the real prompt tokens they ran,
and ``None`` where the program has no such counter (the parent commit) or no
prefill ran."""

import pytest

from benchmark import cells
from benchmark.tests.test_span_metrics import _reader, _run

BENCH = cells.load_benchmark()
NAME = "prefill_rows_per_token"
CELLS = ["dsv32-serve-longdoc"]


def test_it_is_declared_for_the_one_cell_with_the_files_own_words():
    (entry,) = [m for m in BENCH["per_layer"] if m["name"] == NAME]
    mod = _reader(NAME)
    assert entry == {"name": NAME, "unit": mod.UNIT, "better": mod.BETTER,
                     "source": mod.SOURCE, "layer": mod.LAYER,
                     "moves": mod.MOVES, "workloads": CELLS}
    assert (mod.UNIT, mod.BETTER, mod.SOURCE, mod.LAYER, mod.MOVES) == (
        "ratio", "lower", "program_counter", "serving", "tpot_p90_ms")
    assert BENCH["per_layer"][-1] == entry      # appended, nothing moved
    for cell in (w["name"] for w in BENCH["workloads"]):
        resolved = cells.resolve(BENCH, cell)
        listed = [m["name"] for m in resolved["per_layer"]]
        assert (NAME in listed) == (cell in CELLS)
        if cell in CELLS:       # each reports the metric it moves
            assert mod.MOVES in [m["name"] for m in resolved["end_to_end"]]


@pytest.mark.parametrize("rows,tokens,want", [
    # The mix by its buckets: 20 prompts of 9.3k tokens in 11.9k rows.
    (238000, 186000, 238000 / 186000),
    # Walked: whole stretches of 2048 up to each prompt's last token.
    (196608, 186000, 196608 / 186000),
    (16384, 16384, 1.0),
])
def test_it_is_the_rows_over_the_tokens(rows, tokens, want):
    run = _run(before={"serving.prefill_rows": {"value": 4096},
                       "serving.prefill_tokens": {"value": 4000}},
               after={"serving.prefill_rows": {"value": 4096 + rows},
                      "serving.prefill_tokens": {"value": 4000 + tokens}})
    assert _reader(NAME).read(run) == pytest.approx(want)


@pytest.mark.parametrize("before,after", [
    # The parent: tokens counted, no such counter at all.
    ({"serving.prefill_tokens": {"value": 5}},
     {"serving.prefill_tokens": {"value": 9000}}),
    # The counter is registered but no prefill ran in the window.
    ({"serving.prefill_rows": {"value": 4096},
      "serving.prefill_tokens": {"value": 4000}},
     {"serving.prefill_rows": {"value": 4096},
      "serving.prefill_tokens": {"value": 4000}}),
    # A training cell: neither.
    ({}, {}),
])
def test_nothing_to_read_reads_nothing(before, after):
    assert _reader(NAME).read(_run(before=before, after=after)) is None
