"""The reader ISSUE 25 adds, on hand-made run records: the mean rung of the
window's decode iterations, and ``None`` where either counter did not move
(a program without the ladder, as the parent commit is)."""

import pytest

from benchmark import cells
from benchmark.tests.test_span_metrics import _reader, _run

BENCH = cells.load_benchmark()
NAME = "decode_view_tokens"


def test_it_is_declared_for_the_serving_cell_with_the_files_own_words():
    (entry,) = [m for m in BENCH["per_layer"] if m["name"] == NAME]
    mod = _reader(NAME)
    assert entry == {"name": NAME, "unit": mod.UNIT, "better": mod.BETTER,
                     "source": mod.SOURCE, "layer": mod.LAYER,
                     "moves": mod.MOVES, "workloads": ["gpt2m-serve-chat"]}
    assert (mod.UNIT, mod.BETTER, mod.SOURCE, mod.LAYER, mod.MOVES) == (
        "tokens", "lower", "program_counter", "serving", "tpot_p50_ms")
    listed = [m["name"] for m in
              cells.resolve(BENCH, "gpt2m-serve-chat")["per_layer"]]
    assert NAME in listed
    for cell in ("resnet50-1chip", "gpt2m-train-1k", "resnet50-dp4"):
        assert NAME not in [m["name"] for m in
                            cells.resolve(BENCH, cell)["per_layer"]]


def test_it_is_the_view_counter_over_the_iterations():
    run = _run(before={"serving.decode_view_tokens": {"value": 1280},
                       "serving.decode_iterations": {"value": 5}},
               after={"serving.decode_view_tokens": {"value": 1280 + 300 * 128
                                                     + 150 * 256 + 50 * 512},
                      "serving.decode_iterations": {"value": 505}})
    assert _reader(NAME).read(run) == pytest.approx(204.8)


@pytest.mark.parametrize("before,after", [
    # The parent: iterations counted, no view counter at all.
    ({"serving.decode_iterations": {"value": 5}},
     {"serving.decode_iterations": {"value": 55}}),
    # The counter is registered but no decode ran in the window.
    ({"serving.decode_view_tokens": {"value": 640},
      "serving.decode_iterations": {"value": 5}},
     {"serving.decode_view_tokens": {"value": 640},
      "serving.decode_iterations": {"value": 5}}),
    # A training cell: neither.
    ({}, {}),
])
def test_nothing_to_read_reads_nothing(before, after):
    assert _reader(NAME).read(_run(before=before, after=after)) is None
