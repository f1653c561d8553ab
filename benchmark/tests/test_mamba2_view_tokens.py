"""``mamba2_view_tokens`` (ISSUE 47) on hand-made run records: declared by
NAME for its one cell with the file's own words, wherever it stands in
``per_layer``; the view counter over the iterations; ``None`` where the
counter did not move or the program keeps none."""

import pytest

from benchmark import cells
from benchmark.tests.test_span_metrics import _reader, _run

BENCH = cells.load_benchmark()
NAME, CELL = "mamba2_view_tokens", "granite4h-serve-sessions"


def test_it_is_declared_by_name_for_its_cell():
    (entry,) = [m for m in BENCH["per_layer"] if m["name"] == NAME]
    mod = _reader(NAME)
    assert entry == {"name": NAME, "unit": mod.UNIT, "better": mod.BETTER,
                     "source": mod.SOURCE, "layer": mod.LAYER,
                     "moves": mod.MOVES, "workloads": [CELL]}
    assert (mod.UNIT, mod.BETTER, mod.SOURCE, mod.LAYER, mod.MOVES) == (
        "tokens", "lower", "program_counter", "grouped-query attention",
        "tpot_p90_ms")
    # A layer BENCHMARK.json already names, letter for letter.
    assert mod.LAYER in {m["layer"] for m in BENCH["per_layer"]
                         if m["name"] != NAME}
    for cell in (w["name"] for w in BENCH["workloads"]):
        resolved = cells.resolve(BENCH, cell)
        assert (NAME in [m["name"] for m in resolved["per_layer"]]) \
            == (cell == CELL)
    assert mod.MOVES in [m["name"] for m in
                         cells.resolve(BENCH, CELL)["end_to_end"]]


@pytest.mark.parametrize("before,after,want", [
    # 400 iterations: 250 that copied 340 positions a slot a layer, 150
    # that copied 300 (the kernel: the live slots' pages in use).
    ({"serving.decode_view_tokens": {"value": 384.0 * 3},
      "serving.decode_iterations": {"value": 3}},
     {"serving.decode_view_tokens": {"value": 384.0 * 3 + 250 * 340
                                     + 150 * 300},
      "serving.decode_iterations": {"value": 403}}, 325.0),
    # The parent's chunk list: the rung, 96 or 192 chunks of 256 positions
    # over 64 slots, whatever is alive.
    ({}, {"serving.decode_view_tokens": {"value": 384.0 * 60 + 768.0 * 30},
          "serving.decode_iterations": {"value": 90}}, 512.0),
    # No decode in the window; a program without the counter; no serving.
    ({"serving.decode_view_tokens": {"value": 640},
      "serving.decode_iterations": {"value": 5}},
     {"serving.decode_view_tokens": {"value": 640},
      "serving.decode_iterations": {"value": 5}}, None),
    ({"serving.decode_iterations": {"value": 5}},
     {"serving.decode_iterations": {"value": 55}}, None),
    ({}, {}, None)])
def test_it_is_the_view_counter_over_the_iterations(before, after, want):
    got = _reader(NAME).read(_run(before=before, after=after))
    assert got == (want if want is None else pytest.approx(want))
