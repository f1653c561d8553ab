"""The reader ISSUE 32 adds, on hand-made run records: the four-chip cell's
host time inside one monolithic step call, and ``None`` where the cell runs
the stream schedule (the parent commit: the ``step/monolithic`` region never
opens there) or no step ran."""

import pytest

from benchmark import cells
from benchmark.tests.test_span_metrics import _hist, _reader, _run

BENCH = cells.load_benchmark()
NAME = "step_host_ms.dp"
KEY = "trace.span_seconds.step/monolithic"


def test_it_is_declared_for_the_four_chip_cell_with_the_files_own_words():
    (entry,) = [m for m in BENCH["per_layer"] if m["name"] == NAME]
    mod = _reader(NAME)
    assert entry == {"name": NAME, "unit": mod.UNIT, "better": mod.BETTER,
                     "source": mod.SOURCE, "layer": mod.LAYER,
                     "moves": mod.MOVES, "workloads": ["resnet50-dp4"]}
    assert (mod.UNIT, mod.BETTER, mod.SOURCE, mod.LAYER, mod.MOVES) == (
        "ms", "lower", "program_counter", "DP step builders",
        "dp_train_rate")
    for cell in (w["name"] for w in BENCH["workloads"]):
        listed = [m["name"] for m in cells.resolve(BENCH, cell)["per_layer"]]
        assert (NAME in listed) == (cell == "resnet50-dp4")


def test_it_is_the_monolithic_regions_sum_over_the_windows_steps():
    run = _run(before={KEY: _hist(30, 0.03)}, after={KEY: _hist(130, 0.23)},
               steps_in_window=100)
    assert _reader(NAME).read(run) == pytest.approx(2.0)
    assert _reader(NAME).read(run) == _reader("step_host_ms").read(run)


@pytest.mark.parametrize("before,after,steps", [
    # The parent: the stream schedule's region moved, this one never opened.
    ({"trace.span_seconds.step/stream": _hist(30, 1.5)},
     {"trace.span_seconds.step/stream": _hist(130, 6.9)}, 100),
    # The region exists (set-up's steps) but no step ran in the window.
    ({KEY: _hist(30, 0.03)}, {KEY: _hist(30, 0.03)}, 100),
    ({KEY: _hist(30, 0.03)}, {KEY: _hist(130, 0.23)}, 0),
    # A serving cell: neither.
    ({}, {}, 0),
])
def test_nothing_to_read_reads_nothing(before, after, steps):
    run = _run(before=before, after=after, steps_in_window=steps)
    assert _reader(NAME).read(run) is None
