"""Per-layer readers on a hand-made run record: what each takes its number
from, and that a reader with nothing to read returns nothing."""

import types

import pytest

from benchmark import cells, device

BENCH = cells.load_benchmark()


def _run(cell, **kw):
    r = cells.resolve(BENCH, cell)
    run = types.SimpleNamespace(
        config=r["config"], traffic=r["traffic"], flops=r["flops"],
        chips=r["cell"]["chips"], peaks=device.peaks("TPU v5 lite"),
        trace=None, notes={})
    run.__dict__.update(kw)
    return run


@pytest.mark.parametrize("metric,cell", [("mfu_pct", "resnet50-1chip"),
                                         ("mfu_pct.dp", "resnet50-dp4")])
def test_mfu_comes_from_the_traced_window_and_is_not_cut(metric, cell):
    reader = cells.load_module("metrics", metric)
    assert reader.SOURCE == "device_trace"
    run = _run(cell)
    assert reader.read(run) is None                  # an untraced run
    chips = run.chips
    per_item = run.flops.train_flops_per_item(run.config["model"],
                                              run.traffic)
    run.items_per_step, run.traced_steps = 128 * chips, 40
    run.trace = {"window_s": 2.0}
    want = 100 * per_item * 128 * 40 / (2.0 * 197e12)
    assert reader.read(run) == pytest.approx(want, rel=1e-12)
    assert 20 < want < 40
    # No cut at 100: a window that leaves out work reads above it.
    run.trace = {"window_s": 0.5}
    assert reader.read(run) == pytest.approx(4 * want) and 4 * want > 100


def test_step_ms_p50_is_the_median_sub_window():
    reader = cells.load_module("metrics", "step_ms_p50.dp")
    run = _run("resnet50-dp4", stamps=[0.0, 0.5, 1.0, 2.5, 3.0], log_every=10)
    assert reader.read(run) == pytest.approx(50.0)
    assert reader.read(_run("resnet50-dp4")) is None
