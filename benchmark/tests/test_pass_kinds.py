"""The seven readers ISSUE 36 adds, on hand-made run records: each takes the
kind's member of the program's family of histograms (or the spans' ``kind``,
``admitted`` and ``itl_admissions``), and reads ``None`` where the program
has none of them (the parent commit) or the window none of that kind."""

import pytest

from benchmark import cells
from benchmark.tests.test_span_metrics import _hist, _reader, _run

BENCH = cells.load_benchmark()
SERVING = ["gpt2m-serve-chat", "axk1-serve-decode", "phi4flash-serve-reason",
           "longcat-serve-turns"]
NEW = {  # name -> (unit, better, source, cells)
    "steady_pass_ms": ("ms", "lower", "program_counter", SERVING),
    "admission_pass_ms": ("ms", "lower", "program_counter", SERVING),
    "admission_time_pct": ("%", "lower", "program_counter", SERVING),
    "tables_after_admission_ms": ("ms", "lower", "program_span", SERVING),
    "tables_h2d_kb_per_pass": ("KB", "lower", "program_counter", SERVING),
    "tpot_admission_p90_ms": ("ms", "lower", "program_span", SERVING),
    "steady_decode_hbm_roofline": ("%", "higher", "program_counter",
                                   SERVING[1:]),
}


def _family(prefix, **kinds):
    return {prefix + k: _hist(*v) for k, v in kinds.items()}


PASSES = _family("serving.pass_seconds.", start=(10, 0.30), steady=(100, 2.2),
                 admission=(30, 1.65), retire=(10, 0.1), sync=(0, 0.0))


@pytest.mark.parametrize("name", sorted(NEW))
def test_it_is_declared_with_the_files_own_words(name):
    unit, better, source, where = NEW[name]
    (entry,) = [m for m in BENCH["per_layer"] if m["name"] == name]
    mod = _reader(name)
    assert entry == {"name": name, "unit": mod.UNIT, "better": mod.BETTER,
                     "source": mod.SOURCE, "layer": mod.LAYER,
                     "moves": mod.MOVES, "workloads": where}
    assert (mod.UNIT, mod.BETTER, mod.SOURCE, mod.LAYER, mod.MOVES) == (
        unit, better, source, "serving", "tpot_p90_ms")
    for cell in (w["name"] for w in BENCH["workloads"]):
        resolved = cells.resolve(BENCH, cell)
        assert (name in [m["name"] for m in resolved["per_layer"]]) \
            == (cell in where)
        if cell in where:
            assert mod.MOVES in [m["name"] for m in resolved["end_to_end"]]


def test_the_pass_readers_take_their_kinds_members():
    run = _run(after=PASSES)
    assert _reader("steady_pass_ms").read(run) == pytest.approx(22.0)
    assert _reader("admission_pass_ms").read(run) == pytest.approx(55.0)
    assert _reader("admission_time_pct").read(run) == pytest.approx(
        100.0 * (1.65 + 0.30) / 4.25)
    # Deltas, not totals: what stood before the window is taken off.
    run = _run(before=PASSES, after={
        k: _hist(v["count"] * 3, v["sum"] * 3) for k, v in PASSES.items()})
    assert _reader("steady_pass_ms").read(run) == pytest.approx(22.0)


def test_the_bytes_a_pass_copies_are_over_the_tables_regions():
    run = _run(after={
        "trace.span_seconds.serve.tables": _hist(140, 0.16),
        "serving.tables_h2d_bytes": {"value": 140 * 66_000}})
    assert _reader("tables_h2d_kb_per_pass").read(run) == pytest.approx(66.0)


def _span(name, dur_ms=0.0, **args):
    return {"name": name, "ph": "X", "ts": 0.0, "dur": dur_ms * 1e3,
            "args": args}


def test_tables_after_an_admission_joins_two_spans_by_iter():
    """The ``serve.tables`` of the passes whose ``serve.iteration`` names
    somebody under ``admitted``, from the window's first admission on: not
    a warm-up's, not a steady pass's, not a start's that admitted nobody."""
    events = [
        _span("serve.iteration", iter=3, kind="start", admitted=(1,)),
        _span("serve.tables", 40.0, iter=3),            # warm-up
        _span("serving.request", rid=1, admit_iter=3),
        _span("serve.iteration", iter=7, kind="start", admitted=(2,)),
        _span("serve.tables", 2.0, iter=7),
        _span("serve.iteration", iter=8, kind="steady", admitted=()),
        _span("serve.tables", 1.0, iter=8),
        _span("serve.iteration", iter=9, kind="admission", admitted=(3, 4)),
        _span("serve.tables", 1.5, iter=9),
        _span("serve.iteration", iter=10, kind="start", admitted=()),
        _span("serve.tables", 9.0, iter=10),
        _span("serve.iteration", iter=11, kind="sync", admitted=(5,)),
        _span("serving.request", rid=2, admit_iter=7),
        _span("serving.request", rid=3, admit_iter=9)]
    reader = _reader("tables_after_admission_ms")
    run = _run(requests=[object()] * 2)
    assert reader.read(run, events=events) == pytest.approx(1.75)
    assert reader.read(_run(requests=[]), events=events) is None
    # The parent's spans: no ``admit_iter``, no ``admitted``.
    old = [_span("serve.iteration", iter=7),
           _span("serve.tables", 2.0, iter=7),
           _span("serving.request", rid=2)]
    assert reader.read(_run(requests=[object()]), events=old) is None


def test_tpot_admission_is_the_gaps_that_held_a_prefill_over_all_gaps():
    spans = [{"name": "serving.request", "args": {
        "itl_ms": [20.0, 50.0, 20.0, 60.0], "itl_admissions": [0, 1, 0, 2]}},
        {"name": "serving.request", "args": {
            "itl_ms": [20.0, 20.0], "itl_admissions": [0, 0]}},
        {"name": "serving.request", "args": {     # one token: no gap
            "itl_ms": [], "itl_admissions": []}}]
    run = _run(requests=[object()] * 3)
    got = _reader("tpot_admission_p90_ms").read(run, spans=spans)
    from benchmark import loadgen

    assert got == pytest.approx(loadgen.percentile([110.0 / 4, 0.0], 90))


@pytest.mark.parametrize("older", ["decode_hbm_roofline",
                                   "hybrid_decode_hbm_roofline"])
def test_the_steady_roofline_is_the_older_share_rescaled(older, monkeypatch):
    """Whatever the cell's older roofline reads, times ``decode_iter_ms``
    over ``steady_pass_ms``: the same bytes over the steady pass."""
    run = _run(after=dict(PASSES, **{
        "serving.token_seconds": _hist(150, 4.5)}))
    real = cells.load_module
    share = {"decode_hbm_roofline": None, "hybrid_decode_hbm_roofline": None,
             older: 50.0}

    def load(kind, name):
        mod = real(kind, name)
        if name in share:
            mod.read = lambda run, _v=share[name]: _v
        return mod

    mod = _reader("steady_decode_hbm_roofline")
    monkeypatch.setattr(mod, "load_module", load)
    assert mod.read(run) == pytest.approx(50.0 * 30.0 / 22.0)
    share[older] = None
    assert mod.read(run) is None


@pytest.mark.parametrize("name", sorted(NEW))
def test_nothing_to_read_reads_nothing(name):
    """The parent commit's program has neither family, counter nor
    argument: ``None``, and no exception, whatever else the run holds."""
    run = _run(requests=[object()] * 3, peaks={"hbm_bytes_per_s": 819e9},
               before={"serving.decode_iterations": {"value": 5}},
               after={"serving.decode_iterations": {"value": 55},
                      "serving.token_seconds": _hist(50, 4.2),
                      "serving.prefills": {"value": 9}})
    reader = _reader(name)
    old = [{"name": "serving.request",
            "args": {"rid": 1, "tokens": 9, "itl_ms": [3.0, 4.0]}}]
    if name == "tpot_admission_p90_ms":
        assert reader.read(run, spans=old) is None
        assert reader.read(run, spans=[]) is None
    elif name == "tables_after_admission_ms":
        assert reader.read(run, events=old) is None
        assert reader.read(run, events=[]) is None
    else:
        assert reader.read(run) is None
