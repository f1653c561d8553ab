"""The readers ISSUE 24 adds, each on a hand-made run record: the value it
takes from the program's span histograms, and ``None`` where the counter did
not move (a program without the region, as the parent commit is)."""

import types

import pytest

from benchmark import cells

BENCH = cells.load_benchmark()


class _Run(types.SimpleNamespace):
    def counter_delta(self, name, field="value"):
        a = self.counters_after.get(name, {}).get(field, 0)
        b = self.counters_before.get(name, {}).get(field, 0)
        return a - b


def _hist(count, total):
    return {"type": "histogram", "count": count, "sum": total}


def _run(before=None, after=None, **kw):
    return _Run(counters_before=before or {}, counters_after=after or {},
                **kw)


def _reader(name):
    return cells.load_module("metrics", name)


NEW = ["step_host_ms", "stream_host_ms.dp", "stream_coord_ms.dp",
       "serve_host_ms", "prefill_stall_ms", "queue_wait_ms", "itl_p99_ms",
       "warm_start_s"]


def test_the_eight_are_declared_with_their_cells():
    declared = {m["name"]: m for m in BENCH["per_layer"]}
    assert [m["name"] for m in BENCH["per_layer"]][-len(NEW):] == NEW
    every = [w["name"] for w in BENCH["workloads"]]
    assert sorted(declared["warm_start_s"]["workloads"]) == sorted(every)
    assert declared["step_host_ms"]["workloads"] == ["resnet50-1chip",
                                                     "gpt2m-train-1k"]
    for name in NEW:
        assert "workloads" in declared[name], name
        for cell in declared[name]["workloads"]:
            listed = [m["name"] for m in
                      cells.resolve(BENCH, cell)["per_layer"]]
            assert name in listed


@pytest.mark.parametrize("name", NEW)
def test_nothing_to_read_reads_nothing(name):
    """The parent commit's program has no such span or counter: the reader
    returns ``None`` and does not raise, whatever else the run holds."""
    run = _run(steps_in_window=100, requests=[object()] * 3,
               before={"serving.decode_iterations": {"value": 5}},
               after={"serving.decode_iterations": {"value": 55},
                      "serving.token_seconds": _hist(50, 4.2)})
    reader = _reader(name)
    if name == "itl_p99_ms":
        old = [{"name": "serving.request", "args": {"rid": 1, "tokens": 9}}]
        assert reader.read(run, spans=old) is None
        assert reader.read(run, spans=[]) is None
    else:
        assert reader.read(run) is None


@pytest.mark.parametrize("span", ["step/monolithic", "step/parallel"])
def test_step_host_ms_is_the_step_regions_sum_over_the_windows_steps(span):
    key = "trace.span_seconds." + span
    run = _run(before={key: _hist(30, 0.03)},
               after={key: _hist(130, 0.18)}, steps_in_window=100)
    assert _reader("step_host_ms").read(run) == pytest.approx(1.5)
    run.steps_in_window = 0
    assert _reader("step_host_ms").read(run) is None


def test_stream_readers_take_their_own_regions():
    k = "trace.span_seconds."
    run = _run(before={k + "step/stream": _hist(10, 0.5),
                       k + "stream.submit": _hist(20, 0.1),
                       k + "stream.drain": _hist(20, 0.2)},
               after={k + "step/stream": _hist(110, 6.0),
                      k + "stream.submit": _hist(220, 0.5),
                      k + "stream.drain": _hist(220, 1.7),
                      k + "step/monolithic": _hist(3, 9.9)},
               steps_in_window=100)
    assert _reader("stream_host_ms.dp").read(run) == pytest.approx(55.0)
    assert _reader("stream_coord_ms.dp").read(run) == pytest.approx(19.0)
    # The monolithic region moved too (a fallback): the one-chip reader
    # reads that, the stream readers do not.
    assert _reader("step_host_ms").read(run) == pytest.approx(99.0)


def test_serving_readers():
    k = "trace.span_seconds."
    run = _run(before={k + "serve.iteration": _hist(5, 0.5),
                       "serving.decode_iterations": {"value": 5}},
               after={k + "serve.iteration": _hist(505, 50.5),
                      k + "serve.logits_wait": _hist(500, 40.0),
                      k + "serve.prefill": _hist(160, 6.0),
                      "serving.decode_iterations": {"value": 505},
                      "serving.queue_wait_seconds": _hist(160, 3.2)})
    # (50.0 - 40.0 - 6.0) s over 500 iterations
    assert _reader("serve_host_ms").read(run) == pytest.approx(8.0)
    assert _reader("prefill_stall_ms").read(run) == pytest.approx(12.0)
    assert _reader("queue_wait_ms").read(run) == pytest.approx(20.0)
    # No prefill in the window: nothing to take away, nothing stalled.
    del run.counters_after[k + "serve.prefill"]
    assert _reader("serve_host_ms").read(run) == pytest.approx(20.0)
    assert _reader("prefill_stall_ms").read(run) is None


def test_itl_p99_reads_the_gaps_of_the_windows_request_spans():
    def span(rid, gaps):
        return {"name": "serving.request",
                "args": {"rid": rid, "itl_ms": gaps}}

    warm_up = span(0, [900.0, 900.0])
    window = [span(1, [80.0] * 50), span(2, [84.0] * 48 + [170.0]),
              span(3, [])]
    run = _run(requests=[object()] * 3)
    got = _reader("itl_p99_ms").read(run, spans=[warm_up] + window)
    from benchmark import loadgen

    gaps = [80.0] * 50 + [84.0] * 48 + [170.0]
    assert got == pytest.approx(loadgen.percentile(gaps, 99))
    assert 84.0 <= got < 170.0          # the warm-up's 900 is not in it
    assert _reader("itl_p99_ms").read(_run(), spans=window) is None


def test_warm_start_s_is_since_process_start_not_a_delta():
    a = "trace.span_seconds.init.megakernel_warm_start"
    b = "trace.span_seconds.serve.warm_start"
    run = _run(before={a: _hist(1, 7.5), b: _hist(1, 3.0)},
               after={a: _hist(1, 7.5), b: _hist(1, 3.0)})
    assert _reader("warm_start_s").read(run) == pytest.approx(10.5)
    # A training cell: the engine's region never ran.
    run = _run(after={a: _hist(1, 0.002)})
    assert _reader("warm_start_s").read(run) == pytest.approx(0.002)


def test_the_readers_read_what_the_program_writes():
    """End to end on the CPU: the program's regions feed the registry under
    the names the readers take, and the request spans carry ``itl_ms``."""
    import jax

    import horovod_tpu as hvd
    from horovod_tpu import trace
    from horovod_tpu.models.transformer import (TransformerConfig,
                                                init_transformer)
    from horovod_tpu.serving import InferenceEngine

    hvd.init()
    try:
        cfg = TransformerConfig(vocab_size=97, d_model=32, n_heads=2,
                                n_layers=1, d_ff=64, max_seq_len=32)
        eng = InferenceEngine(init_transformer(jax.random.PRNGKey(0), cfg),
                              cfg, max_slots=2, page_size=8, capacity=24)
        eng.warm_start()
        eng.generate([1, 2, 3], max_new_tokens=2)
        trace.clear()
        before = hvd.metrics()
        reqs = [eng.submit([i + 1, 2, 3], max_new_tokens=6)
                for i in range(3)]
        eng.run_until_idle()
        run = _run(before=before, after=hvd.metrics(), requests=reqs)
    finally:
        hvd.shutdown()
    for name in ("serve_host_ms", "prefill_stall_ms", "queue_wait_ms",
                 "itl_p99_ms", "warm_start_s"):
        value = _reader(name).read(run)
        assert value is not None and value >= 0.0, name
    iters = run.counter_delta("serving.decode_iterations")
    whole = run.counter_delta("trace.span_seconds.serve.iteration", "sum")
    assert _reader("serve_host_ms").read(run) < 1e3 * whole / iters
