"""``hostspans.attribute_gaps`` on hand-made device and host events: a gap
inside a span, across two spans, outside every span, under nested spans; the
choice of the dispatching thread; and the spans of a real (CPU) profiler
trace read back from its ``.xplane.pb``."""

import glob

import pytest

from benchmark import hostspans
from benchmark.hostspans import OUTSIDE

MS = 1_000_000


def _dev(*busy):
    """Device events from (name, start_ms, dur_ms)."""
    return [(n, int(s * MS), int(d * MS)) for n, s, d in busy]


def _host(*spans):
    return [("hvd:" + n, int(s * MS), int(d * MS)) for n, s, d in spans]


def _one(att):
    (d,) = att["devices"].values()
    return d


def test_a_gap_inside_one_span_goes_to_that_span():
    dev = {"/device:TPU:0": _dev(("fusion.1", 0, 10), ("fusion.2", 14, 10))}
    host = {"t": _host(("stream.drain", 8, 8))}
    d = _one(hostspans.attribute_gaps(dev, host))
    assert d["idle_s"] == pytest.approx(0.004)
    assert d["by_span"] == {"stream.drain": pytest.approx(0.004)}
    assert d["by_gap"] == {"host:unattributed_fusion-_fusion":
                           {"stream.drain": pytest.approx(0.004)}}


def test_a_gap_across_two_spans_is_split_and_the_rest_is_outside():
    dev = {"/device:TPU:0": _dev(("copy-done.2", 0, 10),
                                 ("slice-start.7", 20, 5))}
    host = {"t": _host(("stream.submit", 9, 3),     # covers 10..12
                       ("stream.drain", 13, 5))}    # covers 13..18
    d = _one(hostspans.attribute_gaps(dev, host))
    assert d["idle_s"] == pytest.approx(0.010)
    assert d["by_span"]["stream.submit"] == pytest.approx(0.002)
    assert d["by_span"]["stream.drain"] == pytest.approx(0.005)
    assert d["by_span"][OUTSIDE] == pytest.approx(0.003)   # 12..13, 18..20
    assert set(d["by_gap"]) == {"host:unattributed_copy-done-_slice-start"}
    assert sum(d["by_span"].values()) == pytest.approx(d["idle_s"])


def test_a_gap_outside_every_span_reads_outside_spans():
    dev = {"/device:TPU:0": _dev(("a", 0, 1), ("b", 5, 1))}
    for host in ({}, {"t": _host(("step/stream", 10, 5))}):
        att = hostspans.attribute_gaps(dev, host)
        assert _one(att)["by_span"] == {OUTSIDE: pytest.approx(0.004)}
        assert hostspans.named_share(att) == 0.0


def test_nested_spans_give_the_innermost():
    dev = {"/device:TPU:0": _dev(("a", 0, 10), ("b", 30, 10))}
    host = {"t": _host(("step/stream", 0, 40),
                       ("stream.drain", 12, 10),        # 12..22
                       ("execute/allreduce", 14, 4),    # 14..18
                       ("megakernel/psum", 15, 2))}     # 15..17
    d = _one(hostspans.attribute_gaps(dev, host))
    assert d["idle_s"] == pytest.approx(0.020)
    assert d["by_span"] == {
        "step/stream": pytest.approx(0.010),            # 10..12, 22..30
        "stream.drain": pytest.approx(0.006),           # 12..14, 18..22
        "execute/allreduce": pytest.approx(0.002),      # 14..15, 17..18
        "megakernel/psum": pytest.approx(0.002)}
    assert hostspans.named_share(hostspans.attribute_gaps(dev, host)) == 1.0


def test_each_device_is_attributed_against_the_dispatching_thread():
    dev = {"/device:TPU:0": _dev(("a", 0, 10), ("b", 20, 10)),
           "/device:TPU:1": _dev(("a", 0, 15), ("b", 20, 10)),
           "/device:TPU:2": []}
    host = {"ticker": _host(("negotiate.tick", 11, 1)),
            "trainer": _host(("step/stream", 0, 30),
                             ("stream.drain", 12, 8))}
    att = hostspans.attribute_gaps(dev, host)
    assert att["thread"] == "trainer"
    assert set(att["devices"]) == {"/device:TPU:0", "/device:TPU:1"}
    d0, d1 = att["devices"]["/device:TPU:0"], att["devices"]["/device:TPU:1"]
    assert d0["by_span"] == {"step/stream": pytest.approx(0.002),
                             "stream.drain": pytest.approx(0.008)}
    assert d1["by_span"] == {"stream.drain": pytest.approx(0.005)}
    # A thread can be named: the ticker saw one millisecond of device 0's.
    by_tick = hostspans.attribute_gaps(dev, host, thread="ticker")
    assert by_tick["devices"]["/device:TPU:0"]["by_span"] == {
        "negotiate.tick": pytest.approx(0.001),
        OUTSIDE: pytest.approx(0.009)}


def test_the_thread_with_the_steps_beats_a_thread_that_covers_more():
    """A training thread ahead of the device is in its step call for a
    millisecond; the drain tick's thread ticks through the whole window."""
    dev = {"/device:TPU:0": _dev(("a", 0, 40), ("b", 50, 40))}
    host = {"ticker": _host(*[("negotiate.tick", t, 2)
                              for t in range(0, 90, 5)]),
            "trainer": _host(("step/parallel", 44, 1))}
    att = hostspans.attribute_gaps(dev, host)
    assert att["thread"] == "trainer"
    assert _one(att)["by_span"] == {"step/parallel": pytest.approx(0.001),
                                    OUTSIDE: pytest.approx(0.009)}
    # No thread has a step or an iteration: coverage decides.
    del host["trainer"]
    assert hostspans.attribute_gaps(dev, host)["thread"] == "ticker"


def test_no_device_events_no_table():
    assert hostspans.attribute_gaps({}, {"t": _host(("x", 0, 1))}) == {
        "thread": None, "devices": {}}


def test_host_spans_are_read_back_from_a_real_trace(tmp_path):
    """The ``hvd:`` annotations of a CPU profiler run, per thread; other
    host events (and a program that has no region) leave nothing."""
    import jax
    import jax.numpy as jnp
    from jax.profiler import TraceAnnotation

    f = jax.jit(lambda x: x @ x)
    x = jnp.ones((64, 64))
    f(x).block_until_ready()
    jax.profiler.start_trace(str(tmp_path))
    try:
        with TraceAnnotation("hvd:step/stream", step=3, mono_us=1):
            with TraceAnnotation("hvd:stream.drain", bucket=0):
                f(x).block_until_ready()
        with TraceAnnotation("not-ours"):
            pass
    finally:
        jax.profiler.stop_trace()
    (path,) = glob.glob(str(tmp_path / "**" / "*.xplane.pb"), recursive=True)
    spans = hostspans.load_host_spans(path)
    (thread,) = spans
    assert thread.startswith("/host:")
    names = [n for n, _, _ in spans[thread]]
    assert sorted(names) == ["hvd:step/stream", "hvd:stream.drain"]
    outer = next(e for e in spans[thread] if e[0] == "hvd:step/stream")
    inner = next(e for e in spans[thread] if e[0] == "hvd:stream.drain")
    assert outer[1] <= inner[1] and inner[1] + inner[2] <= outer[1] + outer[2]
    # A CPU trace has no device plane: the tool's table is empty, not a crash.
    from benchmark import xtrace
    from benchmark.tools import gap_spans

    att = hostspans.attribute_gaps(xtrace.load_device_events(path), spans)
    assert att["devices"] == {}
    assert "dispatching thread" in gap_spans.render(att, 5)
