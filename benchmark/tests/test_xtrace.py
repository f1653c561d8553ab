"""The trace reduction, on hand-made events and on the small recorded
traces kept under ``benchmark/data``: slices of real chip traces, re-encoded
by ``benchmark/tools/slice_trace.py``."""

import os

import pytest

from benchmark import xtrace
from benchmark.cells import HERE
from benchmark.tools import slice_trace


def test_innermost_event_owns_each_instant():
    ev = [("%while.1 = ...", 0, 100), ("%fusion.2 = f32[] fusion()", 10, 20),
          ("%all-reduce.3 = ...", 40, 10), ("%copy.4", 120, 10)]
    r = xtrace.reduce_trace({"/device:TPU:0": ev})
    assert r["busy_s"] == pytest.approx(110e-9)
    assert r["window_s"] == pytest.approx(130e-9)
    assert r["exposed_coll_s"] == pytest.approx(10e-9)
    assert dict(map(tuple, r["device_ops"])) == pytest.approx(
        {"while": 70e-9, "fusion": 20e-9, "all-reduce": 10e-9, "copy": 10e-9})
    assert r["idle_gaps"] == [["host:unattributed_while-_copy",
                               pytest.approx(20e-9)]]


def test_devices_are_averaged_and_empty_ones_left_out():
    a = [("%fusion.1", 0, 50), ("%fusion.2", 100, 50)]
    b = [("%fusion.1", 0, 100)]
    r = xtrace.reduce_trace({"/device:TPU:0": a, "/device:TPU:1": b,
                             "/device:TPU:2": []})
    assert r["devices"] == 2
    assert r["busy_s"] == pytest.approx(100e-9)
    assert r["window_s"] == pytest.approx(150e-9)


def test_xspace_round_trip(tmp_path):
    planes = {"/device:TPU:0": [("fusion.7", 1000, 500), ("copy.1", 2000, 30)],
              "/host:CPU": [("python", 0, 10)]}
    path = tmp_path / "t.xplane.pb"
    path.write_bytes(slice_trace.encode_xspace(planes))
    got = xtrace.load_device_events(str(path))
    assert set(got) == {"/device:TPU:0"}          # host planes are not devices
    ev = sorted(got["/device:TPU:0"], key=lambda e: e[1])
    assert [(n, d) for n, _s, d in ev] == [("fusion.7", 500), ("copy.1", 30)]
    assert ev[1][1] - ev[0][1] == 1000


import json

DATA = os.path.join(HERE, "data")
RECORDED = json.load(open(os.path.join(DATA, "recorded.json")))


@pytest.mark.parametrize("expect", RECORDED, ids=lambda e: e["file"])
def test_recorded_trace_reduces_to_fixed_numbers(expect):
    """Slices of real chip traces (``data/recorded.json`` says of what): the
    numbers are what the reduction gave when each was recorded; a change to
    the arithmetic has to explain itself here."""
    path = os.path.join(DATA, expect["file"])
    assert os.path.getsize(path) < 1_000_000
    r = xtrace.reduce_trace(xtrace.load_device_events(path))
    assert r["devices"] == expect["devices"]
    assert r["busy_s"] / r["window_s"] == pytest.approx(expect["busy_share"],
                                                        rel=1e-9)
    assert r["exposed_coll_s"] == pytest.approx(expect["exposed_coll_s"],
                                                rel=1e-9, abs=1e-15)
    assert [n for n, _ in r["device_ops"][:3]] == expect["top_ops"]
    assert r["idle_gaps"][0][0] == expect["top_gap"]


def test_trace_lines_lists_what_the_reduction_reads():
    """``tools/trace_lines.py`` on the recorded one-chip trace: the line the
    reduction reads, with the same busy union."""
    from benchmark.tools import trace_lines

    path = os.path.join(DATA, RECORDED[0]["file"])
    rows = trace_lines.lines(path)
    assert [(r["plane"], r["line"]) for r in rows] == [
        ("/device:TPU:0", xtrace.OPS_LINE)]
    r = xtrace.reduce_trace(xtrace.load_device_events(path))
    assert rows[0]["busy_s"] == pytest.approx(r["busy_s"])
    assert r["per_device"] == [[pytest.approx(r["busy_s"]),
                                pytest.approx(r["window_s"])]]
