"""The rate is all the work over all the time of the window, so a stall
inside it costs what it took; the median sub-window (``step_ms_p50``) is
the steady step and does not move; nothing is divided by ``--seconds``."""

import pytest

from benchmark import rates


class FakeLoop:
    """A loop whose steps take ``step_s`` each, with optional stalls."""

    def __init__(self, step_s, stall_at=None, stall_s=0.0):
        self.now, self.step_s = 100.0, step_s
        self.stall_at, self.stall_s = stall_at, stall_s
        self.fetches = 0

    def clock(self):
        return self.now

    def do_steps(self, n):
        self.now += n * self.step_s

    def fetch(self):
        self.fetches += 1
        if self.fetches == self.stall_at:
            self.now += self.stall_s
        return 1.0


def _rate(seconds, **kw):
    loop = FakeLoop(0.05, **kw)
    stamps = rates.run_window(loop.do_steps, loop.fetch, 10, seconds,
                              loop.clock)
    return stamps, rates.window_rate(stamps, 128 * 10, 1)


def test_a_stall_in_the_window_costs_the_rate_what_it_took():
    clean_stamps, clean = _rate(40.0)
    stamps, stalled = _rate(40.0, stall_at=7, stall_s=1.0)
    n = len(stamps) - 1
    assert stalled == pytest.approx(128 * 10 * n / (n * 0.5 + 1.0), rel=1e-9)
    assert stalled < 0.98 * clean
    # The steady step beside it does not move, and the line says where.
    assert rates.median_step_s(stamps, 10) == pytest.approx(
        rates.median_step_s(clean_stamps, 10), rel=1e-9)
    dist = rates.distribution(stamps, 128 * 10, 1)
    assert dist["mean_rate"] == stalled
    assert dist["median_rate"] == pytest.approx(clean, rel=1e-9)
    assert dist["min"] < 0.4 * clean
    assert dist["slowest"][0] == [6, pytest.approx(1.5)]


def test_every_sub_window_counts_in_the_rate():
    stamps = [10.0, 11.0, 12.0, 13.0, 15.0]
    assert rates.window_rate(stamps, 10.0, 2) == pytest.approx(40 / 5.0 / 2)
    assert rates.durations(stamps) == [1.0, 1.0, 1.0, 2.0]


@pytest.mark.parametrize("seconds", [10.0, 17.3, 40.0, 51.0])
def test_rate_never_depends_on_seconds(seconds):
    stamps, rate = _rate(seconds)
    assert rate == pytest.approx(128 / 0.05, rel=1e-9)
    # The window ends at the first fetch at or after --seconds.
    assert stamps[-1] - stamps[0] >= seconds
    assert stamps[-2] - stamps[0] < seconds


def test_distribution_reports_quartiles_and_mean():
    stamps = [0.0, 1.0, 2.0, 3.0, 5.0]
    d = rates.distribution(stamps, 10.0, 2)
    assert d["subwindows"] == 4 and d["window_s"] == 5.0
    assert d["mean_rate"] == pytest.approx(40.0 / 5.0 / 2)
    assert d["median_rate"] == pytest.approx(5.0)
    assert d["min"] == pytest.approx(2.5) and d["max"] == pytest.approx(5.0)
