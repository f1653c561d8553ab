"""The load generator: a pure function of its seed, the same requests at
the same instants under every seed, and every latency counted from the due
instant."""

import json
import os

import pytest

from benchmark import loadgen, serve
from benchmark.cells import HERE

TRAFFIC = json.load(open(os.path.join(HERE, "traffic", "serve-chat.json")))


def _shape(plan):
    return ([round(p.due_s, 9) for p in plan], [len(p.prompt) for p in plan],
            [p.max_tokens for p in plan])


def test_deterministic_in_seed():
    a = loadgen.plan(TRAFFIC, 40.0, 2_200_000_123, 50257)
    b = loadgen.plan(TRAFFIC, 40.0, 2_200_000_123, 50257)
    c = loadgen.plan(TRAFFIC, 40.0, 7, 50257)
    assert _shape(a) == _shape(b)
    assert [p.prompt for p in a] == [p.prompt for p in b]
    # Another seed: other token ids, the same lengths at the same instants.
    assert _shape(a) == _shape(c)
    assert [p.prompt for p in a] != [p.prompt for p in c]
    # The order is the traffic file's, not the generator's.
    d = loadgen.plan(dict(TRAFFIC, order_seed=24), 40.0, 7, 50257)
    assert _shape(d) != _shape(c)
    assert sorted(_shape(d)[1]) == sorted(_shape(c)[1])


def test_every_seed_offers_the_same_work():
    plans = [loadgen.plan(TRAFFIC, 40.0, s, 50257) for s in (1, 2, 3 << 30)]
    n = round(TRAFFIC["rate_per_s"] * 40.0)
    for p in plans:
        assert len(p) == n
        assert sorted(len(x.prompt) for x in p) == \
            sorted(len(x.prompt) for x in plans[0])
        assert sorted(x.max_tokens for x in p) == \
            sorted(x.max_tokens for x in plans[0])
        assert 0.0 <= min(x.due_s for x in p)
        assert max(x.due_s for x in p) < 40.0
    lens = [len(x.prompt) for x in plans[0]]
    assert min(lens) >= 16 and max(lens) <= 256
    answers = [x.max_tokens for x in plans[0]]
    assert min(answers) >= 8 and max(answers) <= 96
    assert 27 <= sum(answers) / len(answers) <= 33


@pytest.mark.parametrize("seconds", [10.0, 40.0, 51.0])
def test_arrivals_keep_the_stated_rate(seconds):
    p = loadgen.plan(TRAFFIC, seconds, 5, 50257)
    assert len(p) == round(TRAFFIC["rate_per_s"] * seconds)
    due = sorted(x.due_s for x in p)
    assert 0.0 <= due[0] and due[-1] < seconds
    gaps = [b - a for a, b in zip(due, due[1:])]
    mean = sum(gaps) / len(gaps)
    assert mean == pytest.approx(1 / TRAFFIC["rate_per_s"], rel=0.05)
    # Exponential gaps: the standard deviation is about the mean.
    sd = (sum((g - mean) ** 2 for g in gaps) / len(gaps)) ** 0.5
    assert 0.8 < sd / mean < 1.1


def test_latency_is_timed_from_the_due_instant():
    """A generator that runs 0.5 s late must charge the wait to the
    request, and report its own lag."""
    clock = {"t": 0.0}
    plan = [loadgen.Planned(1.0, [1, 2, 3], 5)]

    def sleep(s):
        clock["t"] += s + 0.5                      # oversleeps by 0.5 s

    def send(p):
        clock["t"] += 0.2                          # 0.1 s to first token
        return loadgen.Done(responded=clock["t"], ok=True, status=200,
                            tokens=[0] * 5, ttft_ms=100.0, total_ms=200.0)

    (r,) = loadgen.drive(plan, send, clock=lambda: clock["t"], sleep=sleep)
    assert r.due == pytest.approx(1.0)
    assert r.sent - r.due == pytest.approx(0.5)
    lat = serve.latencies([r])
    # due at 1.0, sent at 1.5, first token 0.1 s later: 600 ms, not 100.
    assert lat["ttft_ms"][0] == pytest.approx(600.0)
    assert lat["tpot_ms"][0] == pytest.approx(100.0 / 4)


def test_failed_requests_are_counted_not_dropped():
    def send(p):
        raise ConnectionError("refused")

    (r,) = loadgen.drive([loadgen.Planned(0.0, [1], 2)], send)
    assert not r.ok and "refused" in r.error
