"""One general open-loop load generator, driven by a traffic file.

The schedule is a pure function of the traffic parameters, the window
length and the seed.  Inter-arrival gaps, prompt lengths and answer lengths
are the quantiles of the stated distributions, put in an order the traffic
file fixes (``order_seed``): every run of a cell offers the same requests
at the same instants, and ``--seed`` draws the token ids (and, in the
builder, the weights), never the amount of work or where it falls.  A
median wait moves by several per cent with the order of the same arrivals,
which is no property of the system.  Latencies are timed from the instant a
request was DUE, not from when the generator got round to sending it.
"""

from __future__ import annotations

import math
import threading
import time
from dataclasses import dataclass, field
from statistics import NormalDist
from typing import Callable, List, Optional

import numpy as np


@dataclass
class Planned:
    due_s: float              # offset from the window's start
    prompt: List[int]
    max_tokens: int


@dataclass
class Done:
    due: float = 0.0          # absolute, on the generator's clock
    sent: float = 0.0
    responded: float = 0.0
    ok: bool = False
    status: int = 0
    tokens: List[int] = field(default_factory=list)
    ttft_ms: Optional[float] = None     # the server's: submit -> first token
    total_ms: Optional[float] = None    # the server's: submit -> done
    error: str = ""


def _lognormal_quantiles(n: int, median: float, sigma: float, lo: int,
                         hi: int) -> np.ndarray:
    nd = NormalDist()
    z = np.array([nd.inv_cdf((i + 0.5) / n) for i in range(n)])
    return np.clip(np.rint(median * np.exp(sigma * z)), lo, hi).astype(int)


def _gap_quantiles(n: int, rate: float) -> np.ndarray:
    """n inter-arrival gaps of a Poisson process (exponential quantiles),
    scaled to the mean 1/rate."""
    gaps = -np.log1p(-(np.arange(n) + 0.5) / n)
    return gaps * (n / rate) / gaps.sum()


def plan(traffic: dict, seconds: float, seed: int,
         vocab_size: int) -> List[Planned]:
    """The window's requests: round(rate x seconds) of them, the last due
    just inside ``seconds``."""
    rate = float(traffic["rate_per_s"])
    n = max(1, int(round(rate * seconds)))
    order = np.random.default_rng([int(traffic["order_seed"]), 0x10AD])
    gaps = order.permutation(_gap_quantiles(n, rate))
    due = np.cumsum(gaps) - gaps[0] * 0.5
    p, a = traffic["prompt_tokens"], traffic["answer_tokens"]
    prompts = order.permutation(_lognormal_quantiles(
        n, p["median"], p["sigma"], p["min"], p["max"]))
    answers = order.permutation(_lognormal_quantiles(
        n, a["median"], a["sigma"], a["min"], a["max"]))
    ids = np.random.default_rng([int(seed), 0x10AD])
    return [Planned(float(due[i]),
                    ids.integers(0, vocab_size, size=int(prompts[i])).tolist(),
                    int(answers[i])) for i in range(n)]


def warmup_plan(traffic: dict, seed: int, vocab_size: int) -> List[Planned]:
    """One request for each prompt length set-up must have seen (the
    server's prefill buckets the mix can reach), all due at once."""
    rng = np.random.default_rng([int(seed), 0xA11])
    return [Planned(0.0, rng.integers(0, vocab_size, size=int(n)).tolist(),
                    int(traffic.get("warmup_answer_tokens", 4)))
            for n in traffic["warmup_prompt_tokens"]]


def drive(planned: List[Planned], send: Callable[[Planned], Done],
          clock: Callable[[], float] = time.perf_counter,
          sleep: Callable[[float], None] = time.sleep,
          join_timeout: float = 300.0) -> List[Done]:
    """Open loop: one client thread per request in flight, started at the
    request's due instant whatever the earlier ones are doing."""
    results: List[Optional[Done]] = [None] * len(planned)
    threads = []
    t0 = clock()

    def client(i: int, p: Planned, due: float) -> None:
        sent = clock()
        try:
            d = send(p)
        except Exception as e:  # noqa: BLE001 — boundary: a failed request
            d = Done(error=f"{type(e).__name__}: {e}")   # counts as failed
        d.due, d.sent = due, sent
        if not d.responded:
            d.responded = clock()
        results[i] = d

    for i, p in sorted(enumerate(planned), key=lambda ip: ip[1].due_s):
        due = t0 + p.due_s
        wait = due - clock()
        if wait > 0:
            sleep(wait)
        th = threading.Thread(target=client, args=(i, p, due), daemon=True)
        th.start()
        threads.append(th)
    deadline = clock() + join_timeout
    for th in threads:
        th.join(timeout=max(0.0, deadline - clock()))
    return [r if r is not None else Done(error="no response in time")
            for r in results]


def percentile(values, q: float) -> float:
    """Linear-interpolated percentile of a non-empty sequence."""
    v = sorted(values)
    if not v:
        return math.nan
    k = (len(v) - 1) * q / 100.0
    lo, hi = int(math.floor(k)), int(math.ceil(k))
    return v[lo] + (v[hi] - v[lo]) * (k - lo)
