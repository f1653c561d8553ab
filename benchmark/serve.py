"""Traffic kind ``serve_open_loop``: requests on a schedule, over the
served path, timed from the instant each was due.

Set-up builds the engine behind its HTTP front door, sends one request
through every prefill bucket the mix can reach, and hands the same server
to the window.  After the window a seeded sample of the finished requests,
the longest among them, is checked against the plain reference.
"""

from __future__ import annotations

import gc
import threading
import time

import numpy as np

from benchmark import cells, compare, harness, loadgen


def latencies(results) -> dict:
    """Per-request readings, all from the due instant: first token =
    response instant - (total_ms - ttft_ms); gap = (total_ms - ttft_ms) /
    (tokens - 1)."""
    ttft, tpot = [], []
    for r in results:
        if not r.ok:
            continue
        decode_ms = r.total_ms - r.ttft_ms
        ttft.append(1e3 * (r.responded - r.due) - decode_ms)
        if len(r.tokens) > 1:
            tpot.append(decode_ms / (len(r.tokens) - 1))
    return {"ttft_ms": ttft, "tpot_ms": tpot}


def check_sample(planned, results, seed: int, n: int):
    """Indices of ``n`` finished requests drawn from the seed, the longest
    (prompt + served tokens) always among them."""
    done = [i for i, r in enumerate(results) if r.ok]
    if not done:
        return []
    longest = max(done, key=lambda i: len(planned[i].prompt)
                  + len(results[i].tokens))
    rng = np.random.default_rng([int(seed), 0xC4EC])
    rest = [i for i in done if i != longest]
    pick = rng.choice(len(rest), size=min(n - 1, len(rest)), replace=False)
    return [longest] + [rest[j] for j in sorted(pick)]


def served_gap(ref, model, params, planned, results, sample,
               mode: str = "f32", control: bool = False) -> dict:
    """The widest gap by which a served token's logit lies below the
    reference's best, over every served token of the sample.  With
    ``control`` the tokens judged are those a lower precision (``mode``)
    puts first at each position of the same sequences."""
    seqs = [planned[i].prompt + results[i].tokens for i in sample]
    best = ref.served_logits(model, params, seqs, "f32")
    low = ref.served_logits(model, params, seqs, mode) if control else None
    worst, tokens = 0.0, 0
    for k, i in enumerate(sample):
        p, t = len(planned[i].prompt), len(results[i].tokens)
        rows = best[k][p - 1:p - 1 + t]
        served = (low[k][p - 1:p - 1 + t].argmax(axis=-1).tolist()
                  if control else results[i].tokens)
        worst = max(worst, max(compare.served_token_gaps(rows, served)))
        tokens += t
    return {"served_gap_max": worst, "tokens": tokens,
            "requests": len(sample)}


def run_cell(resolved: dict, seed: int, seconds: float, trace: bool,
             run: harness.Run, t_start: float, clock=time.perf_counter,
             build=None) -> dict:
    import jax

    import horovod_tpu as hvd

    config, traffic, ref = (resolved["config"], resolved["traffic"],
                            resolved["ref"])
    model = config["model"]
    marks = {"imports_init_s": clock() - t_start}
    build = build or cells.resolve_callable(config["serve_builder"])
    prog = build(config, traffic, seed, run.chips, ref)
    marks["build_warm_start_s"] = clock() - t_start
    try:
        t0 = clock()
        warm = loadgen.drive(
            loadgen.warmup_plan(traffic, seed, model["vocab_size"]),
            prog.send, clock)
        marks["prefill_buckets_s"] = clock() - t0
        bad = [w.error for w in warm if not w.ok]
        if bad:
            harness.fail(f"a warm-up request failed: {bad[0]}")
        planned = loadgen.plan(traffic, seconds, seed, model["vocab_size"])
        gc.collect()

        tracer = harness.TraceWindow(trace, harness.trace_dir())
        timer = None
        if trace:
            def traced():
                tracer.start()
                time.sleep(traffic["trace_seconds"])
                tracer.stop()

            timer = threading.Timer(seconds / 4, traced)
            timer.daemon = True

        run.counters_before = hvd.metrics()
        run.cache.mark()
        setup_s = clock() - t_start
        if timer:
            timer.start()
        t0 = clock()
        results = loadgen.drive(planned, prog.send, clock)
        window_s = clock() - t0
        if timer:
            timer.join(timeout=60.0)
        run.cache.end()
        run.counters_after = hvd.metrics()

        from benchmark.device import memory_peak_bytes

        peak = memory_peak_bytes(jax.local_devices()[:run.chips])
        params = prog.params()
        run.slots = prog.slots
    finally:
        prog.close()
    run.trace = tracer.reduce()
    gc.collect()

    lat = latencies(results)
    failed = sum(1 for r in results if not r.ok)
    run.requests, run.ttft_ms = results, lat["ttft_ms"]
    harness.say("setup", dict(marks, setup_s=setup_s,
                              cache_setup=run.cache.setup))
    tokens = sum(len(r.tokens) for r in results)
    harness.say("window", {
        "requests": len(results), "failed": failed, "window_s": window_s,
        "offered_rate_per_s": traffic["rate_per_s"], "tokens": tokens,
        "completed_tokens_per_s": tokens / window_s,
        "ttft_ms": {q: loadgen.percentile(lat["ttft_ms"], q)
                    for q in (50, 90, 99)} if lat["ttft_ms"] else None,
        "tpot_ms": {q: loadgen.percentile(lat["tpot_ms"], q)
                    for q in (50, 90, 99)} if lat["tpot_ms"] else None,
        "errors": sorted({r.error for r in results if not r.ok})[:3]})

    t0 = clock()
    sample = check_sample(planned, results, seed, traffic["check_requests"])
    numbers = {"failed_requests": float(failed),
               "window_compiles": float(run.cache.window["requests"])}
    limits = {"failed_requests": 0.0, "window_compiles": 0.0,
              "served_gap_max": config["check"]["limits"]["served_gap_max"]}
    detail = {}
    if sample:
        detail = served_gap(ref, model, params, planned, results, sample)
        numbers["served_gap_max"] = detail["served_gap_max"]
    else:
        numbers["served_gap_max"] = float("inf")
    del params
    verdict = compare.verdict(numbers, limits)
    harness.say("compared", dict(verdict, sample=detail,
                                 reference_s=clock() - t0))

    end_to_end = {"setup_s": setup_s}
    if lat["tpot_ms"]:
        end_to_end["tpot_p50_ms"] = loadgen.percentile(lat["tpot_ms"], 50)
        end_to_end["tpot_p90_ms"] = loadgen.percentile(lat["tpot_ms"], 90)
        end_to_end["ttft_p50_ms"] = loadgen.percentile(lat["ttft_ms"], 50)
    return {"correct": verdict["correct"], "attempted": len(results),
            "failed": failed, "end_to_end": end_to_end,
            "memory_peak_bytes": peak}
