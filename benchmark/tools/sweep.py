"""Find a serving cell's knee, once, by a sweep on the chip.

    python -m benchmark.tools.sweep --workload <cell> --rates 2,3,4,5,6 \
        --seconds 30 --seed 5

One process, one server; each rate offers the cell's own mix for
``--seconds`` and drains.  The knee is the highest rate at which the wait
for a first token at the END of the window (requests due in its last
fifth) is no longer than in its MIDDLE fifth: past it the queue grows all
through the run.  The traffic file then fixes 0.8 of the knee as a number.
"""

from __future__ import annotations

import argparse
import statistics
import sys

from benchmark import cells, device, harness, loadgen, serve
from benchmark.cachecount import CacheCounter


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--rates", required=True)
    ap.add_argument("--seconds", type=float, default=30.0)
    ap.add_argument("--seed", type=int, default=5)
    args = ap.parse_args(argv)
    resolved = cells.resolve(cells.load_benchmark(), args.workload)
    config, traffic, ref = (resolved["config"], resolved["traffic"],
                            resolved["ref"])
    device.require_tpu(resolved["cell"]["chips"])
    import horovod_tpu as hvd

    CacheCounter().install()
    hvd.init()
    vocab = config["model"]["vocab_size"]
    prog = cells.resolve_callable(config["serve_builder"])(
        config, traffic, args.seed, resolved["cell"]["chips"], ref)
    try:
        loadgen.drive(loadgen.warmup_plan(traffic, args.seed, vocab),
                      prog.send)
        for k, rate in enumerate(float(r) for r in args.rates.split(",")):
            planned = loadgen.plan(dict(traffic, rate_per_s=rate),
                                   args.seconds, args.seed + k, vocab)
            before = hvd.metrics()
            results = loadgen.drive(planned, prog.send)
            after = hvd.metrics()
            lat = serve.latencies(results)
            t0 = min(r.due for r in results)

            def ttft_of(lo, hi):
                v = serve.latencies(
                    [r for r in results
                     if lo <= (r.due - t0) / args.seconds < hi])["ttft_ms"]
                return statistics.mean(v) if v else None

            tokens = sum(len(r.tokens) for r in results)
            span = max(r.responded for r in results) - t0
            iters = (after["serving.decode_iterations"]["value"]
                     - before["serving.decode_iterations"]["value"])
            harness.say("sweep", {
                "rate_per_s": rate, "requests": len(results),
                "failed": sum(1 for r in results if not r.ok),
                "ttft_mid_ms": ttft_of(0.4, 0.6),
                "ttft_end_ms": ttft_of(0.8, 1.01),
                "ttft_p50_ms": loadgen.percentile(lat["ttft_ms"], 50),
                "ttft_p90_ms": loadgen.percentile(lat["ttft_ms"], 90),
                "tpot_p50_ms": loadgen.percentile(lat["tpot_ms"], 50),
                "tpot_p90_ms": loadgen.percentile(lat["tpot_ms"], 90),
                "completed_tokens_per_s": tokens / span,
                "drain_s": span - args.seconds,
                "decode_iterations": iters})
    finally:
        prog.close()
        hvd.shutdown()
    return 0


if __name__ == "__main__":
    sys.exit(main())
