"""Read the numbers a cell's limits are set from, in ONE process.

    python -m benchmark.tools.calibrate --workload <cell> --seeds 1,2,... \
        --control-seeds 1,2,3 [--seconds 12]

For every seed: the program's numbers against the float32 reference (what
a sound run reads).  For every control seed: the control's numbers, the
reference computed in the nearest precision below the configuration's and
put in the program's place.  A limit goes above the sound runs' largest and
below the control's smallest (PERF.md section 2 keeps the readings).
Training cells need no measured window; a serving cell runs a short one at
the cell's own load.  Chip only, like a run.
"""

from __future__ import annotations

import argparse
import gc
import json
import sys
import time

import jax

from benchmark import cells, device, harness, loadgen, serve, train
from benchmark.cachecount import CacheCounter


def _train(resolved, seeds, control_seeds, chips, dump=None, dumped=None,
           control_only=False):
    """``dump``: only run the program and write its readings to that file
    (a four-chip call pays for nothing else).  ``dumped``: take the
    program's readings from such a file and run only the reference and the
    control here (one chip holds them: they are not distributed)."""
    config, job, ref = resolved["config"], resolved["traffic"], resolved["ref"]
    model = config["model"]
    build = cells.resolve_callable(config["builder"])
    n = config["check"]["steps"]
    low = config["control_precision"]
    taken = json.load(open(dumped)) if dumped else {}
    written = {}
    for seed in seeds:
        t0 = time.perf_counter()
        if control_only:
            got = None
            batch = ref.make_batch(model, job, seed,
                                   job["per_chip_batch"] * chips)
        elif dumped:
            got = taken[str(seed)]
            batch = ref.make_batch(model, job, seed,
                                   job["per_chip_batch"] * chips)
        else:
            prog = build(config, job, seed, chips, ref)
            got = train._followed_steps(prog, ref, model, seed, n)
            batch = prog.batch
            prog.free()
            jax.clear_caches()
            gc.collect()
        if dump:
            written[str(seed)] = got
            with open(dump, "w") as f:
                json.dump(written, f)
            harness.say("calibrate", {"seed": seed, "dumped": dump,
                                      "losses": got["losses"],
                                      "program_s": time.perf_counter() - t0})
            continue
        t1 = time.perf_counter()
        want = ref.train_reference(model, job, ref.init_params(model, seed),
                                   batch, n, chips, "f32")
        t2 = time.perf_counter()
        out = {"seed": seed, "reference_s": t2 - t1,
               "reference_losses": want["losses"]}
        if got is not None:
            out.update(program=train.numbers_compared(got, want),
                       losses=[got["losses"], want["losses"]],
                       program_s=t1 - t0)
        if seed in control_seeds:
            ctl = ref.train_reference(model, job,
                                      ref.init_params(model, seed), batch, n,
                                      chips, low)
            out["control"] = train.numbers_compared(ctl, want)
            out["control_s"] = time.perf_counter() - t2
            out["control_losses"] = ctl["losses"]
        del batch
        harness.say("calibrate", out)


def _serve(resolved, seeds, control_seeds, chips, seconds):
    config, traffic, ref = (resolved["config"], resolved["traffic"],
                            resolved["ref"])
    model = config["model"]
    build = cells.resolve_callable(config["serve_builder"])
    low = config["control_precision"]
    for seed in seeds:
        prog = build(config, traffic, seed, chips, ref)
        try:
            loadgen.drive(loadgen.warmup_plan(traffic, seed,
                                              model["vocab_size"]), prog.send)
            planned = loadgen.plan(traffic, seconds, seed,
                                   model["vocab_size"])
            results = loadgen.drive(planned, prog.send)
            params = prog.params()
        finally:
            prog.close()
        gc.collect()
        sample = serve.check_sample(planned, results, seed,
                                    traffic["check_requests"])
        out = {"seed": seed,
               "failed": sum(1 for r in results if not r.ok),
               "program": serve.served_gap(ref, model, params, planned,
                                           results, sample)}
        if seed in control_seeds:
            out["control"] = serve.served_gap(ref, model, params, planned,
                                              results, sample, low, True)
        del params
        harness.say("calibrate", out)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--control-seeds", default="")
    ap.add_argument("--seconds", type=float, default=12.0)
    ap.add_argument("--dump-program", default=None,
                    help="training: write the program's readings here and "
                         "skip the reference")
    ap.add_argument("--control-only", action="store_true",
                    help="training: no program at all, only the control "
                         "against the reference at the cell's own size "
                         "(one chip holds both)")
    ap.add_argument("--program-from", default=None,
                    help="training: take the program's readings from this "
                         "file; runs on one chip whatever the cell asks")
    args = ap.parse_args(argv)
    seeds = [int(s) for s in args.seeds.split(",") if s]
    control = {int(s) for s in args.control_seeds.split(",") if s}
    resolved = cells.resolve(cells.load_benchmark(), args.workload)
    chips = resolved["cell"]["chips"]
    device.require_tpu(1 if args.program_from or args.control_only
                       else chips)
    import horovod_tpu as hvd

    CacheCounter().install()
    hvd.init()
    try:
        if resolved["traffic"]["runner"] == "benchmark.train:run_cell":
            _train(resolved, seeds, control, chips, args.dump_program,
                   args.program_from, args.control_only)
        else:
            _serve(resolved, seeds, control, chips, args.seconds)
    finally:
        hvd.shutdown()
    return 0


if __name__ == "__main__":
    sys.exit(main())
