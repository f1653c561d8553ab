"""Traffic kind ``train``: one job's steps, read as sub-windows.

Set-up builds ONE program (the compiled step with its state), drives it
from the seed through its first three steps (whose losses, first gradient
and parameter change the reference follows), warms it up, and hands the
same object to the window.  The loop dispatches steps as a training script
does and fetches the loss every ``log_every`` steps; the fetch is the only
block and each fetch is a timestamp.  The rate is all the window's items
over all its time, first stamp to last.
"""

from __future__ import annotations

import gc
import time

from benchmark import cells, compare, harness, rates
from benchmark import precision as P


def _followed_steps(prog, ref, model, seed, n_steps):
    """The program's first ``n_steps`` steps through the window's own call:
    losses, per-leaf norms of the first gradient and of the parameters'
    change."""
    import jax

    losses, grad_norms = [], None
    for k in range(n_steps):
        losses.append(float(prog.advance()))
        if k == 0:
            g = prog.first_gradient()
            grad_norms = P.named(g, P.leaf_norms(g))
    p0 = ref.init_params(model, seed)          # the seed's weights again
    dparam = P.named(p0, P.leaf_diff_norms(prog.params(), p0))
    del p0
    jax.block_until_ready(prog.params())
    return {"losses": losses, "grad_norms": grad_norms,
            "dparam_norms": dparam}


class GcPauses:
    """The host collector's pauses inside the window (a ``gc.callbacks``
    entry): a stalled sub-window that is the collector's shows here."""

    def __init__(self, clock) -> None:
        self.clock, self.pauses, self._t = clock, [], None

    def __call__(self, phase: str, info: dict) -> None:
        if phase == "start":
            self._t = self.clock()
        elif self._t is not None:
            self.pauses.append((info["generation"], self.clock() - self._t))

    def summary(self) -> dict:
        longest = max(self.pauses, key=lambda p: p[1], default=(None, 0.0))
        return {"count": len(self.pauses),
                "total_s": sum(p[1] for p in self.pauses),
                "longest_generation": longest[0], "longest_s": longest[1]}


def numbers_compared(got: dict, want: dict) -> dict:
    g = compare.worst_leaf_gap(got["grad_norms"], want["grad_norms"])
    d = compare.worst_leaf_gap(got["dparam_norms"], want["dparam_norms"])
    return {"numbers": {"loss_rel": compare.loss_gap(got["losses"],
                                                     want["losses"]),
                        "grad_norm_gap": g["gap"],
                        "dparam_norm_gap": d["gap"]},
            "where": {"grad_norm_gap": g["leaf"],
                      "dparam_norm_gap": d["leaf"]}}


def run_cell(resolved: dict, seed: int, seconds: float, trace: bool,
             run: harness.Run, t_start: float, clock=time.perf_counter,
             build=None, reference_mode: str = "f32") -> dict:
    """Drive one training cell; returns what the result line needs."""
    import jax

    import horovod_tpu as hvd

    config, job, ref = resolved["config"], resolved["traffic"], resolved["ref"]
    model = config["model"]
    chips = run.chips
    marks = {"imports_init_s": clock() - t_start}

    build = build or cells.resolve_callable(config["builder"])
    prog = build(config, job, seed, chips, ref)
    marks["build_s"] = clock() - t_start
    fallbacks0 = hvd.metrics().get("overlap.fallbacks", {}).get("value", 0)

    n_follow = config["check"]["steps"]
    t0 = clock()
    got = _followed_steps(prog, ref, model, seed, n_follow)
    marks["first_steps_s"] = clock() - t0

    # Warm-up: the stream schedule negotiates on step one and replays from
    # its response cache after; both must be in steady state.
    log_every = job["log_every"]
    t0, steps = clock(), n_follow
    while (steps < job["warmup_min_steps"]
           or clock() - t0 < job["warmup_min_seconds"]):
        for _ in range(log_every):
            loss = prog.advance()
        float(loss)
        steps += log_every
    marks["warmup_s"] = clock() - t0
    gc.collect()
    pauses = GcPauses(clock)

    tracer = harness.TraceWindow(trace, harness.trace_dir())
    last = {"loss": None, "steps": 0}

    def do_steps(n):
        for _ in range(n):
            last["loss"] = prog.advance()
        last["steps"] += n

    def fetch():
        value = float(last["loss"])
        last["value"] = value
        if tracer.enabled:
            now = clock()
            if tracer.started_at is None and now - window_t0 > seconds / 4:
                tracer.start()
                last["trace_from"] = last["steps"]
            elif (tracer.started_at is not None and not tracer.stopped
                  and now - tracer.started_at >= job["trace_seconds"]):
                tracer.stop()
                run.traced_steps = last["steps"] - last["trace_from"]
        return value

    run.counters_before = hvd.metrics()
    run.cache.mark()
    setup_s = clock() - t_start
    window_t0 = clock()
    # What set-up built stays out of the collector's way: a full collection
    # over it inside the window is a stall of the harness's own making.
    gc.freeze()
    gc.callbacks.append(pauses)
    try:
        stamps = rates.run_window(do_steps, fetch, log_every, seconds, clock)
    finally:
        gc.callbacks.remove(pauses)
        gc.unfreeze()
    run.cache.end()
    run.counters_after = hvd.metrics()
    if tracer.started_at is not None and not tracer.stopped:
        tracer.stop()
        run.traced_steps = last["steps"] - last["trace_from"]
    loss_end = last["value"]

    from benchmark.device import memory_peak_bytes

    peak = memory_peak_bytes(jax.local_devices()[:chips])
    run.stamps, run.log_every = stamps, log_every
    run.items_per_step = prog.items_per_step
    run.steps_in_window = last["steps"]
    run.notes.update(prog.describe)
    items = prog.items_per_step * log_every
    harness.say("setup", dict(marks, setup_s=setup_s,
                              cache_setup=run.cache.setup))
    harness.say("subwindows", dict(rates.distribution(stamps, items, chips),
                                   gc_pauses=pauses.summary()))
    fell_back = (hvd.metrics().get("overlap.fallbacks", {}).get("value", 0)
                 - fallbacks0)
    run.trace = tracer.reduce()

    # The reference runs after the program's state is freed, so that the
    # peak above stays the program's; its time is not part of set-up.
    batch = prog.batch
    prog.free()
    jax.clear_caches()       # the step's executable holds its temporaries
    gc.collect()
    t0 = clock()
    want = ref.train_reference(model, job, ref.init_params(model, seed),
                               batch, n_follow, chips, reference_mode)
    del batch
    cmp_ = numbers_compared(got, want)
    numbers = dict(cmp_["numbers"])
    limits = dict(config["check"]["limits"])
    # The loss must fall over the window (the job learns its one batch).
    numbers["loss_end_over_start"] = loss_end / got["losses"][0]
    limits["loss_end_over_start"] = 1.0
    numbers["overlap_fallbacks"] = float(fell_back)
    limits["overlap_fallbacks"] = 0.0
    numbers["window_compiles"] = float(run.cache.window["requests"])
    limits["window_compiles"] = 0.0
    verdict = compare.verdict(numbers, {k: limits[k] for k in numbers})
    harness.say("compared", dict(verdict, where=cmp_["where"],
                                 losses={"program": got["losses"],
                                         "reference": want["losses"],
                                         "window_end": loss_end},
                                 reference_s=clock() - t0))

    # One-chip cells must not inherit a loose bound from a host-scheduled
    # cell: a job may name a rate metric of its own (``rate_metric``).
    end_to_end = {
        job.get("rate_metric", "train_rate"): rates.window_rate(stamps, items,
                                                                chips),
        "setup_s": setup_s,
    }
    return {"correct": verdict["correct"], "attempted": last["steps"],
            "failed": 0, "end_to_end": end_to_end, "memory_peak_bytes": peak}
