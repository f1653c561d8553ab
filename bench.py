"""Benchmark: ResNet-50 data-parallel training throughput (images/sec/chip).

Mirrors the reference's headline benchmark — ResNet training throughput
with synthetic ImageNet data via tf_cnn_benchmarks
(docs/benchmarks.md:22-40): ResNet-101, batch 64/GPU on 16 Pascal GPUs
reached 1656.82 images/sec total = 103.55 images/sec/GPU.  That per-chip
number is the ``vs_baseline`` denominator here.

``python bench.py`` is ONE process that runs :func:`run` on every chip
it finds and prints ONE JSON line:
  {"metric": "resnet50_images_per_sec_per_chip", "value": N,
   "unit": "images/sec/chip", "vs_baseline": N, "mfu": N,
   "device": {"platform": "tpu", "device_kind": "...", "count": N}, ...}
It starts no child and exits non-zero on any exception, when jax finds
no TPU, and when the chip's ``device_kind`` is not in the peak table.
The compile cache follows ``horovod_tpu.core.state.compile_cache_dir``
(``JAX_COMPILATION_CACHE_DIR`` if set, else ``<checkout>/.jax_cache``).

The ``--mode`` microbenchmarks (control, dataplane, input, serving,
overlap, pipeline, memory, fused, tuning, routing) are CPU contract
gates: each pins the 8-virtual-device CPU mesh itself and reports
counts, identities and host-side ratios, never a device speed.

Usage:
  python bench.py                 # the chip run (batch 128 a chip)
  python bench.py --smoke         # tiny shapes, any platform (CPU sanity)
  python bench.py --mode control  # control-plane negotiations/sec only
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import time

# Reference: 1656.82 images/sec on 16 GPUs (docs/benchmarks.md:22-40).
BASELINE_IMAGES_PER_SEC_PER_CHIP = 1656.82 / 16

# Peak dense bf16 FLOP/s of one chip, keyed by the exact
# ``jax.devices()[0].device_kind``.  Source: Google Cloud documentation,
# "TPU v5e" (197 TFLOP/s bf16, 16 GB HBM at 819 GB/s); a v5e reports
# itself as "TPU v5 lite".  A kind that is not here is an error, not a
# default: add it with its source.
PEAK_BF16_FLOPS = {
    "TPU v5 lite": 197e12,
}


def chip_peak_flops(device_kind: str) -> float:
    try:
        return PEAK_BF16_FLOPS[device_kind]
    except KeyError:
        raise RuntimeError(
            f"device_kind {device_kind!r} is not in bench.PEAK_BF16_FLOPS "
            f"(known: {sorted(PEAK_BF16_FLOPS)}); add its published peak "
            f"with the source before measuring on it") from None


def device_info() -> dict:
    """The device as jax reports it; every result line carries it."""
    import jax

    dev = jax.devices()[0]
    return {"platform": dev.platform, "device_kind": dev.device_kind,
            "count": jax.device_count()}


def require_tpu() -> dict:
    """:func:`device_info`, or SystemExit naming what was found instead
    of a TPU: a measurement path never falls back to the CPU.  A TPU
    whose kind has no published peak here is an error too."""
    device = device_info()
    if device["platform"] != "tpu":
        raise SystemExit(
            f"no TPU: jax found platform {device['platform']!r} "
            f"({device['device_kind']!r} x {device['count']}); this path "
            f"runs on the chip and does not fall back")
    chip_peak_flops(device["device_kind"])
    return device


def run(batch_size: int, image_size: int, warmup: int, iters: int,
        model_ctor=None, num_classes: int = 1000) -> dict:
    import jax
    import jax.numpy as jnp
    import optax

    import horovod_tpu as hvd
    from horovod_tpu.models import resnet as R
    from horovod_tpu.parallel import overlap
    from horovod_tpu.parallel.training import (make_train_step_with_state,
                                               shard_batch)

    hvd.init()
    n_chips = hvd.size()
    model = (model_ctor or R.ResNet50)(num_classes=num_classes)
    params, stats = R.init_resnet(model, image_size=image_size,
                                  batch_size=batch_size)
    params = hvd.broadcast_parameters(params, root_rank=0)

    # The reference benchmark recipe: SGD with momentum, synthetic data
    # (docs/benchmarks.md:28-33).
    opt = optax.sgd(0.1, momentum=0.9)
    loss_fn = R.resnet_loss_fn(model)
    step = make_train_step_with_state(loss_fn, opt)
    schedule = overlap.resolve_mode(None, hvd.mesh())

    global_batch = batch_size * n_chips
    images, labels = R.synthetic_imagenet(global_batch,
                                          image_size=image_size,
                                          num_classes=num_classes)
    batch = shard_batch((jnp.asarray(images), jnp.asarray(labels)))
    opt_state = opt.init(params)

    # FLOPs per chip per step from XLA's cost analysis of the monolithic
    # program (SPMD-partitioned, so already per chip).  With overlap off
    # that program IS the step and its executable runs the loops; the
    # overlapped step is a host-driven sequence of programs with no
    # single executable to ask, so its monolithic twin is compiled for
    # the count alone.
    mono = step if schedule == "off" else make_train_step_with_state(
        loss_fn, opt, overlap="off")
    compiled = mono.lower(params, stats, opt_state, batch).compile()
    flops_per_chip_step = float(compiled.cost_analysis()["flops"])
    if schedule == "off":
        step = compiled

    for _ in range(warmup):
        params, stats, opt_state, loss = step(params, stats, opt_state,
                                              batch)
    jax.block_until_ready((params, loss))

    t0 = time.perf_counter()
    for _ in range(iters):
        params, stats, opt_state, loss = step(params, stats, opt_state,
                                              batch)
    jax.block_until_ready((params, loss))
    dt = time.perf_counter() - t0

    delivered = flops_per_chip_step * iters / dt
    return {"value": global_batch * iters / dt / n_chips,
            "n_chips": n_chips, "overlap": schedule,
            "flops_source": "xla_cost_analysis",
            "tflops_per_chip": round(delivered / 1e12, 2),
            "loss": float(loss)}


def _control_bench(tensors: int = 64, ranks: int = 4,
                   seconds: float = 1.0) -> dict:
    """Negotiations/sec through the real control plane, cache off vs on.

    Models the rank-0 controller's steady-state tick for a 64-tensor
    program (the multi-process hot path of ops/collective._drain +
    ops/transport._handle_request_batch): rank 0's own requests go
    through the Coordinator facade; the workers' arrivals are, cache
    OFF, wire-parsed full requests fed to submit (table accumulation +
    validation + response construction + fusion planning) and, cache
    ON, decoded bit-vector hits fed to ``hit_from_wire`` followed by
    the memoized-plan replay — exactly what each tick costs on the
    production code path.
    """
    from horovod_tpu import trace as _hvd_trace
    from horovod_tpu.ops import cache as hvd_cache
    from horovod_tpu.ops import wire
    from horovod_tpu.ops.coordinator import Coordinator

    threshold = 64 << 20

    def request_of(t: int, r: int) -> "wire.Request":
        return wire.Request(
            request_rank=r, request_type=wire.RequestType.ALLREDUCE,
            tensor_type=wire.DataType.FLOAT32, tensor_name=f"grad.{t}",
            tensor_shape=(1024,), reduce_op=wire.ReduceOp.SUM)

    # The workers' frames as they sit in the receive buffer: packed wire
    # bytes (parsing them is part of the cache-off cost, exactly as in
    # transport._serve).
    packed = [[request_of(t, r).pack() for r in range(1, ranks)]
              for t in range(tensors)]

    def drain(coord, cache) -> int:
        # Mirrors collective._drain's per-tick hvd-trace work (cycle
        # advance + negotiate span + the 16-byte context trailer) so
        # the trace on/off A/B below prices the span layer on the same
        # path production ticks pay it.
        t0 = time.monotonic() if _hvd_trace.enabled() else 0.0
        resps = []
        if cache is not None:
            marker = cache.take_flush_marker()
            if marker is not None:
                resps.append(marker)
            replayed, _g, _e, _c = cache.take_ready(lambda psid: threshold)
            resps += replayed
        resps += coord.poll_responses({})
        if cache is not None:
            for resp in resps:
                cache.observe_response(resp)
        if resps and _hvd_trace.enabled():
            _hvd_trace.next_cycle()
            _hvd_trace.span("negotiate.tick", "negotiate", t0,
                            time.monotonic(),
                            args={"responses": len(resps)})
            _hvd_trace.pack_ctx()
        return sum(len(r.tensor_names) for r in resps
                   if r.response_type == wire.ResponseType.ALLREDUCE)

    def measure(cache_on: bool):
        cache = hvd_cache.ResponseCache(rank=0) if cache_on else None
        coord = Coordinator(size=ranks, fusion_threshold=threshold,
                            cache=cache)

        # Warmup cycle = the first (cold) negotiation; populates the
        # cache and yields the entry indices the workers' bits name.
        for t in range(tensors):
            coord.submit(request_of(t, 0))
            for buf in packed[t]:
                req, _ = wire.Request.unpack(buf)
                coord.submit(req)
        n = drain(coord, cache)
        assert n == tensors, (n, tensors)
        idxs = None
        if cache is not None:
            idxs = [cache.entry_index(f"grad.{t}") for t in range(tensors)]
            assert all(i is not None for i in idxs), idxs
            epoch = cache.epoch

        def one_cycle() -> int:
            if cache is None:
                for t in range(tensors):
                    coord.submit(request_of(t, 0))
                    for buf in packed[t]:
                        req, _ = wire.Request.unpack(buf)
                        coord.submit(req)
            else:
                for t in range(tensors):
                    coord.submit(request_of(t, 0))
                    for r in range(1, ranks):
                        down = cache.hit_from_wire(idxs[t], r, epoch)
                        assert down is None, down
            return drain(coord, cache)

        done = 0
        cycles = 0
        t0 = time.perf_counter()
        while time.perf_counter() - t0 < seconds:
            got = one_cycle()
            assert got == tensors, (got, tensors)
            done += got
            cycles += 1
        dt = time.perf_counter() - t0
        if cache is not None:
            s = cache.stats
            assert s.replayed_tensors >= done, \
                ("cache-on run must serve from replay", s)
        coord.close()
        return done / dt, cycles

    off_rate, off_cycles = measure(cache_on=False)
    on_rate, on_cycles = measure(cache_on=True)

    # Telemetry overhead A/B (the hvd-telemetry acceptance gate,
    # docs/metrics.md): the SAME steady-state measurement with the
    # whole subsystem (registry + flight recorder) disabled.  Recorded
    # in the JSON — ≤ 5 % regression is the contract; the boolean is
    # informational (a loaded box can fake either direction).
    from horovod_tpu import telemetry as _telemetry

    was_enabled = _telemetry.enabled()
    _telemetry.set_enabled(False)
    try:
        notel_on_rate, _ = measure(cache_on=True)
        notel_off_rate, _ = measure(cache_on=False)
    finally:
        _telemetry.set_enabled(was_enabled)

    def overhead_pct(with_tel, without_tel):
        if not without_tel:
            return None
        return round((1.0 - with_tel / without_tel) * 100.0, 2)

    tel_pct = overhead_pct(on_rate, notel_on_rate)

    # hvd-trace overhead A/B (same contract as telemetry's): the same
    # steady-state measurement with span recording disabled.  The
    # baseline legs above ran with tracing at its default (on), so
    # trace-off minus trace-on is the span layer's whole cost.
    trace_was = _hvd_trace.enabled()
    _hvd_trace.set_enabled(False)
    try:
        notrace_on_rate, _ = measure(cache_on=True)
    finally:
        _hvd_trace.set_enabled(trace_was)
    trace_pct = overhead_pct(on_rate, notrace_on_rate)

    tel_counters = {
        name: m.get("value")
        for name, m in _telemetry.metrics().items()
        if m.get("type") in ("counter", "gauge")
    }
    return {
        "metric": "control_plane_negotiations_per_sec",
        "value": round(on_rate, 1),
        "unit": "negotiations/sec",
        "cache_on": round(on_rate, 1),
        "cache_off": round(off_rate, 1),
        "speedup": round(on_rate / off_rate, 2) if off_rate else None,
        "vs_baseline": round(on_rate / off_rate, 2) if off_rate else None,
        "tensors": tensors,
        "ranks": ranks,
        "cycles": {"cache_on": on_cycles, "cache_off": off_cycles},
        "telemetry": {
            "cache_on_metrics_on": round(on_rate, 1),
            "cache_on_metrics_off": round(notel_on_rate, 1),
            "cache_off_metrics_on": round(off_rate, 1),
            "cache_off_metrics_off": round(notel_off_rate, 1),
            "overhead_pct": tel_pct,
            "overhead_off_pct": overhead_pct(off_rate, notel_off_rate),
            "overhead_ok": tel_pct is not None and tel_pct <= 5.0,
            "counters": tel_counters,
        },
        "trace": {
            "trace_on": round(on_rate, 1),
            "trace_off": round(notrace_on_rate, 1),
            "overhead_pct": trace_pct,
            "overhead_ok": trace_pct is not None and trace_pct <= 5.0,
        },
    }


def _tree_bench(tensors: int = 16, seconds: float = 0.4) -> dict:
    """Tree-overlay section of ``--mode control``: rank-0 received
    control frames per steady-state negotiation cycle (and per
    metrics/trace pull) at simulated world sizes 64/256/1024, plus the
    root's merged-envelope processing rate.

    Virtual-slice-style dryrun, no XLA and no sockets: the layouts and
    per-child envelopes come from the REAL aggregation code
    (ops/tree.steady_envelope — the same grouping the live interiors
    run), and the root side runs the REAL ResponseCache accounting +
    fused replay per envelope section.  The frame counts are the
    structural quantity the CI gate bounds: rank 0 receives one merged
    envelope per direct child instead of world-1 per-rank frames."""
    import math

    from horovod_tpu.ops import cache as hvd_cache
    from horovod_tpu.ops import tree as hvd_tree
    from horovod_tpu.ops import wire

    # Pinned, not read from HVD_TPU_TREE_FANOUT: the gate's bound and
    # the contract test's flat-vs-tree ratio assume this shape, and an
    # ambient env setting must not fail the bench without a code
    # defect (tests/test_tree.py pins the same way).
    fanout = 8
    threshold = 64 << 20

    def request_of(t: int, r: int) -> "wire.Request":
        return wire.Request(
            request_rank=r, request_type=wire.RequestType.ALLREDUCE,
            tensor_type=wire.DataType.FLOAT32, tensor_name=f"grad.{t}",
            tensor_shape=(1024,), reduce_op=wire.ReduceOp.SUM)

    worlds = []
    for world in (64, 256, 1024):
        layout = hvd_tree.build_layout(world, fanout)
        cache = hvd_cache.ResponseCache(rank=0)
        for t in range(tensors):
            name = f"grad.{t}"
            cache.stage_negotiated(
                name, {r: request_of(t, r) for r in range(world)})
            cache.observe_response(wire.Response(
                wire.ResponseType.ALLREDUCE, tensor_names=[name],
                tensor_shapes=[(1024,)],
                tensor_type=wire.DataType.FLOAT32))
        epoch = cache.epoch
        idxs = list(range(tensors))
        envelopes = [hvd_tree.steady_envelope(layout, c, epoch, idxs)
                     for c in layout.children(0)]

        def one_cycle() -> int:
            for i in idxs:  # rank 0's own hits
                cache.hit_from_wire(i, 0, epoch)
            for env in envelopes:
                for sec in hvd_tree.iter_subtree_sections(env):
                    if sec[0] == "bits":
                        _k, ep, ranks, ii = sec
                        for r in ranks:
                            for i in ii:
                                cache.hit_from_wire(i, r, ep)
            resps, _g, _e, _c = cache.take_ready(lambda _p: threshold)
            for r in resps:
                cache.observe_response(r, replay=True)
            return sum(len(r.tensor_names) for r in resps)

        got = one_cycle()
        assert got == tensors, (got, tensors)
        done = 0
        cycles = 0
        t0 = time.perf_counter()
        while time.perf_counter() - t0 < seconds:
            done += one_cycle()
            cycles += 1
        dt = time.perf_counter() - t0
        # Structural frame accounting comes from the one shared
        # implementation (ops/tree.simulate_cycle_frames) — the bench
        # adds only the measured processing rate and the gate bound.
        stats = hvd_tree.simulate_cycle_frames(world, fanout)
        stats["fanout_log_bound"] = fanout * max(1, math.ceil(
            math.log(world, max(2, fanout))))
        stats["negotiations_per_sec"] = round(done / dt, 1)
        stats["cycles"] = cycles
        worlds.append(stats)
    return {
        "metric": "tree_root_frames_per_cycle",
        "fanout": fanout,
        "tensors": tensors,
        "worlds": worlds,
    }


def _dataplane_bench(tensors: int = 32, elems: int = 256,
                     cycles: int = 30) -> dict:
    """Steady-state fused-cycle latency + dispatches/cycle, eager
    per-tensor executor vs megakernel (``--mode dataplane``).

    Runs the REAL dynamic path end to end on the 8-virtual-CPU-device
    mesh (same trick as tests/conftest.py, no chip): a
    ``tensors``-wide AVERAGE allreduce program with stable names, so
    after the cold cycle every cycle is a response-cache replay whose
    fusion plan is memoized — the steady state of a training loop.  The
    eager leg (HVD_TPU_MEGAKERNEL=0) surrounds each fused response with
    the per-tensor pack/slice/divide choreography; the megakernel leg
    launches one donated pack→reduce→unpack executable per fusion group
    (ops/megakernel.py).  Dispatches/cycle are REAL XLA executable
    launches counted at jax's dispatch choke point
    (utils/xla_dispatch.py).  The same run proves the two legs bitwise
    identical and the hierarchical ICI×DCN kernel (2 virtual slices)
    equivalent to the flat psum — the dataplane perf contract of
    docs/performance.md.
    """
    import numpy as np

    os.environ["HVD_TPU_COUNT_DISPATCHES"] = "1"
    # Pin the default compressor: the base legs' bitwise-identity and
    # hierarchical-equivalence gates are contracts of the UNCOMPRESSED
    # path; the quantized codecs get their own measured legs below.
    os.environ["HVD_TPU_COMPRESSION"] = "none"
    import jax
    import jax.numpy as jnp

    import horovod_tpu as hvd
    from horovod_tpu.ops import megakernel as mk
    from horovod_tpu.utils import xla_dispatch

    hvd.init(devices=jax.devices())
    try:
        n = hvd.size()
        rng = np.random.default_rng(7)
        # Integer-valued floats: exact under any reduction order, so the
        # hierarchical leg can be compared bitwise, not just allclose.
        base = [rng.integers(-8, 8, size=(n, elems)).astype(np.float32)
                for _ in range(tensors)]
        inputs = [hvd.shard(t) for t in base]

        def cycle(tag):
            # quiesce: the background drain tick must not fire between
            # two submissions of one cycle — it would negotiate them as
            # two fused responses and break every ==1-launch contract
            # below.  One explicit drain on exit serves the whole group.
            with hvd.quiesce():
                hs = [hvd.allreduce_async(x, average=True,
                                          name=f"{tag}.{j}")
                      for j, x in enumerate(inputs)]
            return [hvd.synchronize(h) for h in hs]

        def measure(tag, mega):
            mk.set_enabled(mega)
            cycle(tag)   # cold: compile + populate the response cache
            cycle(tag)   # warm: replayed negotiation, memoized plan
            launches0 = mk.stats.launches
            # Dispatch counting needs every launch visible — the
            # exact_scope disables jax's C++ fastpath while counting
            # (measurement-only; the latency loop below runs outside
            # it, at full dispatch speed on both legs).
            with xla_dispatch.exact_scope():
                with xla_dispatch.record(all_threads=True) as scope:
                    results = cycle(tag)
            groups = mk.stats.launches - launches0
            cycle(tag)   # re-warm the fastpath after the exact window
            lats = []
            for _ in range(cycles):
                t0 = time.perf_counter()
                cycle(tag)
                lats.append(time.perf_counter() - t0)
            # Median, not mean: this is a shared box (CI runner, the
            # 1-core dev container) and a single background spike in
            # one leg would otherwise fake — or mask — a regression.
            lats.sort()
            return results, scope.count, lats[len(lats) // 2], groups

        eager_res, eager_disp, eager_lat, _ = measure("eager", False)
        mega_res, mega_disp, mega_lat, groups = measure("mega", True)
        identical = all(
            np.asarray(a).tobytes() == np.asarray(b).tobytes()
            for a, b in zip(eager_res, mega_res))

        # Hierarchical ICI×DCN verification: declare 2 virtual slices on
        # the dryrun mesh and compare against the flat-psum results.
        os.environ["HVD_TPU_HIERARCHICAL"] = "on"
        os.environ["HVD_TPU_VIRTUAL_SLICES"] = "2"
        try:
            hier0 = mk.stats.hier_launches
            hier_res = cycle("hier")
            hier_ran = mk.stats.hier_launches > hier0
            hier_equal = hier_ran and all(
                np.asarray(a).tobytes() == np.asarray(b).tobytes()
                for a, b in zip(eager_res, hier_res))
        finally:
            del os.environ["HVD_TPU_HIERARCHICAL"]
            del os.environ["HVD_TPU_VIRTUAL_SLICES"]

        # Bytes-on-wire accounting + quantized-reduction legs (ISSUE 6):
        # per compressor, the steady-state cycle latency, REAL
        # dispatches/cycle (the quantize→exchange→dequantize pipeline
        # must stay inside the one fused executable), logical vs wire
        # bytes per cycle from the executor's accounting, and — for the
        # int codecs — equality against the eager-quantized REFERENCE
        # (ops/compression.reference_allreduce) at tick 0.
        from horovod_tpu.ops import compression as _compression

        rows = np.concatenate([t.reshape(n, -1) for t in base], axis=1)
        compression_section = {}
        none_lat = None
        for comp_name in ("none", "int8", "int4"):
            hvd.set_compression(default=comp_name)  # flushes exec state
            ref_equal = None
            if comp_name != "none":
                # Fresh names → tick 0, zero residuals: the reference
                # must match the fused kernel BITWISE.  The reference
                # models single-group packing; cycle() quiesces the
                # drain tick, so the cycle lands in exactly one launch
                # deterministically — no retry loop needed.
                got = cycle(f"refq.{comp_name}")
                fmt = _compression.wire_format(comp_name)
                ref, _ = _compression.reference_allreduce(rows, fmt, 0)
                expected = np.asarray(jnp.asarray(ref) / n)  # AVERAGE
                got_flat = np.concatenate(
                    [np.asarray(r)[0].reshape(-1) for r in got])
                ref_equal = bool(
                    expected.tobytes() == got_flat.tobytes())
            _, disp_c, lat_c, grp = measure(f"comp.{comp_name}", True)
            if comp_name == "none":
                # The ADJACENT uncompressed measurement is the
                # throughput baseline — comparing against a leg timed
                # minutes earlier folds the shared box's load drift
                # into the ratio.
                none_lat = lat_c
            w0 = mk.stats.wire_bytes
            l0 = mk.stats.logical_bytes
            cycle(f"comp.{comp_name}")
            wire_b = mk.stats.wire_bytes - w0
            logical_b = mk.stats.logical_bytes - l0
            compression_section[comp_name] = {
                "cycle_us": round(lat_c * 1e6, 1),
                "speedup_vs_uncompressed":
                    round(none_lat / lat_c, 2) if lat_c else None,
                "dispatches_per_cycle": disp_c,
                "logical_bytes_per_cycle": logical_b,
                "wire_bytes_per_cycle": wire_b,
                "compression_ratio":
                    round(logical_b / wire_b, 2) if wire_b else None,
                "reference_equal": ref_equal,
            }
        hvd.set_compression()  # restore the (pinned-none) env default

        # hvd-mem: measured ledger high-watermark of one steady-state
        # fused cycle vs the static planner's prediction (the ±15 %
        # accuracy contract of docs/memory.md; --mode memory owns the
        # CI gate, this section records the figures per round).
        # cycle() quiesces the drain tick, so the watermark always
        # observes a single-launch cycle.
        from horovod_tpu.memory import ledger as _mem_ledger
        from horovod_tpu.memory import planner as _mem_planner

        led = _mem_ledger.ledger
        led.reset()
        cycle("memsec")
        mem_measured = led.watermark()
        mem_predicted = _mem_planner.plan_dataplane(
            tensors, elems, n).framework_bytes
        mem_err_pct = (round(abs(mem_predicted - mem_measured)
                             / mem_measured * 100.0, 2)
                       if mem_measured else None)
        led.reset()

        # Telemetry overhead A/B on the megakernel leg (same contract
        # as --mode control: the hvd-telemetry acceptance gate rides
        # the bench JSON).  The executor instrumentation is per
        # fused-response, so the expected delta is noise-level.
        from horovod_tpu import telemetry as _telemetry

        was_enabled = _telemetry.enabled()
        _telemetry.set_enabled(False)
        try:
            _, _, mega_lat_notel, _ = measure("notel", True)
        finally:
            _telemetry.set_enabled(was_enabled)
            mk.set_enabled(None)
        tel_pct = (round((mega_lat / mega_lat_notel - 1.0) * 100.0, 2)
                   if mega_lat_notel else None)

        # hvd-trace overhead A/B on the same leg: the launch + dispatch
        # spans are per fused response, so the expected delta is
        # noise-level too (the ≤ 5 % gate of docs/tracing.md).
        from horovod_tpu import trace as _hvd_trace

        trace_was = _hvd_trace.enabled()
        _hvd_trace.set_enabled(False)
        try:
            _, _, mega_lat_notrace, _ = measure("notrace", True)
        finally:
            _hvd_trace.set_enabled(trace_was)
            mk.set_enabled(None)
        trace_pct = (round((mega_lat / mega_lat_notrace - 1.0) * 100.0,
                           2) if mega_lat_notrace else None)
        snap = _telemetry.metrics()
        tel_counters = {
            name: m.get("value") for name, m in snap.items()
            if name.startswith(("megakernel.", "collective.", "cache.",
                                "compression."))
            and m.get("type") in ("counter", "gauge")
        }

        reduction = (eager_disp / mega_disp) if mega_disp else None
        return {
            "metric": "dataplane_fused_cycle_latency_us",
            "value": round(mega_lat * 1e6, 1),
            "unit": "us/cycle",
            "eager_us": round(eager_lat * 1e6, 1),
            "megakernel_us": round(mega_lat * 1e6, 1),
            "speedup": round(eager_lat / mega_lat, 2) if mega_lat else None,
            "vs_baseline": round(eager_lat / mega_lat, 2) if mega_lat
            else None,
            "dispatches_per_cycle": {"eager": eager_disp,
                                     "megakernel": mega_disp},
            "dispatch_reduction": round(reduction, 1)
            if reduction else None,
            "fusion_groups_per_cycle": groups,
            "bitwise_identical": identical,
            "hierarchical_equal": hier_equal,
            "compression": compression_section,
            # hvd-mem (docs/memory.md): the ledger's measured peak vs
            # the planner's prediction, plus the ledger's share of the
            # telemetry on/off overhead (the accounting sites gate on
            # telemetry.enabled(), so tel_pct measures them too — the
            # ≤5 % acceptance rides the same A/B).
            "memory": {
                "ledger_peak_bytes": mem_measured,
                "planner_predicted_bytes": mem_predicted,
                "prediction_error_pct": mem_err_pct,
                "prediction_ok": mem_err_pct is not None
                and mem_err_pct <= 15.0,
                "ledger_overhead_pct": tel_pct,
                "ledger_overhead_ok": tel_pct is not None
                and tel_pct <= 5.0,
            },
            "tensors": tensors,
            "elems": elems,
            "replicas": n,
            "telemetry": {
                "megakernel_us_metrics_on": round(mega_lat * 1e6, 1),
                "megakernel_us_metrics_off": round(
                    mega_lat_notel * 1e6, 1),
                "overhead_pct": tel_pct,
                "overhead_ok": tel_pct is not None and tel_pct <= 5.0,
                "counters": tel_counters,
            },
            "trace": {
                "megakernel_us_trace_on": round(mega_lat * 1e6, 1),
                "megakernel_us_trace_off": round(
                    mega_lat_notrace * 1e6, 1),
                "overhead_pct": trace_pct,
                "overhead_ok": trace_pct is not None
                and trace_pct <= 5.0,
            },
        }
    finally:
        hvd.shutdown()


def _input_bench(steps: int = 40, batch: int = 64, dim: int = 512,
                 delay_ms: float = 0.0) -> dict:
    """Input-pipeline microbench (``--mode input``): steps/sec with a
    synthetic SLOW host loader, host-overlap off vs on.

    The off leg is the classic synchronous loop — per-step
    ``shard_batch(next(loader))`` plus a per-step ``float(loss)`` fetch
    (the accidental-synchronization pattern PR 5's audit removes); the
    on leg is the hvd-pipeline steady state — ``prefetch_to_device``
    double buffering plus deferred fetches with one ``barrier_fence()``
    at the end.  The loader's delay is auto-calibrated to the measured
    step time (the worst case for a non-overlapped loop: host work ≈
    device work, so overlap is worth ~2x), unless ``delay_ms`` pins it.
    Both legs consume the identical deterministic batch sequence from
    the same initial params; the final parameters must be BITWISE
    identical — prefetch and async dispatch reorder host work, never
    arithmetic.  CPU-only like ``--mode control``: no XLA collectives
    beyond the 8-virtual-device mesh, no chip.
    """
    import jax
    import jax.numpy as jnp
    import numpy as np
    import optax

    import horovod_tpu as hvd
    from horovod_tpu.parallel.input import prefetch_to_device
    from horovod_tpu.parallel.training import (barrier_fence,
                                               make_train_step, shard_batch)

    hvd.init(devices=jax.devices())
    try:
        n = hvd.size()
        gbatch = batch * n

        def loss_fn(params, b):
            x, y = b
            h = jnp.tanh(x @ params["w1"])
            return jnp.mean((h @ params["w2"] - y) ** 2)

        rng = np.random.default_rng(11)
        params0 = {
            "w1": jnp.asarray(rng.normal(0, 0.05, (dim, dim)), jnp.float32),
            "w2": jnp.asarray(rng.normal(0, 0.05, (dim, 1)), jnp.float32),
        }
        opt = optax.sgd(0.01)
        step = make_train_step(loss_fn, opt, donate=False)

        # Precomputed deterministic batches: the loader's cost is then
        # EXACTLY the synthetic delay (decode/augment stand-in), not
        # delay + RNG jitter — which would blur the calibration below.
        data = []
        for i in range(steps):
            r = np.random.default_rng(1000 + i)
            data.append((r.normal(size=(gbatch, dim)).astype(np.float32),
                         r.normal(size=(gbatch, 1)).astype(np.float32)))

        def host_batches(delay_s: float):
            for b in data:
                if delay_s:
                    time.sleep(delay_s)
                yield b

        # Warmup/compile, then calibrate the synchronous per-step cost
        # (shard + step + fetch) over a steady-state window.  The loader
        # delay is pinned to it: host work ≈ device work is the worst
        # case for a non-overlapped loop and the honest one for the
        # overlap claim (a much slower loader would be loader-bound
        # either way; a much faster one hides in async dispatch alone).
        params, opt_state = params0, opt.init(params0)
        for _ in range(3):
            params, opt_state, loss = step(params, opt_state,
                                           shard_batch(data[0]))
            float(loss)
        samples = []
        for i in range(11):
            t0 = time.perf_counter()
            params, opt_state, loss = step(params, opt_state,
                                           shard_batch(data[i % steps]))
            float(loss)
            samples.append(time.perf_counter() - t0)
        # Median, not mean: one background spike during calibration
        # would skew the loader delay.  The delay is pinned slightly
        # ABOVE the step time (1.4x): the overlapped leg then stays
        # producer-bound — its sleep absorbs host/XLA core contention —
        # while the synchronous leg still pays delay + step serially.
        # (Below ~1x the on-leg goes consumer-bound and, on a small-core
        # box, stager/step contention eats the win; far above it the
        # ratio (delay+step)/(delay+transfer) decays toward 1.)
        samples.sort()
        step_s = samples[len(samples) // 2]
        # Cap high enough that 1.4x holds up to ~180 ms steps (a badly
        # loaded CI box); a lower cap would silently break the
        # delay > step invariant and fail the 1.3x gate with no defect.
        delay_s = (delay_ms / 1e3) if delay_ms else min(
            max(1.4 * step_s, 0.002), 0.25)

        def run_off():
            params, opt_state = params0, opt.init(params0)
            t0 = time.perf_counter()
            for b in host_batches(delay_s):
                params, opt_state, loss = step(params, opt_state,
                                               shard_batch(b))
                float(loss)  # the per-step sync under audit
            return params, time.perf_counter() - t0

        def run_on():
            params, opt_state = params0, opt.init(params0)
            t0 = time.perf_counter()
            with prefetch_to_device(host_batches(delay_s),
                                    depth=2) as staged:
                for b in staged:
                    params, opt_state, loss = step(params, opt_state, b)
            barrier_fence(params, loss)
            return params, time.perf_counter() - t0

        # on first, off second: if background load creeps up over the
        # run it penalizes the leg under test, not the baseline.
        params_on, dt_on = run_on()
        params_off, dt_off = run_off()
        identical = all(
            np.asarray(a).tobytes() == np.asarray(b).tobytes()
            for a, b in zip(jax.tree_util.tree_leaves(params_on),
                            jax.tree_util.tree_leaves(params_off)))

        snap = hvd.metrics()
        stall = snap.get("host.stall_seconds", {})
        on_rate = steps / dt_on
        off_rate = steps / dt_off
        return {
            "metric": "input_pipeline_steps_per_sec",
            "value": round(on_rate, 1),
            "unit": "steps/sec",
            "prefetch_on": round(on_rate, 1),
            "prefetch_off": round(off_rate, 1),
            "speedup": round(on_rate / off_rate, 2) if off_rate else None,
            "vs_baseline": round(on_rate / off_rate, 2) if off_rate
            else None,
            "params_identical": identical,
            "loader_delay_ms": round(delay_s * 1e3, 2),
            "calibrated_step_ms": round(step_s * 1e3, 2),
            "steps": steps,
            "replicas": n,
            "telemetry": {
                "host_stall_seconds_sum": round(stall.get("sum", 0.0), 4),
                "host_stall_events": stall.get("count", 0),
                "batches_staged": snap.get(
                    "input.batches_staged", {}).get("value"),
            },
        }
    finally:
        hvd.shutdown()


def _overlap_mp_leg(timeout: float = 300.0) -> dict:
    """The np=2 multi-process overlap leg: launch tests/mp_worker.py
    scenario_overlap under the real launcher — the overlapped mp step
    must be bitwise-identical to the monolithic mp step, replay its
    partial cycles from the response cache on the steady state, and
    recover bitwise through a mid-partial-cycle transport reset.
    Classified ``ok`` (all markers), ``skipped`` (worker not shipped /
    quick shape) or ``failed`` (the CI gate fails on it)."""
    import subprocess

    worker = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                          "tests", "mp_worker.py")
    if not os.path.exists(worker):
        return {"status": "skipped", "detail": "tests/mp_worker.py "
                                               "not shipped"}
    env = dict(os.environ)
    # One CPU device per process: strip the 8-virtual-device override
    # the bench parent set for its own mesh.
    env["XLA_FLAGS"] = " ".join(
        f for f in env.get("XLA_FLAGS", "").split()
        if not f.startswith("--xla_force_host_platform_device_count"))
    env["JAX_PLATFORMS"] = "cpu"
    try:
        proc = subprocess.run(
            [sys.executable, "-m", "horovod_tpu.run", "-np", "2",
             "--platform", "cpu", worker, "overlap"],
            env=env, capture_output=True, timeout=timeout,
            cwd=os.path.dirname(os.path.abspath(__file__)))
    except subprocess.TimeoutExpired:
        return {"status": "failed",
                "detail": f"timed out after {timeout:.0f}s"}
    out = proc.stdout.decode(errors="replace") \
        + proc.stderr.decode(errors="replace")
    markers = [f"OVERLAP_{leg}_OK rank={r}"
               for leg in ("SEG", "PLAIN") for r in (0, 1)] \
        + [f"OVERLAP_OK rank={r}" for r in (0, 1)]
    if proc.returncode == 0 and all(m in out for m in markers):
        return {"status": "ok", "bitwise_identical": True,
                "steady_state_cache_replay": True}
    return {"status": "failed", "rc": proc.returncode,
            "detail": out[-1500:]}


def _overlap_bench(steps: int = 12, warmup: int = 3, batch_per: int = 8,
                   seq: int = 64) -> dict:
    """Backward/communication-overlap microbench (``--mode overlap``):
    steps/sec on a compute-heavy transformer-LM chain, monolithic vs
    bucketed-backward, plus the bitwise param-identity gates.

    Legs, all over one transformer-LM chain, one batch, one initial
    state:

    * ``monolithic`` — the pre-overlap static step (HVD_TPU_OVERLAP=off):
      ONE compiled program, in-program bucketed psum.
    * ``serialized`` — the same bucketed sub-programs with hard fences:
      reduction strictly after backward (the "reduction serialized
      after backward" symptom of docs/performance.md — what a
      non-overlapped dynamic path would do).
    * ``overlapped`` — streaming dispatch: each backward segment's
      buckets hand their megakernel to the device while earlier
      segments are still executing.

    ``speedup`` is overlapped/serialized — the scheduling win at equal
    device work (the honest overlap measure); the timed legs run as
    ALTERNATING blocks and report the per-leg median so background load
    hits both legs symmetrically.  ``vs_monolithic`` rides along for
    context (on a CPU mesh the single-program static step may win it).
    On the CPU mesh there is no comm/compute concurrency to exploit —
    the 8 virtual devices and the host share one thread pool, which is
    exactly why ``HVD_TPU_OVERLAP=auto`` resolves to ``off`` there — so
    the CI floor asserts the streamed schedule costs at most a
    scheduling-noise margin over the serialized one (parity on a quiet
    box; same contract as the dataplane bench's int8 throughput floor),
    not a CPU win.

    Identity gates:

    * ``bitwise_identical`` — the single-backward streaming schedule's
      params (same model, plain-callable loss) ≡ the serialized
      dispatch of the same sub-programs, bitwise; ``serial_identical``
      is the same gate for the segmented schedule (structural: same
      programs, different interleaving).  Against the monolithic step
      both are checked ``plain_close`` / ``segmented_close`` (allclose,
      rtol 1e-4 / atol 1e-5): the gradients are the same bits, but the
      apply (and each per-stage backward) is its own XLA program, which
      XLA:CPU compiles a ULP apart from the same jaxpr inside one big
      program, and Adam's per-coordinate normalization can amplify
      that on a near-zero-gradient coordinate to ~1e-6 after a few
      steps — the identity contract of parallel/overlap.py.
      ``segmented_bitwise`` rides along as information.
    * ``int8`` — under HVD_TPU_COMPRESSION=int8 the monolithic static
      path does not quantize at all, so the comparator is the
      serialized schedule: same bucket partition ⇒ same pow2-scale
      blocks, same stochastic-rounding ticks, same per-bucket
      error-feedback residual keys ⇒ bitwise-identical params.

    CPU-only like ``--mode control``: 8-virtual-device mesh, no chip.
    ``HVD_TPU_BENCH_OVERLAP_QUICK=1`` (the tier-1 test) shrinks the
    chain and the timed blocks — compile time dominates the full-size
    run; the CI `overlap-bench` job owns the full-size gates.
    """
    import jax
    import jax.numpy as jnp
    import numpy as np
    import optax

    import horovod_tpu as hvd
    from horovod_tpu.models.transformer import (
        TransformerConfig, chained_lm_loss, chained_lm_params,
        init_transformer, synthetic_lm_batch)
    from horovod_tpu.parallel.training import (barrier_fence,
                                               make_train_step, shard_batch)

    quick = os.environ.get("HVD_TPU_BENCH_OVERLAP_QUICK") == "1"
    layers, blocks = (2, 1) if quick else (4, 3)
    if quick:
        steps, seq = 6, 32
    hvd.init(devices=jax.devices())
    try:
        n = hvd.size()
        cfg = TransformerConfig(vocab_size=128, d_model=64, n_heads=4,
                                n_layers=layers, d_ff=256,
                                max_seq_len=seq)
        chain = chained_lm_loss(cfg)

        def plain_loss(p, b):  # not a ChainedLoss ⇒ unsegmented schedule
            return chain(p, b)

        key = jax.random.PRNGKey(0)
        params0 = chained_lm_params(init_transformer(key, cfg), cfg)
        tokens, targets = synthetic_lm_batch(jax.random.PRNGKey(1),
                                             batch_per * n, seq,
                                             cfg.vocab_size)
        batch = shard_batch((jnp.asarray(tokens), jnp.asarray(targets)))
        opt = optax.adam(1e-3)
        # Threshold sized so each decoder layer splits into several
        # dispatch buckets — the granularity the overlap streams at.
        threshold = 16 * 1024

        def build(mode, loss=chain):
            return make_train_step(loss, opt, donate=False,
                                   fusion_threshold=threshold,
                                   overlap=mode)

        def run(step, n_steps, wu=warmup):
            p, s = params0, opt.init(params0)
            for _ in range(wu):
                p, s, loss = step(p, s, batch)
            barrier_fence(p, loss)
            t0 = time.perf_counter()
            for _ in range(n_steps):
                p, s, loss = step(p, s, batch)
            barrier_fence(p, loss)
            return p, time.perf_counter() - t0

        def identical(a, b):
            return all(
                np.asarray(x).tobytes() == np.asarray(y).tobytes()
                for x, y in zip(jax.tree_util.tree_leaves(a),
                                jax.tree_util.tree_leaves(b)))

        # Identity legs first (short, untimed).
        step_on = build("on")
        step_serial = build("serial")
        step_off = build("off")
        params_on, _ = run(step_on, 2, wu=2)
        params_serial, _ = run(step_serial, 2, wu=2)
        params_off, _ = run(step_off, 2, wu=2)
        params_u_on, _ = run(build("on", plain_loss), 2, wu=2)
        params_u_serial, _ = run(build("serial", plain_loss), 2, wu=2)
        params_u_off, _ = run(build("off", plain_loss), 2, wu=2)

        def close(a, b):
            return all(np.allclose(np.asarray(x), np.asarray(y),
                                   rtol=1e-4, atol=1e-5)
                       for x, y in zip(jax.tree_util.tree_leaves(a),
                                       jax.tree_util.tree_leaves(b)))

        bitwise = identical(params_u_on, params_u_serial)
        plain_close = close(params_u_on, params_u_off)
        serial_eq = identical(params_on, params_serial)
        seg_bitwise = identical(params_on, params_off)
        seg_close = close(params_on, params_off)

        # Timed legs: alternating blocks, median per leg (background
        # load hits both symmetrically — same policy as the dataplane
        # bench's paired cycles).
        rates = {"on": [], "serial": [], "off": []}
        for _ in range(blocks):
            for mode, step in (("on", step_on), ("serial", step_serial),
                               ("off", step_off)):
                _, dt = run(step, steps, wu=1)
                rates[mode].append(steps / dt)

        def median(xs):
            xs = sorted(xs)
            return xs[len(xs) // 2]

        on_rate = median(rates["on"])
        serial_rate = median(rates["serial"])
        off_rate = median(rates["off"])

        # Quantized leg: per-bucket EF residuals must survive the
        # refactor — overlapped ≡ serialized bitwise under int8.
        hvd.set_compression(default="int8")
        try:
            p8_on, dt8_on = run(build("on"), 4, wu=2)
            p8_serial, _ = run(build("serial"), 4, wu=2)
            int8 = {
                "bitwise_identical": identical(p8_on, p8_serial),
                "quantized_active": not identical(p8_on, params_on),
                "overlapped_steps_per_sec": round(4 / dt8_on, 2),
            }
        finally:
            hvd.set_compression(default="none")

        # np=2 multi-process leg (mp streaming ≡ mp serial, bitwise).
        # Skipped in the quick shape — CI owns the real run.
        mp_leg = ({"status": "skipped", "detail": "quick shape"}
                  if quick else _overlap_mp_leg())

        snap = hvd.metrics()
        exposed = snap.get("overlap.exposed_comm_seconds", {})
        return {
            "metric": "overlap_steps_per_sec",
            "value": round(on_rate, 2),
            "unit": "steps/sec",
            "overlapped": round(on_rate, 2),
            "serialized": round(serial_rate, 2),
            "monolithic": round(off_rate, 2),
            "speedup": round(on_rate / serial_rate, 2) if serial_rate
            else None,
            "vs_monolithic": round(on_rate / off_rate, 2) if off_rate
            else None,
            "vs_baseline": round(on_rate / serial_rate, 2) if serial_rate
            else None,
            "bitwise_identical": bitwise,
            "plain_close": plain_close,
            "serial_identical": serial_eq,
            "segmented_bitwise": seg_bitwise,
            "segmented_close": seg_close,
            "int8": int8,
            "mp": mp_leg,
            "buckets": step_on.bucket_count,
            "segments": step_on.segment_count,
            "steps": steps,
            "replicas": n,
            "telemetry": {
                "buckets_dispatched": snap.get(
                    "overlap.buckets_dispatched", {}).get("value"),
                "exposed_comm_seconds_sum": round(
                    exposed.get("sum", 0.0), 4),
                "fallbacks": snap.get(
                    "overlap.fallbacks", {}).get("value", 0),
            },
        }
    finally:
        hvd.shutdown()


def _pipeline_bench(steps: int = 8, warmup: int = 2) -> dict:
    """Pipeline-schedule microbench (``--mode pipeline``): the
    host-scheduled MPMD pipeline train step (parallel/pipeline.py),
    1F1B with streamed partial-cycle gradient reduction vs the
    GPipe-ordered dispatch of the SAME per-stage executables with the
    reduction serialized after a flush fence — equal device work, only
    the interleaving and the reduction dispatch points differ.

    Reported per leg: steps/sec and **exposed-bubble seconds** per
    step (``pipeline.bubble_seconds`` — host time waiting on gradient
    reductions after the last schedule tick; the 1F1B leg streams each
    stage's buckets the moment its last backward dispatches, so its
    reductions ride inside the schedule while the GPipe leg pays the
    whole reduction after the flush).  The headline gate is
    ``bubble_hidden``: 1F1B's exposed-bubble seconds strictly below
    the GPipe leg's.  ``speedup`` (1f1b/gpipe steps/sec) rides with
    the same CPU-floor caveat as ``--mode overlap`` — on the shared
    thread pool the legs tie; the wall-clock win needs a real
    accelerator mesh.

    Identity gates: ``bitwise_identical`` (1F1B params+loss ≡ the
    GPipe-ordered leg after several adam steps — same microbatch
    accumulation order by construction) and ``reference_close`` (one
    SGD step ≡ ``p0 - lr·grad`` of the monolithic microbatch-mean
    loss, allclose).  The schedule SHAPE facts (scheduled bubble
    fraction, peak in-flight activations per schedule) come from the
    dryrun plan — no hardware in that part.

    CPU-only like ``--mode control``: 8-virtual-device mesh, no chip.
    ``HVD_TPU_BENCH_PIPELINE_QUICK=1`` (the tier-1 test) shrinks the
    chain and the timed blocks.
    """
    import jax
    import jax.numpy as jnp
    import numpy as np
    import optax

    import horovod_tpu as hvd
    from horovod_tpu.parallel.training import barrier_fence, shard_batch

    quick = os.environ.get("HVD_TPU_BENCH_PIPELINE_QUICK") == "1"
    S, m, d, blocks = (3, 4, 48, 1) if quick else (4, 8, 96, 3)
    if quick:
        steps = 4
    hvd.init(devices=jax.devices())
    try:
        n = hvd.size()

        def stage_first(p, carry, b):
            x, _y = b
            return jnp.tanh(x @ p["w"] + p["b"])

        def stage_mid(p, carry, b):
            return jnp.tanh(carry @ p["w"] + p["b"])

        def stage_last(p, carry, b):
            _x, y = b
            pred = carry @ p["w"] + p["b"]
            return jnp.mean((pred - y) ** 2)

        chain = hvd.ChainedLoss([stage_first]
                                + [stage_mid] * (S - 2) + [stage_last])
        ks = jax.random.split(jax.random.PRNGKey(0), S)
        params0 = [{"w": jax.random.normal(k, (d, d)) * d ** -0.5,
                    "b": jnp.zeros((d,))} for k in ks]
        B = n * m * 4
        x = jax.random.normal(jax.random.PRNGKey(1), (B, d))
        y = jax.random.normal(jax.random.PRNGKey(2), (B, d))
        batch = shard_batch((x, y))
        opt = optax.adam(1e-3)

        def build(schedule):
            return hvd.make_pipeline_train_step(
                chain, opt, num_microbatches=m, schedule=schedule,
                fusion_threshold=d * d * 4)

        def run(step, n_steps, wu=warmup):
            p, s = params0, opt.init(params0)
            for _ in range(wu):
                p, s, loss = step(p, s, batch)
            barrier_fence(p, loss)
            t0 = time.perf_counter()
            for _ in range(n_steps):
                p, s, loss = step(p, s, batch)
            barrier_fence(p, loss)
            return p, float(loss), time.perf_counter() - t0

        def identical(a, b):
            return all(
                np.asarray(u).tobytes() == np.asarray(v).tobytes()
                for u, v in zip(jax.tree_util.tree_leaves(a),
                                jax.tree_util.tree_leaves(b)))

        step_f = build("1f1b")
        step_g = build("gpipe")

        # Identity legs (short, untimed).
        p_f, l_f, _ = run(step_f, 2, wu=1)
        p_g, l_g, _ = run(step_g, 2, wu=1)
        bitwise = identical(p_f, p_g) and l_f == l_g

        # Reference leg: one SGD step vs the monolithic mean-loss grad.
        sgd = optax.sgd(0.1)
        step_ref = hvd.make_pipeline_train_step(
            chain, sgd, num_microbatches=m, schedule="1f1b",
            fusion_threshold=d * d * 4)
        p1, _, _l1 = step_ref(params0, sgd.init(params0), batch)

        def mb_of(arr, i):
            lb = B // n
            return jnp.concatenate(
                [arr[r * lb:(r + 1) * lb].reshape(
                    m, lb // m, d)[i] for r in range(n)], 0)

        def ref_loss(p):
            tot = 0.0
            for i in range(m):
                tot = tot + chain(p, (mb_of(x, i), mb_of(y, i)))
            return tot / m

        g_ref = jax.grad(ref_loss)(params0)
        reference_close = all(
            np.allclose(np.asarray(a),
                        np.asarray(p0) - 0.1 * np.asarray(g),
                        rtol=2e-5, atol=2e-6)
            for a, p0, g in zip(jax.tree_util.tree_leaves(p1),
                                jax.tree_util.tree_leaves(params0),
                                jax.tree_util.tree_leaves(g_ref)))

        # Timed legs: alternating blocks, per-leg median steps/sec AND
        # per-leg exposed-bubble seconds (the telemetry histogram's sum
        # delta — reduction time NOT hidden inside the schedule).
        def bubble_sum():
            return hvd.metrics().get(
                "pipeline.bubble_seconds", {}).get("sum", 0.0)

        rates = {"1f1b": [], "gpipe": []}
        exposed = {"1f1b": [], "gpipe": []}
        for _ in range(blocks):
            for mode, step in (("1f1b", step_f), ("gpipe", step_g)):
                b0 = bubble_sum()
                _, _, dt = run(step, steps, wu=1)
                # wu step's bubble rides the delta too: normalize per
                # step over everything the block ran.
                exposed[mode].append((bubble_sum() - b0) / (steps + 1))
                rates[mode].append(steps / dt)

        def median(xs):
            xs = sorted(xs)
            return xs[len(xs) // 2]

        f_rate, g_rate = median(rates["1f1b"]), median(rates["gpipe"])
        f_exp, g_exp = median(exposed["1f1b"]), median(exposed["gpipe"])

        # hvd-mem: per-schedule measured activation peak (the ledger's
        # pipeline.activations category) vs the planner's prediction
        # (schedule_plan peak carries x carry bytes) — bytes, not
        # tensor counts — plus a telemetry-on/off steps/sec A/B (the
        # ledger accounting rides telemetry.enabled()).
        from horovod_tpu import telemetry as _telemetry
        from horovod_tpu.memory import ledger as _mem_ledger
        from horovod_tpu.memory import planner as _mem_planner

        led = _mem_ledger.ledger
        memory_section = {}
        for mode, stepx in (("1f1b", step_f), ("gpipe", step_g)):
            led.reset()
            stepx(params0, opt.init(params0), batch)
            measured = led.peak_by_category().get(
                "pipeline.activations", 0)
            predicted = _mem_planner.pipeline_activation_bytes(
                S, m, microbatch_rows=B // m, width=d, schedule=mode)
            err = (round(abs(predicted - measured) / measured * 100.0,
                         2) if measured else None)
            memory_section[mode] = {
                "ledger_peak_bytes": measured,
                "planner_predicted_bytes": predicted,
                "prediction_error_pct": err,
                "prediction_ok": err is not None and err <= 15.0,
            }
        led.reset()
        was_enabled = _telemetry.enabled()
        _telemetry.set_enabled(False)
        try:
            _, _, dt_off = run(step_f, max(2, steps // 2), wu=1)
        finally:
            _telemetry.set_enabled(was_enabled)
        _, _, dt_on = run(step_f, max(2, steps // 2), wu=1)
        mem_overhead = (round((dt_on / dt_off - 1.0) * 100.0, 2)
                        if dt_off else None)
        memory_section["ledger_overhead_pct"] = mem_overhead
        memory_section["ledger_overhead_ok"] = (
            mem_overhead is not None and mem_overhead <= 5.0)

        plan_f, plan_g = step_f.plan, step_g.plan
        snap = hvd.metrics()
        return {
            "metric": "pipeline_steps_per_sec",
            "value": round(f_rate, 2),
            "unit": "steps/sec",
            "schedule_1f1b": round(f_rate, 2),
            "schedule_gpipe": round(g_rate, 2),
            "speedup": round(f_rate / g_rate, 2) if g_rate else None,
            "vs_baseline": round(f_rate / g_rate, 2) if g_rate else None,
            "bitwise_identical": bitwise,
            "reference_close": reference_close,
            "exposed_bubble_seconds_per_step": {
                "1f1b": round(f_exp, 5), "gpipe": round(g_exp, 5)},
            "bubble_hidden": f_exp < g_exp,
            "plan": {
                "n_stages": S, "microbatches": m,
                "ticks_1f1b": plan_f.total_ticks,
                "bubble_fraction_1f1b": round(plan_f.bubble_fraction, 3),
                "bubble_fraction_gpipe": round(plan_g.bubble_fraction, 3),
                "peak_activations_1f1b": plan_f.peak_activations,
                "peak_activations_gpipe": plan_g.peak_activations,
            },
            "buckets": step_f.bucket_count,
            "steps": steps,
            "replicas": n,
            "memory": memory_section,
            "telemetry": {
                "microbatches": snap.get(
                    "pipeline.microbatches", {}).get("value"),
                "bubble_seconds_sum": round(snap.get(
                    "pipeline.bubble_seconds", {}).get("sum", 0.0), 4),
                "inflight_activations": snap.get(
                    "pipeline.inflight_activations", {}).get("value"),
                "inflight_activation_bytes": snap.get(
                    "pipeline.inflight_activation_bytes",
                    {}).get("value"),
            },
        }
    finally:
        hvd.shutdown()


def _memory_bench(tensors: int = 16, elems: int = 256,
                  cycles: int = 20) -> dict:
    """hvd-mem microbench (``--mode memory``): the planner-vs-ledger
    accuracy contract plus plan determinism and the seeded-OOM
    forensics path, CPU-only like ``--mode control``.

    Four gates ride the JSON (CI job ``memory``, ``--check-memory-plan``):

    * ``plan_deterministic`` — identical configs produce byte-identical
      plan JSON (CLI determinism);
    * ``dataplane.prediction_error_pct`` — the static framework-bytes
      prediction lands within the bound of the measured ledger
      high-watermark for a steady-state fused allreduce cycle;
    * ``pipeline.prediction_error_pct`` — same contract for the MPMD
      schedule's activation carries;
    * ``oom_dump.ok`` — a simulated small capacity
      (``HVD_TPU_MEM_CAPACITY``) produces a flight dump naming the
      failing executable and the top ledger categories.
    """
    import glob as _glob
    import tempfile

    os.environ["HVD_TPU_COMPRESSION"] = "none"
    import jax
    import jax.numpy as jnp
    import numpy as np
    import optax

    import horovod_tpu as hvd
    from horovod_tpu.memory import ledger as _mem_ledger
    from horovod_tpu.memory import planner as _mem_planner
    from horovod_tpu.ops import megakernel as mk
    from horovod_tpu.telemetry import flight as _flight

    hvd.init(devices=jax.devices())
    try:
        n = hvd.size()
        led = _mem_ledger.ledger
        rng = np.random.default_rng(11)
        base = [rng.standard_normal((n, elems)).astype(np.float32)
                for _ in range(tensors)]
        inputs = [hvd.shard(t) for t in base]

        def cycle(tag):
            # quiesce: submissions land as ONE fused response (the
            # prediction below models the single fused launch).
            with hvd.quiesce():
                hs = [hvd.allreduce_async(x, average=True,
                                          name=f"{tag}.{j}")
                      for j, x in enumerate(inputs)]
            return [hvd.synchronize(h) for h in hs]

        cycle("warm")
        led.reset()
        cycle("acc")
        dp_measured = led.watermark()
        dp_predicted = _mem_planner.plan_dataplane(
            tensors, elems, n).framework_bytes
        dp_err = (round(abs(dp_predicted - dp_measured)
                        / dp_measured * 100.0, 2)
                  if dp_measured else None)

        # Pipeline accuracy: one step of a small MPMD chain.
        from horovod_tpu.parallel.training import shard_batch

        S, m, d = 3, 4, 32

        def stage_first(p, carry, b):
            x, _y = b
            return jnp.tanh(x @ p["w"])

        def stage_mid(p, carry, b):
            return jnp.tanh(carry @ p["w"])

        def stage_last(p, carry, b):
            _x, y = b
            return jnp.mean((carry @ p["w"] - y) ** 2)

        ks = jax.random.split(jax.random.PRNGKey(0), S)
        params = [{"w": jax.random.normal(k, (d, d)) * d ** -0.5}
                  for k in ks]
        B = n * m
        batch = shard_batch(
            (np.asarray(jax.random.normal(jax.random.PRNGKey(1),
                                          (B, d))),
             np.asarray(jax.random.normal(jax.random.PRNGKey(2),
                                          (B, d)))))
        opt = optax.sgd(0.1)
        step = hvd.make_pipeline_train_step(
            [stage_first] + [stage_mid] * (S - 2) + [stage_last], opt,
            num_microbatches=m, fusion_threshold=d * d * 4)
        led.reset()
        step(params, opt.init(params), batch)
        pl_measured = led.peak_by_category().get(
            "pipeline.activations", 0)
        pl_predicted = _mem_planner.pipeline_activation_bytes(
            S, m, microbatch_rows=B // m, width=d)
        pl_err = (round(abs(pl_predicted - pl_measured)
                        / pl_measured * 100.0, 2)
                  if pl_measured else None)
        led.reset()

        # Plan determinism (the CLI's byte-identity contract).
        det = all(
            _mem_planner.build_plan(name, **kw).to_json()
            == _mem_planner.build_plan(name, **kw).to_json()
            for name, kw in (
                ("dataplane", {"tensors": tensors, "elems": elems,
                               "world": n}),
                ("transformer_lm", {"batch_size": 64, "world": 8}),
                ("serving", {"n_layers": 2, "n_heads": 8,
                             "head_dim": 16, "max_slots": 8,
                             "pages_per_slot": 8, "page_size": 16}),
                ("pipeline", {"n_stages": 4, "num_microbatches": 8,
                              "microbatch_rows": 32, "width": 64,
                              "world": 8})))

        # Seeded OOM: simulated small capacity -> flight dump naming
        # the failing executable + top ledger categories.
        oom = {"ok": False, "executable": None, "top_categories": []}
        with tempfile.TemporaryDirectory() as td:
            with _flight.recorder._dump_lock:
                _flight.recorder._last_dump.clear()
            os.environ["HVD_TPU_FLIGHT_DIR"] = td
            os.environ["HVD_TPU_MEM_CAPACITY"] = "4096"
            led.set("serving.kv_pages", 3000)
            led.set("megakernel.residuals", 2000)
            led.set("input.prefetch", 1000)
            try:
                cycle("oomseed")  # guard raises, eager fallback runs
            finally:
                os.environ.pop("HVD_TPU_FLIGHT_DIR", None)
                os.environ.pop("HVD_TPU_MEM_CAPACITY", None)
                led.reset()
            dumps = _glob.glob(os.path.join(td, "*oom*"))
            if dumps:
                extra = json.load(open(dumps[0])).get("extra", {})
                oom = {
                    "ok": bool(extra.get("executable"))
                    and len(extra.get("top_categories", [])) >= 3,
                    "executable": extra.get("executable"),
                    "top_categories": [t["category"] for t in
                                       extra.get("top_categories",
                                                 [])],
                }

        # Ledger overhead A/B (informational here; the binding ≤5 %
        # gate rides --mode dataplane's telemetry section).
        from horovod_tpu import telemetry as _telemetry

        def timed():
            lats = []
            for _ in range(cycles):
                t0 = time.perf_counter()
                cycle("ovh")
                lats.append(time.perf_counter() - t0)
            lats.sort()
            return lats[len(lats) // 2]

        lat_on = timed()
        was_enabled = _telemetry.enabled()
        _telemetry.set_enabled(False)
        try:
            lat_off = timed()
        finally:
            _telemetry.set_enabled(was_enabled)
        ovh = (round((lat_on / lat_off - 1.0) * 100.0, 2)
               if lat_off else None)

        worst = max(e for e in (dp_err, pl_err) if e is not None) \
            if (dp_err is not None or pl_err is not None) else None
        return {
            "metric": "memory_plan_prediction_error_pct",
            "value": worst,
            "unit": "%",
            "vs_baseline": None,
            "dataplane": {"ledger_peak_bytes": dp_measured,
                          "planner_predicted_bytes": dp_predicted,
                          "prediction_error_pct": dp_err},
            "pipeline": {"ledger_peak_bytes": pl_measured,
                         "planner_predicted_bytes": pl_predicted,
                         "prediction_error_pct": pl_err},
            "plan_deterministic": det,
            "oom_dump": oom,
            "ledger_overhead_pct": ovh,
            "tensors": tensors,
            "elems": elems,
            "replicas": n,
        }
    finally:
        hvd.shutdown()


def _fused_bench(rows: int = 1024, k: int = 512, n_feat: int = 512,
                 cycles: int = 7) -> dict:
    """hvd-fuse microbench (``--mode fused``): the fused
    computation-collective contracts, CPU-only like ``--mode control``
    (8-virtual-device mesh, no chip — XLA:CPU's thunk runtime
    genuinely overlaps a chunk's psum with the next chunk's GEMM, so
    the exposed-communication contract measures for real here).

    Four gates ride the JSON (CI job ``fused-bench``, ``--check-speedup``):

    * ``bitwise.*`` — every fused program (tensor-parallel psum closer,
      MoE dispatch→FFN→combine round trip) reproduces its unfused
      reference program's bytes exactly;
    * ``dispatches_per_fused_group`` — one fused group is ONE XLA
      executable launch, counted at jax's dispatch choke point
      (utils/xla_dispatch.py), on both legs;
    * ``exposed_comm.strictly_below`` — the fused leg's exposed
      communication seconds (``max(0, total - compute_only)``, the
      ``fused.exposed_comm_seconds`` figure) land strictly below the
      unfused leg's — i.e. chunking actually hid the collective;
    * ``bitwise.fallback_off_parity`` — ``HVD_TPU_FUSE=off`` pins the
      unfused reference program bytes.
    """
    os.environ["HVD_TPU_COUNT_DISPATCHES"] = "1"
    import jax
    import jax.numpy as jnp
    import numpy as np
    from jax.sharding import PartitionSpec as P

    from horovod_tpu.core.topology import (EXPERT_AXIS, MODEL_AXIS,
                                           make_mesh)
    from horovod_tpu.memory import planner as _mem_planner
    from horovod_tpu.ops import fused as F
    from horovod_tpu.parallel.expert import (MoEOutput, init_moe_params,
                                             local_experts, moe_layer)
    from horovod_tpu.utils import xla_dispatch

    n = 8
    mesh = make_mesh(model=n)
    chunks = F.fuse_chunks()
    rng = np.random.default_rng(17)
    x = jnp.asarray(rng.standard_normal((rows, k)).astype(np.float32))
    w = jnp.asarray((rng.standard_normal((k, n_feat)) * 0.05)
                    .astype(np.float32))

    def build_tensor(fuse, with_comm=True):
        # The row-parallel closer body (parallel/tensor.row_parallel's
        # exact dot→psum ordering); with_comm=False elides the
        # collective legs — the compute_only baseline both exposed
        # measurements subtract.
        def body(x, w):
            def leg(xc):
                part = jnp.dot(xc, w,
                               preferred_element_type=jnp.float32)
                if with_comm:
                    part = jax.lax.psum(part, MODEL_AXIS)
                return part
            return F.chunked_map(leg, x, axis=0, chunks=chunks,
                                 fuse=fuse)
        return jax.jit(jax.shard_map(
            body, mesh=mesh, in_specs=(P(), P()), out_specs=P(),
            check_vma=False))

    fused_t = build_tensor(True)
    unfused_t = build_tensor(False)
    tensor_bitwise = bool(
        np.asarray(fused_t(x, w)).tobytes()
        == np.asarray(unfused_t(x, w)).tobytes())

    # Fallback parity: HVD_TPU_FUSE=off must pin the reference program
    # even when the call site passes no explicit override.
    prev = os.environ.get(F.FUSE_ENV)
    os.environ[F.FUSE_ENV] = "off"
    try:
        off_t = build_tensor(None)
        fallback_parity = bool(
            np.asarray(off_t(x, w)).tobytes()
            == np.asarray(unfused_t(x, w)).tobytes())
    finally:
        if prev is None:
            os.environ.pop(F.FUSE_ENV, None)
        else:
            os.environ[F.FUSE_ENV] = prev

    # One fused group == ONE XLA executable launch (warm).
    def count_dispatches(fn, *args):
        jax.block_until_ready(fn(*args))
        with xla_dispatch.exact_scope():
            with xla_dispatch.record(all_threads=True) as scope:
                jax.block_until_ready(fn(*args))
        return scope.count

    tensor_disp = count_dispatches(fused_t, x, w)

    # Exposed communication: both legs against their own compute_only
    # baseline, same clamp + median idiom (ops/fused.measure_exposed_
    # comm) — the unfused leg serializes GEMM→psum, the fused leg hides
    # chunk i's psum under chunk i+1's GEMM.
    exposed_unfused = F.measure_exposed_comm(
        unfused_t, build_tensor(False, with_comm=False), (x, w),
        cycles=cycles)
    exposed_fused = F.measure_exposed_comm(
        fused_t, build_tensor(True, with_comm=False), (x, w),
        cycles=cycles)
    strictly_below = bool(exposed_fused < exposed_unfused)

    # The flagship: the MoE dispatch→FFN→combine round trip, fused vs
    # unfused, bitwise, on its own expert mesh.
    E, D, H, tokens = 8, 16, 32, 256
    mesh_e = make_mesh(expert=n)
    key = jax.random.PRNGKey(5)
    kx, kp = jax.random.split(key)
    from jax.sharding import NamedSharding
    # Pre-place on the expert mesh: an uncommitted input would cost an
    # implicit reshard executable and double the counted dispatches.
    xe = jax.device_put(jax.random.normal(kx, (tokens, D)),
                        NamedSharding(mesh_e, P(EXPERT_AXIS)))
    params = jax.device_put(init_moe_params(kp, E, D, H),
                            NamedSharding(mesh_e, P()))

    def build_moe(fuse):
        def f(x, params):
            mine = local_experts(params, axis_name=EXPERT_AXIS)
            return moe_layer(x, mine, axis_name=EXPERT_AXIS,
                             num_experts=E, top_k=2,
                             capacity_factor=8.0, fuse=fuse,
                             fuse_chunks=chunks)
        return jax.jit(jax.shard_map(
            f, mesh=mesh_e, in_specs=(P(EXPERT_AXIS), P()),
            out_specs=MoEOutput(P(EXPERT_AXIS), P(), P()),
            check_vma=False))

    moe_f = build_moe(True)
    moe_u = build_moe(False)
    got_f, got_u = moe_f(xe, params), moe_u(xe, params)
    moe_bitwise = all(
        np.asarray(a).tobytes() == np.asarray(b).tobytes()
        for a, b in zip(got_f, got_u))
    moe_disp = count_dispatches(moe_f, xe, params)

    # Host-side services: dispatch the tensor group through
    # FusedProgram so the bench exercises the AOT-compile → manifest →
    # ledger-charge path and the run's JSON carries the counters.
    launch_bytes = _mem_planner.fused_group_bytes(
        (rows, n_feat), chunks, dtype="float32")
    prog = F.FusedProgram("bench/row_parallel", fused_t, mesh=mesh,
                          chunks=chunks, launch_bytes=launch_bytes)
    jax.block_until_ready(prog(x, w))
    wrapped_bitwise = bool(
        np.asarray(prog(x, w)).tobytes()
        == np.asarray(unfused_t(x, w)).tobytes())

    hidden_pct = (round((1.0 - exposed_fused / exposed_unfused) * 100.0,
                        1) if exposed_unfused else None)
    return {
        "metric": "fused_exposed_comm_us",
        "value": round(exposed_fused * 1e6, 1),
        "unit": "us/group",
        "vs_baseline": round(exposed_unfused * 1e6, 1),
        "exposed_comm": {
            "unfused_us": round(exposed_unfused * 1e6, 1),
            "fused_us": round(exposed_fused * 1e6, 1),
            "hidden_pct": hidden_pct,
            "strictly_below": strictly_below,
        },
        "bitwise": {
            "tensor_psum": tensor_bitwise,
            "expert_roundtrip": bool(moe_bitwise),
            "fused_program_wrapper": wrapped_bitwise,
            "fallback_off_parity": fallback_parity,
        },
        "dispatches_per_fused_group": {
            "tensor": tensor_disp,
            "expert": moe_disp,
        },
        "chunks": chunks,
        "rows": rows,
        "launch_bytes": launch_bytes,
        "telemetry": {
            "groups_compiled": F._M_GROUPS.value,
            "launches": F._M_LAUNCHES.value,
        },
        "replicas": n,
    }


def _serving_bench(n_requests: int = 40, max_slots: int = 8,
                   seed: int = 7) -> dict:
    """Serving microbench (``--mode serving``): tokens/sec through the
    hvd-serve engine, continuous batching vs static batching, on a
    seeded ragged-arrival trace.

    Both legs run the IDENTICAL engine, executables and trace; the only
    difference is the admission policy — continuous admits into any
    free slot every iteration (``engine.step(admit=True)``), static
    admits only at batch boundaries (all slots empty), the classic
    serve-a-batch-to-completion loop.  Raggedness (prompt 4–24 tokens,
    4–48 generated, staggered logical arrivals) is what continuous
    batching monetizes: static burns decode iterations on mostly-empty
    batches while the longest sequence finishes.

    Also asserted in-bench, because the schedulers may differ ONLY in
    wall time: every request's generated tokens are identical between
    the two legs (``results_identical`` — the batch-composition
    invariance the serving bitwise contract guarantees), and a greedy
    engine rollout equals the token-by-token argmax rollout of the
    jitted non-incremental ``serving_forward`` (``bitwise_identical``).
    CPU-only like ``--mode control``: no XLA collectives, no chip.
    ``HVD_TPU_BENCH_SERVING_QUICK=1`` (the tier-1 test)
    shrinks the traces — the deterministic gates hold at any trace
    size, and the CI `serving-bench` job owns the full-size
    throughput gates.
    """
    import jax
    import jax.numpy as jnp
    import numpy as np

    from horovod_tpu.models.transformer import (TransformerConfig,
                                                init_transformer,
                                                serving_forward)
    from horovod_tpu.serving import InferenceEngine

    quick = os.environ.get("HVD_TPU_BENCH_SERVING_QUICK") == "1"
    if quick:
        n_requests = 14

    # Sized so the decode dispatch dominates the per-iteration cost
    # (host-side sampling is constant per token and would otherwise
    # dilute the iteration-count advantage under measurement).
    cfg = TransformerConfig(vocab_size=256, d_model=128, n_heads=8,
                            n_layers=3, d_ff=256, max_seq_len=128)
    params = init_transformer(jax.random.PRNGKey(0), cfg)
    rng = np.random.default_rng(seed)
    trace = []
    arrival = 0
    for _ in range(n_requests):
        arrival += int(rng.integers(0, 2))
        # Heavy-tailed generation lengths — the real serving shape
        # (most completions short, a tail of long ones) and the case
        # static batching handles worst: one long sequence pins the
        # whole batch while its siblings' slots idle.
        if rng.random() < 0.25:
            max_new = int(rng.integers(48, 65))
        else:
            max_new = int(rng.integers(4, 13))
        trace.append({
            "prompt": [int(t) for t in
                       rng.integers(0, cfg.vocab_size,
                                    size=int(rng.integers(4, 17)))],
            "max_new": max_new,
            "arrival": arrival,
        })

    def run(continuous: bool):
        eng = InferenceEngine(params, cfg, max_slots=max_slots,
                              page_size=16, capacity=128)
        eng.warm_start()
        # Steady-state measurement: pre-build the trace's prefill
        # buckets (a live fleet has them from the manifest warm start;
        # cold XLA compiles would otherwise dominate both legs equally
        # and mask the scheduling difference under test).
        for t in trace:
            eng._prefill_exec(eng._bucket_for(len(t["prompt"])))
        reqs = [eng.submit(t["prompt"], max_new_tokens=t["max_new"],
                           arrival=t["arrival"]) for t in trace]
        it = 0
        t0 = time.perf_counter()
        while not eng.scheduler.idle():
            eng.step(now=it, admit=continuous
                     or eng.scheduler.occupancy() == 0)
            it += 1
        dt = time.perf_counter() - t0
        tokens = sum(len(r.generated) for r in reqs)
        ttft = sorted(r.t_first_token - r.t_submit for r in reqs)
        per_tok = sorted(
            (r.t_done - r.t_first_token) / (len(r.generated) - 1)
            for r in reqs if len(r.generated) > 1)

        def pct(xs, q):
            return round(xs[min(len(xs) - 1,
                                int(q * (len(xs) - 1)))] * 1e3, 3)

        return {
            "tokens_per_sec": round(tokens / dt, 1),
            "tokens": tokens,
            "iterations": it,
            "wall_seconds": round(dt, 3),
            "ttft_ms": {"p50": pct(ttft, 0.5), "p99": pct(ttft, 0.99)},
            "token_ms": {"p50": pct(per_tok, 0.5),
                         "p99": pct(per_tok, 0.99)},
        }, [list(r.generated) for r in reqs]

    cont, cont_out = run(continuous=True)
    stat, stat_out = run(continuous=False)
    results_identical = cont_out == stat_out

    prefix_section = _serving_prefix_bench(
        params, cfg, n_requests=10 if quick else 24, max_slots=max_slots)
    spec_section = _serving_spec_bench(
        n_requests=10 if quick else 24, max_slots=max_slots)

    # Bitwise contract: engine prefill+decode (cached executables) vs
    # the jitted non-incremental forward, as a greedy rollout.
    eng = InferenceEngine(params, cfg, max_slots=max_slots,
                          page_size=16, capacity=128)
    eng.warm_start()
    prompt = trace[0]["prompt"]
    got = eng.generate(list(prompt), max_new_tokens=8)
    sf = jax.jit(serving_forward, static_argnums=(2, 3))
    seq = list(prompt)
    ref = []
    for _ in range(8):
        logits = np.asarray(sf(params, jnp.asarray([seq], jnp.int32),
                               cfg, eng.capacity))
        tok = int(np.argmax(logits[0, -1]))
        ref.append(tok)
        seq.append(tok)
    bitwise = got == ref

    speedup = (round(cont["tokens_per_sec"] / stat["tokens_per_sec"], 2)
               if stat["tokens_per_sec"] else None)
    return {
        "metric": "serving_tokens_per_sec",
        "value": cont["tokens_per_sec"],
        "unit": "tokens/sec",
        "continuous": cont,
        "static": stat,
        "speedup": speedup,
        "vs_baseline": speedup,
        "results_identical": results_identical,
        "bitwise_identical": bitwise,
        "requests": n_requests,
        "slots": max_slots,
        "prefix_cache": prefix_section,
        "speculative": spec_section,
    }


def _serving_prefix_bench(params, cfg, n_requests: int = 24,
                          max_slots: int = 8, seed: int = 13) -> dict:
    """Shared-prefix page-cache leg of ``--mode serving``: a
    repeated-prefix trace (one 32-token system header + per-request
    suffixes — the RAG/few-shot shape the cache monetizes) replayed
    through the IDENTICAL engine with the prefix cache on vs off.
    Gates (CI, --check-spec-speedup): completions BITWISE-equal
    between the legs (cache hits are observably side-effect-free) and
    ``prefill_tokens_saved > 0`` (the header's pages map copy-free
    after the first admission); p50 TTFT per leg rides along — the
    saved prefill work is the TTFT win."""
    import numpy as np

    from horovod_tpu import telemetry as _telemetry
    from horovod_tpu.serving import InferenceEngine

    rng = np.random.default_rng(seed)
    header = [int(t) for t in rng.integers(0, cfg.vocab_size, size=32)]
    trace = []
    arrival = 0
    for _ in range(n_requests):
        arrival += int(rng.integers(0, 2))
        trace.append({
            "prompt": header + [int(t) for t in rng.integers(
                0, cfg.vocab_size, size=int(rng.integers(4, 13)))],
            "max_new": int(rng.integers(4, 13)),
            "arrival": arrival,
        })

    def counter(name):
        return _telemetry.metrics().get(name, {}).get("value", 0)

    def run(prefix: bool):
        eng = InferenceEngine(params, cfg, max_slots=max_slots,
                              page_size=16, capacity=128,
                              prefix_cache=prefix)
        eng.warm_start()
        for t in trace:  # steady state: pre-build the buckets
            eng._prefill_exec(eng._bucket_for(len(t["prompt"])))
            # ...including the suffix-only buckets hits compile to
            # (the 32-token header is page-aligned at page_size=16).
            eng._prefill_exec(eng._bucket_for(len(t["prompt"]) - 32))
        pages_before = counter("serving.prefix_pages_shared")
        reqs = [eng.submit(t["prompt"], max_new_tokens=t["max_new"],
                           arrival=t["arrival"]) for t in trace]
        it = 0
        t0 = time.perf_counter()
        while not eng.scheduler.idle():
            eng.step(now=it)
            it += 1
        dt = time.perf_counter() - t0
        pages = counter("serving.prefix_pages_shared") - pages_before
        ttft = sorted(r.t_first_token - r.t_submit for r in reqs)
        return {
            "tokens_per_sec": round(
                sum(len(r.generated) for r in reqs) / dt, 1),
            "wall_seconds": round(dt, 3),
            "ttft_p50_ms": round(ttft[len(ttft) // 2] * 1e3, 3),
            "prefill_tokens_saved": int(pages) * eng.cache.page_size,
            "prefix_stats": eng.cache.prefix_stats(),
        }, [list(r.generated) for r in reqs]

    on, on_out = run(prefix=True)
    off, off_out = run(prefix=False)
    return {
        "on": on,
        "off": off,
        "bitwise_identical": on_out == off_out,
        "prefill_tokens_saved": on["prefill_tokens_saved"],
        "ttft_p50_improved": on["ttft_p50_ms"] <= off["ttft_p50_ms"],
        "requests": n_requests,
        "header_tokens": 32,
    }


def _serving_spec_bench(n_requests: int = 24, max_slots: int = 8,
                        seed: int = 11, spec_tokens: int = 5) -> dict:
    """Speculative-decoding leg of ``--mode serving``: the same seeded
    heavy-tailed trace through the IDENTICAL target model with and
    without a draft.  The pair is constructed for EXACT greedy
    agreement (every layer's residual contribution is zeroed in both
    models and the embed/unembed halves are shared, so target and
    draft logits are bitwise-identical): acceptance is deterministically
    1.0 and the measured speedup is the *mechanism's* — what the
    dispatch structure buys at full acceptance, the honest upper bound
    a CPU microbench can state (a real distilled draft lands wherever
    its acceptance rate does; serving.spec_acceptance_rate reports it
    live).  Gates (CI): speculative >= 1.3x non-speculative tokens/sec,
    completions BITWISE-equal (the bitwise-greedy acceptance rule —
    holds at ANY acceptance rate), and the steady-state dispatch
    contract: one draft propose + ONE target verify executable call
    per decode iteration, zero eager dispatches."""
    import numpy as np

    from horovod_tpu.models.transformer import TransformerConfig
    from horovod_tpu.serving import InferenceEngine
    from horovod_tpu.serving.harness import (agreement_pair,
                                             count_spec_dispatches)

    # FFN-heavy target, thin draft (~8% of the target's per-token
    # compute): the economics speculative decoding monetizes — the
    # verify's per-token cost is ~C_decode/2 regardless of depth (width
    # scales with the block, amortization scales with it too), so the
    # draft's relative cost decides the ceiling.  Quick mode keeps the
    # deterministic gates (bitwise agreement, dispatch contract) on a
    # small target — the economics gate is CI-only, full-size.
    quick = os.environ.get("HVD_TPU_BENCH_SERVING_QUICK") == "1"
    cfg = TransformerConfig(vocab_size=256, d_model=128, n_heads=8,
                            n_layers=3 if quick else 8,
                            d_ff=256 if quick else 1024, max_seq_len=128)
    dcfg = TransformerConfig(vocab_size=256, d_model=128, n_heads=8,
                             n_layers=1, d_ff=64, max_seq_len=128)
    params, draft = agreement_pair(cfg, dcfg)

    rng = np.random.default_rng(seed)
    trace = []
    arrival = 0
    for _ in range(n_requests):
        arrival += int(rng.integers(0, 2))
        if rng.random() < 0.25:
            max_new = int(rng.integers(48, 65))
        else:
            max_new = int(rng.integers(4, 13))
        trace.append({
            "prompt": [int(t) for t in rng.integers(
                0, cfg.vocab_size, size=int(rng.integers(4, 17)))],
            "max_new": max_new,
            "arrival": arrival,
        })

    def run(speculative: bool):
        kw = {}
        if speculative:
            kw = {"draft": (draft, dcfg), "spec_tokens": spec_tokens}
        eng = InferenceEngine(params, cfg, max_slots=max_slots,
                              page_size=16, capacity=128, **kw)
        eng.warm_start()
        for t in trace:
            eng._prefill_exec(eng._bucket_for(len(t["prompt"])))
            if speculative:
                eng._prefill_exec(eng._bucket_for(len(t["prompt"])),
                                  draft=True)
        reqs = [eng.submit(t["prompt"], max_new_tokens=t["max_new"],
                           arrival=t["arrival"]) for t in trace]
        it = 0
        t0 = time.perf_counter()
        while not eng.scheduler.idle():
            eng.step(now=it)
            it += 1
        dt = time.perf_counter() - t0
        tokens = sum(len(r.generated) for r in reqs)
        return {
            "tokens_per_sec": round(tokens / dt, 1),
            "tokens": tokens,
            "iterations": it,
            "wall_seconds": round(dt, 3),
            "acceptance_rate": (round(eng.spec_acceptance_rate, 4)
                                if eng.spec_acceptance_rate is not None
                                else None),
        }, [list(r.generated) for r in reqs], eng

    # Best-of-2 per leg: the verdicts are deterministic (identical
    # completions every repeat — asserted), only the wall clock on a
    # shared box is not, and a transient load spike on either leg must
    # not flip the CI gate.  Quick mode runs each leg once — the
    # repeat is pure wall-clock insurance for the CI speedup gate.
    spec, spec_out, spec_eng = run(speculative=True)
    if quick:
        spec2, spec_out2, eng2 = spec, spec_out, spec_eng
    else:
        spec2, spec_out2, eng2 = run(speculative=True)
    if spec2["tokens_per_sec"] > spec["tokens_per_sec"]:
        spec, spec_eng = spec2, eng2
    base, base_out, _ = run(speculative=False)
    if quick:
        base2, base_out2 = base, base_out
    else:
        base2, base_out2, _ = run(speculative=False)
    if base2["tokens_per_sec"] > base["tokens_per_sec"]:
        base = base2
    repeats_identical = (spec_out == spec_out2
                         and base_out == base_out2)

    # Steady-state dispatch contract on the spec engine: one propose +
    # ONE verify executable call per decode iteration, nothing eager —
    # the same harness tests/test_speculative.py asserts through.
    for p in ([1, 2, 3], [4, 5, 6, 7]):
        spec_eng.submit(list(p), max_new_tokens=spec_tokens + 3)
    spec_eng.step()  # admissions + prefills
    proposes, verifies, eager = count_spec_dispatches(spec_eng)
    calls = {"verify": verifies, "propose": proposes}
    spec_eng.run_until_idle()

    speedup = (round(spec["tokens_per_sec"] / base["tokens_per_sec"], 2)
               if base["tokens_per_sec"] else None)
    return {
        "speculative": spec,
        "non_speculative": base,
        "speedup": speedup,
        "bitwise_greedy": spec_out == base_out and repeats_identical,
        "spec_tokens": spec_tokens,
        "verify_dispatches_per_iteration": calls["verify"],
        "propose_dispatches_per_iteration": calls["propose"],
        "eager_dispatches_per_iteration": eager,
        "requests": n_requests,
    }


def _tuning_bench(windows: int = 80) -> dict:
    """hvd-tune convergence leg of ``--mode tuning``: the REAL policy
    engine (tuning/policy.py, with the REAL hvd-mem pricing hook)
    closed over a deterministic fleet model, started deliberately
    mis-tuned — compression off on a simulated-DCN hierarchy, in-flight
    depth 1, oversized spec_tokens on a low-acceptance draft.

    The model is the paper's additive critical path: per-step
    milliseconds = compute + dcn(wire format) + dispatch-gap(in-flight
    depth) + speculative overhead(depth x miss rate).  Each decision
    window synthesizes the leg attribution the sensors would measure
    from that model and feeds it to the engine; an applied decision
    changes the model's knobs, which changes the NEXT window's legs —
    the closed loop, minus the hardware.  Gates (CI): converged
    steps/sec >= 1.5x mis-tuned AND within 10% of the hand-tuned
    reference, convergence within a bounded number of windows, and a
    bit-identical decision sequence on replay (the engine is free of
    wall clock and PRNG).  The separate actuation leg
    (tests/test_tuning.py) covers the marker path on the real
    runtime."""
    from horovod_tpu.memory.planner import retune_delta_bytes
    from horovod_tpu.tuning.policy import (PolicyEngine, WindowSnapshot)

    COMPUTE_MS = 10.0
    DCN_MS = {"none": 60.0, "bf16": 30.0, "int8": 14.0, "int4": 11.0}
    # Dispatch-gap vs in-flight depth: queueing-shaped — the gap
    # collapses once the window covers the dispatch latency.
    GAP_MS = {1: 40.0, 2: 24.0, 4: 14.0, 8: 2.0}
    ACCEPTANCE = 0.3
    SPEC_MS_PER_MISS = 0.9

    MIS_TUNED = {"dcn_compress": "none", "max_inflight": 1,
                 "fusion_threshold": 64 << 20, "cycle_time": 0.005,
                 "spec_tokens": 6}
    HAND_TUNED = {"dcn_compress": "int4", "max_inflight": 8,
                  "fusion_threshold": 64 << 20, "cycle_time": 0.005,
                  "spec_tokens": 1}

    def step_ms(k) -> float:
        return (COMPUTE_MS + DCN_MS[k["dcn_compress"]]
                + GAP_MS[k["max_inflight"]]
                + SPEC_MS_PER_MISS * k["spec_tokens"]
                * (1.0 - ACCEPTANCE))

    def legs_of(k) -> dict:
        # What trace/analyze.window_legs would attribute (busy µs).
        return {"dispatch": COMPUTE_MS * 1e3,
                "dcn": DCN_MS[k["dcn_compress"]] * 1e3,
                "dispatch-gap": GAP_MS[k["max_inflight"]] * 1e3,
                "host": 1e3}

    def run_loop():
        knobs = dict(MIS_TUNED)
        eng = PolicyEngine(price=lambda knob, old, new, s:
                           retune_delta_bytes(knob, old, new, s.knobs))
        decisions, trail = [], []
        for w in range(windows):
            snap = WindowSnapshot(
                index=w, legs=legs_of(knobs), knobs=dict(knobs),
                spec_acceptance=ACCEPTANCE, headroom_frac=0.5,
                headroom_bytes=8 << 30)
            d = eng.step(snap)
            if d is not None:
                knobs[d.knob] = d.value  # the fleet applies the marker
            decisions.append(None if d is None else
                             (d.seq, d.window, d.knob, str(d.value)))
            trail.append(round(step_ms(knobs), 4))
        return knobs, [d for d in decisions if d], trail

    knobs, decisions, trail = run_loop()
    _, decisions2, _ = run_loop()

    mis_sps = 1000.0 / step_ms(MIS_TUNED)
    converged_sps = 1000.0 / trail[-1]
    hand_sps = 1000.0 / step_ms(HAND_TUNED)
    last_window = max((d[1] for d in decisions), default=0)
    return {
        "mis_tuned_steps_per_sec": round(mis_sps, 2),
        "converged_steps_per_sec": round(converged_sps, 2),
        "hand_tuned_steps_per_sec": round(hand_sps, 2),
        "speedup": round(converged_sps / mis_sps, 2),
        "vs_hand_tuned": round(converged_sps / hand_sps, 3),
        "n_decisions": len(decisions),
        "last_decision_window": last_window,
        "windows": windows,
        "deterministic_replay": decisions == decisions2,
        "converged_knobs": {k: str(v) for k, v in sorted(knobs.items())},
        "decisions": [f"w{w}: {knob}={val}"
                      for _seq, w, knob, val in decisions],
    }


def _routing_bench(smoke: bool = False) -> dict:
    """hvd-route fleet leg of ``--mode routing`` (pure Python, no jax,
    no chip).  Three legs over simulated replicas that speak the
    client surface of routing/replica.py (health / generate / drain /
    resume / prefixes — duck-typed where the HTTP client would sit):

    1. **Trace replay** — a seeded million-request heavy-tailed trace
       (Zipf-shared prompt headers, Pareto completion lengths, a
       mid-trace arrival spike) against 6 single-server replica queues
       with LRU prefix caches keyed by the REAL chain hashes
       (routing/affinity.py).  Least-loaded + prefix-affinity dispatch
       vs round-robin: p99 TTFT and aggregate tokens/sec gates, plus a
       bit-identical placement digest on replay (the scorer is free of
       wall clock and PRNG).
    2. **Failover digest identity** — the REAL Router dispatches over
       replicas whose completions are a pure rolling-hash function of
       the tokens so far (the sim analogue of the serving bitwise
       contract: prompt+partial reproduces the uninterrupted tail).
       One replica drains mid-generation (503 with partial tokens),
       another dies outright (connection severed, no partials); every
       merged completion must be digest-identical to a single-replica
       reference run.
    3. **Autoscaling** — the REAL FleetAutoscaler over the REAL
       Router: a sustained spike boots a replica (priced by the
       hvd-mem planner against host headroom, prefix-seeded from the
       busiest donor), a second spike against exhausted headroom is
       VETOED (never an OOM), and the trough drains the booted replica
       back, donating its prefix index to a survivor.
    """
    import hashlib
    import random as _random
    from collections import OrderedDict

    from horovod_tpu.memory.planner import (kv_cache_bytes,
                                            prefix_pages_bytes)
    from horovod_tpu.routing import (AutoscaleConfig, FleetAutoscaler,
                                     Router, RouterConfig)
    from horovod_tpu.routing.affinity import (prompt_header_hashes,
                                              published_page_hashes)
    from horovod_tpu.routing.replica import ReplicaUnreachable

    PAGE, PPS = 16, 8
    FP = "routing-bench-fp"

    # ---- leg 1: million-request heavy-tailed trace replay ----------------
    n_requests = 20_000 if smoke else 1_000_000
    n_replicas = 6
    n_headers = 400
    header_tokens = 4 * PAGE      # 4-page shared prompt headers
    cache_cap = 64                # headers one replica keeps warm (LRU)
    prefill_us = 60.0             # cost per uncached prompt token
    decode_us = 50.0              # cost per generated token
    rng = _random.Random(20)

    headers = [[rng.randrange(256) for _ in range(header_tokens)]
               for _ in range(n_headers)]
    # One chain hash per header, computed ONCE through the real scheme
    # (routing/affinity.py) — the first-page digest stands for the
    # whole chain in the sim's per-replica index.
    header_key = [prompt_header_hashes(FP.encode(), h + [0], PAGE,
                                       PPS)[0] for h in headers]
    weights = [1.0 / (r + 1) ** 0.7 for r in range(n_headers)]
    cum, acc = [], 0.0
    for w in weights:
        acc += w
        cum.append(acc)
    hdr = rng.choices(range(n_headers), cum_weights=cum, k=n_requests)
    suffix = [rng.randrange(8, 25) for _ in range(n_requests)]
    mtok = [max(1, min(64, int(4 * rng.paretovariate(1.5))))
            for _ in range(n_requests)]
    # Arrivals: Poisson at a base rate with a 1.25x spike through the
    # middle third (the autoscaling leg re-uses the same shape).
    base_us = 1e6 / 2400.0
    arrive, t = [], 0.0
    lo, hi = n_requests // 3, 2 * n_requests // 3
    for i in range(n_requests):
        mean = base_us / 1.25 if lo <= i < hi else base_us
        t += rng.expovariate(1.0 / mean)
        arrive.append(t)

    def _replay(policy: str) -> dict:
        busy = [0.0] * n_replicas
        caches = [OrderedDict() for _ in range(n_replicas)]
        hits, total_tokens = 0, 0
        ttfts = []
        placements = hashlib.sha256()
        aff_bonus = header_tokens * prefill_us  # prefill saved by a hit
        for i in range(n_requests):
            now = arrive[i]
            key = header_key[hdr[i]]
            if policy == "rr":
                r = i % n_replicas
            else:
                best = None
                for j in range(n_replicas):
                    backlog = busy[j] - now
                    if backlog < 0.0:
                        backlog = 0.0
                    score = backlog
                    if key in caches[j]:
                        score -= aff_bonus
                    if best is None or score < best[0]:
                        best = (score, j)
                r = best[1]
            cache = caches[r]
            if key in cache:
                hits += 1
                cache.move_to_end(key)
                prefill = suffix[i] * prefill_us
            else:
                cache[key] = None
                if len(cache) > cache_cap:
                    cache.popitem(last=False)
                prefill = (header_tokens + suffix[i]) * prefill_us
            start = busy[r] if busy[r] > now else now
            ttfts.append(start + prefill - now)
            busy[r] = start + prefill + mtok[i] * decode_us
            total_tokens += mtok[i]
            placements.update(bytes([r]))
        ttfts.sort()
        makespan_s = max(busy) / 1e6
        return {
            "p50_ttft_ms": round(ttfts[len(ttfts) // 2] / 1e3, 3),
            "p99_ttft_ms": round(
                ttfts[int(0.99 * (len(ttfts) - 1))] / 1e3, 3),
            "tokens_per_sec": round(total_tokens / makespan_s, 1),
            "affinity_hit_rate": round(hits / n_requests, 4),
            "placement_digest": placements.hexdigest()[:16],
        }

    rr = _replay("rr")
    aff = _replay("affinity")
    aff_replay = _replay("affinity")

    # ---- shared sim replica for the real-Router legs ---------------------
    VOCAB = 251

    def _fold(state: int, tok: int) -> int:
        return (state * 1103515245 + tok + 12345) & 0x7FFFFFFF

    def _complete(prompt, m):
        # State is a pure fold over the tokens SO FAR, so
        # _complete(prompt + partial, m - k) == _complete(prompt, m)[k:]
        # — the sim analogue of the serving bitwise contract that makes
        # drain continuations digest-exact.
        s = 0
        for tok in prompt:
            s = _fold(s, int(tok))
        out = []
        for _ in range(m):
            tok = (s * 48271 + 11) % VOCAB
            out.append(tok)
            s = _fold(s, tok)
        return out

    class _SimReplica:
        def __init__(self, name: str) -> None:
            self.name = name
            self.ready = True
            self.dead = False
            self.queue_depth = 0  # external load knob (autoscale leg)
            self.pending = 0      # decaying backlog of recent serves
            self.served = 0
            self.drain_at = None   # served count: 503 mid-generation
            self.die_at = None     # served count: connection severed
            self.index = OrderedDict()  # published chain-hash digests
            self.chains = []            # published token chains
            self.resumes = []           # payloads received via resume()

        def _publish(self, toks) -> None:
            self.chains.append(list(toks))
            for h in published_page_hashes(FP.encode(), toks, PAGE,
                                           PPS):
                self.index[h] = None

        def health(self):
            if self.dead:
                raise ReplicaUnreachable(f"{self.name} is down")
            det = {"ready": self.ready,
                   "queue_depth": self.queue_depth + self.pending,
                   "kv_free_pages": 1 << 20,
                   "kv_total_pages": 1 << 20,
                   "page_size": PAGE, "pages_per_slot": PPS,
                   "fingerprint": FP,
                   "prefix_index": list(self.index)[-512:]}
            # Each poll "works off" part of the backlog, so the
            # reported depth tracks recent assignment — without it
            # every score ties at zero and the name tie-break funnels
            # the whole fleet's traffic onto one replica.
            self.pending = max(0, self.pending - 8)
            return (200 if self.ready else 503), {"serving": det}

        def generate(self, payload, timeout=None):
            if self.dead:
                raise ReplicaUnreachable(f"{self.name} is down")
            if not self.ready:
                return 503, {"error": "draining", "tokens": []}
            self.served += 1
            self.pending += 1
            prompt = [int(tok) for tok in payload["tokens"]]
            m = int(payload.get("max_tokens", 32))
            if self.served == self.die_at:
                self.dead = True
                raise ReplicaUnreachable(f"{self.name} died mid-call")
            if self.served == self.drain_at:
                emitted = _complete(prompt, max(1, m // 2))
                self.ready = False
                return 503, {"error": "drained", "tokens": emitted}
            toks = _complete(prompt, m)
            self._publish(prompt + toks)
            return 200, {"tokens": toks, "finish_reason": "length"}

        def drain(self):
            if self.dead:
                raise ReplicaUnreachable(f"{self.name} is down")
            self.ready = False
            return 200, {"requests": [],
                         "prefixes": [list(c) for c in self.chains]}

        def prefixes(self):
            if self.dead:
                raise ReplicaUnreachable(f"{self.name} is down")
            return 200, {"prefixes": [list(c) for c in self.chains]}

        def resume(self, payload):
            if self.dead:
                raise ReplicaUnreachable(f"{self.name} is down")
            self.resumes.append(payload)
            for chain in payload.get("prefixes") or []:
                self._publish([int(tok) for tok in chain])
            self.ready = True
            return 200, {"installed":
                         len(payload.get("requests") or []),
                         "ready": True}

    # ---- leg 2: drain/death failover, digest-identical completions -------
    def _failover_leg() -> dict:
        lrng = _random.Random(7)
        reqs = []
        for _ in range(240):
            prompt = (headers[lrng.randrange(40)]
                      + [lrng.randrange(256)
                         for _ in range(lrng.randrange(4, 12))])
            reqs.append((prompt, 8 + lrng.randrange(24)))

        def _digest(runs) -> str:
            d = hashlib.sha256()
            for prompt, toks in runs:
                d.update(f"{len(prompt)}:".encode())
                d.update(",".join(str(int(tok))
                                  for tok in toks).encode())
            return d.hexdigest()

        reference = _digest((p, _complete(p, m)) for p, m in reqs)

        router = Router(RouterConfig(probe_base=0.0),
                        sleep=lambda s: None)
        fleet = [_SimReplica(f"r{j}") for j in range(4)]
        fleet[1].drain_at = 25  # drains mid-generation (503+partials)
        fleet[2].die_at = 40    # severed mid-call, no partials
        for rep in fleet:
            router.add_replica(rep.name, rep)
        router.poll()
        runs, continuations, failovers, aff_requests = [], 0, 0, 0
        for k, (prompt, m) in enumerate(reqs):
            if k % 16 == 0:
                router.poll()
            status, resp = router.dispatch({"tokens": prompt,
                                            "max_tokens": m})
            if status != 200:
                return {"requests": len(reqs),
                        "digest_identical": False,
                        "error": f"dispatch {status}: {resp}"}
            runs.append((prompt, resp["tokens"]))
            stamp = resp.get("router") or {}
            continuations += int(stamp.get("resubmits", 0))
            failovers += int(stamp.get("failovers", 0))
            if int(stamp.get("affinity_pages", 0)) > 0:
                aff_requests += 1
        return {"requests": len(reqs),
                "digest_identical": _digest(runs) == reference,
                "continuations": continuations,
                "failovers": failovers,
                "affinity_requests": aff_requests}

    # ---- leg 3: autoscaling with planner pricing -------------------------
    def _autoscale_leg() -> dict:
        router = Router(RouterConfig(probe_base=0.0),
                        sleep=lambda s: None)
        pool = {}

        def launch(name: str):
            rep = _SimReplica(name)
            pool[name] = rep
            return rep

        def retire(name: str) -> None:
            pool.pop(name, None)

        base = [_SimReplica(f"base{j}") for j in range(2)]
        for rep in base:
            pool[rep.name] = rep
            router.add_replica(rep.name, rep)
        # Warm the donor so scale-up has live prefixes to seed from.
        base[0].resume({"requests": [],
                        "prefixes": [headers[j] + [1]
                                     for j in range(8)]})
        router.poll()

        # hvd-mem pricing: one replica's serving footprint (KV pool +
        # prefix reserve) against a shrinking host-headroom ledger.
        replica_bytes = (kv_cache_bytes(4, 8, 64, 8, PPS, PAGE)
                         + prefix_pages_bytes(4, 8, 64, 64, PAGE))
        host = {"free": replica_bytes + replica_bytes // 2}
        scaler = FleetAutoscaler(
            router, launch, retire,
            AutoscaleConfig(min_replicas=2, max_replicas=4,
                            up_load=4.0, down_load=1.0, sustain=2,
                            cooldown=1),
            price=lambda: replica_bytes,
            headroom=lambda: host["free"])

        events, seeded_pages, oom_free = [], 0, True

        def tick() -> None:
            nonlocal seeded_pages, oom_free
            router.poll()
            e = scaler.observe()
            if e is None:
                return
            events.append(e)
            if e.startswith("up:"):
                host["free"] -= replica_bytes
                if host["free"] < 0:  # a boot the planner should have
                    oom_free = False  # vetoed landed on an OOM
                newcomer = pool.get(e.split(":", 1)[1])
                if newcomer is not None:
                    seeded_pages = max(seeded_pages,
                                       len(newcomer.index))
            elif e.startswith("down:"):
                host["free"] += replica_bytes

        # Spike: deep queues everywhere -> scale up (priced, seeded).
        for rep in pool.values():
            rep.queue_depth = 9
        for _ in range(4):
            tick()
        # Still spiking, headroom now exhausted -> veto, never a boot.
        for rep in pool.values():
            rep.queue_depth = 9
        for _ in range(4):
            tick()
        # Trough: fleet idles -> drain the booted replica back.
        for rep in pool.values():
            rep.queue_depth = 0
        for _ in range(4):
            tick()

        donated = any(rep.resumes for rep in base)
        return {"events": events,
                "scaled_up": any(e.startswith("up:") for e in events),
                "seeded_pages": seeded_pages,
                "veto": "veto:up" in events,
                "scaled_down": any(e.startswith("down:")
                                   for e in events),
                "prefixes_donated": donated,
                "fleet_final": router.replica_names(),
                "oom_free": oom_free and host["free"] >= 0}

    failover = _failover_leg()
    autoscale = _autoscale_leg()
    return {
        "metric": "routing_tokens_per_sec",
        "value": aff["tokens_per_sec"],
        "unit": "tokens/sec",
        "vs_baseline": round(aff["tokens_per_sec"]
                             / rr["tokens_per_sec"], 2)
        if rr["tokens_per_sec"] else None,
        "n_requests": n_requests,
        "n_replicas": n_replicas,
        "round_robin": rr,
        "affinity": aff,
        "p99_ttft_speedup": round(rr["p99_ttft_ms"]
                                  / aff["p99_ttft_ms"], 2),
        "tokens_per_sec_speedup": round(aff["tokens_per_sec"]
                                        / rr["tokens_per_sec"], 2),
        "affinity_hit_rate": aff["affinity_hit_rate"],
        "deterministic_replay": aff == aff_replay,
        "failover": failover,
        "autoscale": autoscale,
    }


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--smoke", action="store_true",
                    help="tiny shapes for CPU sanity checks")
    ap.add_argument("--mode",
                    choices=["resnet", "control", "dataplane", "input",
                             "serving", "overlap", "pipeline",
                             "memory", "fused", "tuning", "routing"],
                    default="resnet",
                    help="resnet (default) = the chip run. Every other "
                         "mode is a CPU contract gate that pins the "
                         "8-virtual-device CPU mesh itself and never "
                         "touches a chip: control = control-plane "
                         "negotiations/sec (no XLA); dataplane = "
                         "steady-state fused-cycle latency + "
                         "dispatches/cycle, eager vs megakernel; "
                         "input = steps/sec with a synthetic slow host "
                         "loader, prefetch+async on vs off; "
                         "serving = hvd-serve tokens/sec, "
                         "continuous vs static batching on a seeded "
                         "ragged-arrival trace, plus the hvd-spec "
                         "prefix-cache and speculative-decoding legs; "
                         "overlap = backward/communication overlap "
                         "steps/sec, streamed vs serialized bucket "
                         "dispatch on a transformer-LM chain, plus the "
                         "param-identity gates; "
                         "pipeline = 1F1B MPMD pipeline schedule vs the "
                         "GPipe-ordered dispatch of the same per-stage "
                         "executables — steps/sec, exposed-bubble "
                         "seconds, bitwise + reference parity gates; "
                         "memory = hvd-mem planner "
                         "accuracy vs the live ledger, plan "
                         "determinism, and the seeded-OOM forensics "
                         "path; fused = hvd-fuse "
                         "computation-collective kernels — bitwise vs "
                         "the unfused reference, one-dispatch-per-"
                         "group, and exposed-communication strictly "
                         "below the unfused leg; "
                         "tuning = hvd-tune closed-loop convergence — "
                         "the real policy engine + hvd-mem pricing "
                         "over a deterministic mis-tuned fleet model "
                         "(no XLA); routing = hvd-route "
                         "fleet dispatch — least-loaded + prefix-"
                         "affinity vs round-robin on a seeded million-"
                         "request heavy-tailed trace, drain/death "
                         "failover digest identity through the real "
                         "Router, and planner-priced autoscaling "
                         "(no XLA)")
    ap.add_argument("--check-speedup", type=float, default=None,
                    help="control mode: exit nonzero when the cache-on/"
                         "cache-off speedup is below this bound; "
                         "dataplane mode: exit nonzero when megakernel/"
                         "eager throughput is below this bound OR the "
                         "dispatches/cycle reduction is < 2x OR the "
                         "identity/hierarchical checks fail; input mode: "
                         "exit nonzero when prefetch-on/off steps/sec is "
                         "below this bound OR the trained params differ; "
                         "serving mode: exit nonzero when continuous/"
                         "static tokens/sec is below this bound OR the "
                         "two schedulers' completions differ OR the "
                         "engine rollout is not bitwise-equal to the "
                         "non-incremental forward (CI gates); overlap "
                         "mode: exit nonzero when overlapped/serialized "
                         "steps/sec is below this bound OR any "
                         "param-identity gate fails (bitwise vs the "
                         "serialized schedule, full precision and int8; "
                         "allclose vs the monolithic step); pipeline "
                         "mode: exit nonzero when "
                         "1f1b/gpipe steps/sec is below this bound OR "
                         "the 1f1b exposed-bubble seconds are not "
                         "strictly below the gpipe leg's OR the "
                         "bitwise/reference parity gates fail")
    ap.add_argument("--check-spec-speedup", type=float, default=None,
                    help="serving mode: exit nonzero when speculative/"
                         "non-speculative tokens/sec on the seeded "
                         "heavy-tailed trace is below this bound, when "
                         "speculative completions are not bitwise-equal "
                         "to non-speculative greedy (the bitwise-greedy "
                         "acceptance rule), when a steady-state "
                         "speculative iteration is not exactly one "
                         "draft propose + ONE target verify executable "
                         "dispatch with zero eager launches, when the "
                         "prefix-cache leg's completions differ from "
                         "cache-off, or when the repeated-prefix trace "
                         "saves no prefill tokens")
    ap.add_argument("--check-wire-ratio", type=float, default=None,
                    help="dataplane mode: exit nonzero when the int8 "
                         "bytes-on-wire compression ratio is below this "
                         "bound, when the int8/int4 fused kernels do "
                         "not match the eager-quantized reference, or "
                         "when the int8 leg falls under a 0.5x "
                         "throughput floor vs the adjacent uncompressed "
                         "leg (parity on a quiet box; the floor keeps "
                         "the CI gate load-proof)")
    ap.add_argument("--check-memory-plan", type=float, default=None,
                    help="memory mode: exit nonzero when the planner's "
                         "framework-bytes prediction misses the "
                         "measured ledger high-watermark by more than "
                         "this percentage on either leg, when repeated "
                         "plans are not byte-identical, or when the "
                         "seeded RESOURCE_EXHAUSTED fails to dump the "
                         "executable + top ledger categories")
    ap.add_argument("--check-tree-frames", type=float, default=None,
                    help="with --mode control: fail unless rank-0 rx "
                         "frames per simulated cycle stay under "
                         "C*fanout*log_fanout(world) at every "
                         "simulated world size (ops/tree.py gate)")
    ap.add_argument("--control-seconds", type=float, default=1.0,
                    help="control mode: seconds per measurement leg")
    ap.add_argument("--batch-size", type=int, default=128)
    ap.add_argument("--image-size", type=int, default=224)
    ap.add_argument("--iters", type=int, default=20)
    ap.add_argument("--warmup", type=int, default=3)
    ap.add_argument("--no-space-to-depth", dest="space_to_depth",
                    action="store_false", default=True,
                    help="disable the MLPerf space-to-depth stem")
    args = ap.parse_args()

    if args.mode == "control":
        result = _control_bench(seconds=args.control_seconds)
        result["tree"] = _tree_bench()
        print(json.dumps(result))
        if args.check_speedup is not None:
            speedup = result.get("speedup") or 0.0
            if speedup < args.check_speedup:
                print(f"FAIL: response-cache speedup {speedup}x is below "
                      f"the required {args.check_speedup}x",
                      file=sys.stderr)
                return 1
        if args.check_tree_frames is not None:
            # The scale-out gate (CI job tree-bench): at simulated
            # world=256 rank 0's per-cycle frame count must sit under
            # c * fanout * log_fanout(world) — i.e. the tree actually
            # deleted the O(world) frame funnel, structurally.
            failures = []
            for w in result["tree"]["worlds"]:
                bound = args.check_tree_frames * w["fanout_log_bound"]
                if w["tree_frames_per_cycle"] > bound:
                    failures.append(
                        f"world={w['world']}: "
                        f"{w['tree_frames_per_cycle']} rank-0 frames "
                        f"per cycle > allowed {bound:.0f}")
                if w["world"] >= 64 and w["tree_frames_per_cycle"] * 4 \
                        > w["flat_frames_per_cycle"]:
                    failures.append(
                        f"world={w['world']}: tree frames "
                        f"{w['tree_frames_per_cycle']} not ≤ 1/4 of "
                        f"flat {w['flat_frames_per_cycle']}")
            if failures:
                for f in failures:
                    print(f"FAIL: {f}", file=sys.stderr)
                return 1
        return 0

    if args.mode == "dataplane":
        # CPU-only like --mode control: force the 8-virtual-device mesh
        # BEFORE the first jax import so the dynamic path runs anywhere
        # (same bootstrap as tests/conftest.py).
        os.environ["JAX_PLATFORMS"] = "cpu"
        if "--xla_force_host_platform_device_count" not in \
                os.environ.get("XLA_FLAGS", ""):
            os.environ["XLA_FLAGS"] = (
                os.environ.get("XLA_FLAGS", "")
                + " --xla_force_host_platform_device_count=8").strip()
        result = _dataplane_bench()
        print(json.dumps(result))
        if args.check_speedup is not None:
            failures = []
            if (result.get("speedup") or 0.0) < args.check_speedup:
                failures.append(
                    f"megakernel speedup {result.get('speedup')}x < "
                    f"required {args.check_speedup}x")
            if (result.get("dispatch_reduction") or 0.0) < 2.0:
                failures.append(
                    f"dispatches/cycle reduction "
                    f"{result.get('dispatch_reduction')}x < required 2x")
            if not result.get("bitwise_identical"):
                failures.append("megakernel results not bitwise-identical "
                                "to the per-tensor path")
            if not result.get("hierarchical_equal"):
                failures.append("hierarchical ICI×DCN allreduce not "
                                "equivalent to flat psum")
            if failures:
                for f in failures:
                    print(f"FAIL: {f}", file=sys.stderr)
                return 1
        if args.check_wire_ratio is not None:
            failures = []
            comp = result.get("compression") or {}
            int8 = comp.get("int8") or {}
            ratio = int8.get("compression_ratio") or 0.0
            if ratio < args.check_wire_ratio:
                failures.append(
                    f"int8 bytes-on-wire ratio {ratio}x < required "
                    f"{args.check_wire_ratio}x")
            for name in ("int8", "int4"):
                if not (comp.get(name) or {}).get("reference_equal"):
                    failures.append(
                        f"{name} fused kernel does not match the "
                        f"eager-quantized reference")
            # Throughput: the quantized kernel is still ONE dispatch
            # per group and measures at parity (~1.0x) on a quiet box;
            # the CI assertion is a regression FLOOR, not the parity
            # claim — shared-runner wall clocks swing ±40% under load
            # (same policy as the tier-1 bench contract test), and the
            # measured ratio rides the JSON either way.
            spd = int8.get("speedup_vs_uncompressed") or 0.0
            if spd < 0.5:
                failures.append(
                    f"int8 leg at {spd}x of the uncompressed "
                    f"megakernel throughput (floor 0.5x)")
            if failures:
                for f in failures:
                    print(f"FAIL: {f}", file=sys.stderr)
                return 1
        return 0

    if args.mode == "memory":
        # CPU-only like --mode dataplane: pin the 8-virtual-device mesh
        # before the first jax import (same bootstrap as conftest.py).
        os.environ["JAX_PLATFORMS"] = "cpu"
        if "--xla_force_host_platform_device_count" not in \
                os.environ.get("XLA_FLAGS", ""):
            os.environ["XLA_FLAGS"] = (
                os.environ.get("XLA_FLAGS", "")
                + " --xla_force_host_platform_device_count=8").strip()
        result = _memory_bench()
        print(json.dumps(result))
        if args.check_memory_plan is not None:
            failures = []
            for leg in ("dataplane", "pipeline"):
                err = (result.get(leg) or {}).get(
                    "prediction_error_pct")
                if err is None or err > args.check_memory_plan:
                    failures.append(
                        f"{leg} planner prediction off by {err}% "
                        f"(bound {args.check_memory_plan}%)")
            if not result.get("plan_deterministic"):
                failures.append(
                    "repeated plans are not byte-identical")
            if not (result.get("oom_dump") or {}).get("ok"):
                failures.append(
                    f"seeded RESOURCE_EXHAUSTED did not produce the "
                    f"forensic dump: {result.get('oom_dump')}")
            if failures:
                for f in failures:
                    print(f"FAIL: {f}", file=sys.stderr)
                return 1
        return 0

    if args.mode == "fused":
        # CPU-only like --mode dataplane: pin the 8-virtual-device mesh
        # before the first jax import (same bootstrap as conftest.py).
        os.environ["JAX_PLATFORMS"] = "cpu"
        if "--xla_force_host_platform_device_count" not in \
                os.environ.get("XLA_FLAGS", ""):
            os.environ["XLA_FLAGS"] = (
                os.environ.get("XLA_FLAGS", "")
                + " --xla_force_host_platform_device_count=8").strip()
        result = _fused_bench()
        print(json.dumps(result))
        if args.check_speedup is not None:
            failures = []
            for name, ok in (result.get("bitwise") or {}).items():
                if not ok:
                    failures.append(
                        f"fused {name} program not bitwise-identical "
                        f"to the unfused reference")
            for leg, disp in (result.get("dispatches_per_fused_group")
                              or {}).items():
                if disp != 1:
                    failures.append(
                        f"{leg} fused group dispatched {disp} XLA "
                        f"executables per cycle (contract: exactly 1)")
            if not (result.get("exposed_comm")
                    or {}).get("strictly_below"):
                ec = result.get("exposed_comm") or {}
                failures.append(
                    f"fused exposed communication "
                    f"{ec.get('fused_us')}us not strictly below the "
                    f"unfused leg's {ec.get('unfused_us')}us")
            if failures:
                for f in failures:
                    print(f"FAIL: {f}", file=sys.stderr)
                return 1
        return 0

    if args.mode == "tuning":
        # Pure Python (policy engine + pricing formulas): no XLA, no
        # mesh, no chip.
        result = _tuning_bench()
        print(json.dumps(result))
        if args.check_speedup is not None:
            failures = []
            if (result.get("speedup") or 0.0) < args.check_speedup:
                failures.append(
                    f"tuned/mis-tuned speedup {result.get('speedup')}x "
                    f"< required {args.check_speedup}x")
            if (result.get("vs_hand_tuned") or 0.0) < 0.9:
                failures.append(
                    f"converged throughput is "
                    f"{result.get('vs_hand_tuned')} of the hand-tuned "
                    f"reference (required: within 10%)")
            if (result.get("last_decision_window") or 0) > 60:
                failures.append(
                    f"last decision at window "
                    f"{result.get('last_decision_window')} "
                    f"(required: converged within 60 windows)")
            if not result.get("deterministic_replay"):
                failures.append("decision sequence not identical on "
                                "replay")
            if failures:
                for f in failures:
                    print(f"FAIL: {f}", file=sys.stderr)
                return 1
        return 0

    if args.mode == "routing":
        # Pure Python (router + autoscaler + queueing sim): no XLA, no
        # mesh, no chip.
        result = _routing_bench(smoke=args.smoke)
        print(json.dumps(result))
        if args.check_speedup is not None:
            failures = []
            if (result.get("p99_ttft_speedup")
                    or 0.0) < args.check_speedup:
                failures.append(
                    f"p99 TTFT speedup {result.get('p99_ttft_speedup')}"
                    f"x over round-robin < required "
                    f"{args.check_speedup}x")
            if (result.get("tokens_per_sec_speedup")
                    or 0.0) < args.check_speedup:
                failures.append(
                    f"tokens/sec speedup "
                    f"{result.get('tokens_per_sec_speedup')}x over "
                    f"round-robin < required {args.check_speedup}x")
            if (result.get("affinity_hit_rate") or 0.0) <= 0.0:
                failures.append("affinity hit rate is zero — the "
                                "prefix index never routed a warm "
                                "header")
            if not result.get("deterministic_replay"):
                failures.append("placement sequence not identical on "
                                "replay")
            fo = result.get("failover") or {}
            if not fo.get("digest_identical"):
                failures.append(
                    "failover completions are not digest-identical to "
                    f"the single-replica reference ({fo.get('error')})")
            if (fo.get("continuations") or 0) < 1:
                failures.append("no drain continuation was exercised")
            if (fo.get("failovers") or 0) < 2:
                failures.append("drain+death failovers not exercised")
            auto = result.get("autoscale") or {}
            for gate, msg in (
                    ("scaled_up", "the spike never booted a replica"),
                    ("seeded_pages", "the booted replica was not "
                                     "prefix-seeded from a donor"),
                    ("veto", "the exhausted-headroom boot was not "
                             "vetoed by the planner price check"),
                    ("scaled_down", "the trough never drained a "
                                    "replica back"),
                    ("prefixes_donated", "the drained replica's "
                                         "prefix index was not "
                                         "donated to a survivor"),
                    ("oom_free", "a scale-up landed on an OOM")):
                if not auto.get(gate):
                    failures.append(f"autoscale: {msg} "
                                    f"(events={auto.get('events')})")
            if failures:
                for f in failures:
                    print(f"FAIL: {f}", file=sys.stderr)
                return 1
        return 0

    if args.mode == "input":
        # CPU-only like --mode dataplane: pin the 8-virtual-device mesh
        # before the first jax import (same bootstrap as conftest.py).
        os.environ["JAX_PLATFORMS"] = "cpu"
        if "--xla_force_host_platform_device_count" not in \
                os.environ.get("XLA_FLAGS", ""):
            os.environ["XLA_FLAGS"] = (
                os.environ.get("XLA_FLAGS", "")
                + " --xla_force_host_platform_device_count=8").strip()
        result = _input_bench()
        print(json.dumps(result))
        if args.check_speedup is not None:
            failures = []
            if (result.get("speedup") or 0.0) < args.check_speedup:
                failures.append(
                    f"input-pipeline speedup {result.get('speedup')}x < "
                    f"required {args.check_speedup}x")
            if not result.get("params_identical"):
                failures.append("trained params differ between prefetch "
                                "on and off")
            if failures:
                for f in failures:
                    print(f"FAIL: {f}", file=sys.stderr)
                return 1
        return 0

    if args.mode == "overlap":
        # CPU-only like --mode dataplane: pin the 8-virtual-device mesh
        # before the first jax import (same bootstrap as conftest.py).
        os.environ["JAX_PLATFORMS"] = "cpu"
        if "--xla_force_host_platform_device_count" not in \
                os.environ.get("XLA_FLAGS", ""):
            os.environ["XLA_FLAGS"] = (
                os.environ.get("XLA_FLAGS", "")
                + " --xla_force_host_platform_device_count=8").strip()
        result = _overlap_bench()
        print(json.dumps(result))
        if args.check_speedup is not None:
            failures = []
            if (result.get("speedup") or 0.0) < args.check_speedup:
                failures.append(
                    f"overlap speedup {result.get('speedup')}x (streamed "
                    f"vs serialized dispatch) < required "
                    f"{args.check_speedup}x")
            if not result.get("bitwise_identical"):
                failures.append(
                    "single-backward overlapped params not bitwise-"
                    "identical to the serialized schedule")
            if not result.get("serial_identical"):
                failures.append(
                    "segmented overlapped params not bitwise-identical "
                    "to the serialized schedule")
            if not result.get("plain_close"):
                failures.append(
                    "single-backward overlapped params diverge from "
                    "the monolithic step beyond float tolerance")
            if not result.get("segmented_close"):
                failures.append(
                    "segmented overlapped params diverge from the "
                    "monolithic step beyond float tolerance")
            int8 = result.get("int8") or {}
            if not int8.get("bitwise_identical"):
                failures.append(
                    "int8 overlapped params not bitwise-identical to "
                    "the int8 serialized schedule (per-bucket EF "
                    "residuals broken)")
            if not int8.get("quantized_active"):
                failures.append(
                    "int8 leg produced the full-precision params — the "
                    "quantized wire path never engaged")
            if (result.get("mp") or {}).get("status") == "failed":
                failures.append(
                    f"np=2 mp overlap leg failed: "
                    f"{(result.get('mp') or {}).get('detail', '')[:300]}")
            if failures:
                for f in failures:
                    print(f"FAIL: {f}", file=sys.stderr)
                return 1
        return 0

    if args.mode == "pipeline":
        # CPU-only like --mode dataplane: pin the 8-virtual-device mesh
        # before the first jax import (same bootstrap as conftest.py).
        os.environ["JAX_PLATFORMS"] = "cpu"
        if "--xla_force_host_platform_device_count" not in \
                os.environ.get("XLA_FLAGS", ""):
            os.environ["XLA_FLAGS"] = (
                os.environ.get("XLA_FLAGS", "")
                + " --xla_force_host_platform_device_count=8").strip()
        result = _pipeline_bench()
        print(json.dumps(result))
        if args.check_speedup is not None:
            failures = []
            if (result.get("speedup") or 0.0) < args.check_speedup:
                failures.append(
                    f"pipeline speedup {result.get('speedup')}x (1f1b "
                    f"vs gpipe-ordered dispatch) < required "
                    f"{args.check_speedup}x")
            if not result.get("bitwise_identical"):
                failures.append(
                    "1f1b params/loss not bitwise-identical to the "
                    "gpipe-ordered dispatch of the same executables")
            if not result.get("reference_close"):
                failures.append(
                    "pipeline step diverges from the monolithic "
                    "microbatch-mean gradient beyond float tolerance")
            if not result.get("bubble_hidden"):
                exp = result.get("exposed_bubble_seconds_per_step", {})
                failures.append(
                    f"1f1b exposed-bubble seconds {exp.get('1f1b')} not "
                    f"strictly below the gpipe leg's {exp.get('gpipe')} "
                    f"(reduction not hidden in the schedule)")
            if failures:
                for f in failures:
                    print(f"FAIL: {f}", file=sys.stderr)
                return 1
        return 0

    if args.mode == "serving":
        # CPU-only like --mode dataplane: pin the 8-virtual-device mesh
        # before the first jax import (same bootstrap as conftest.py).
        os.environ["JAX_PLATFORMS"] = "cpu"
        if "--xla_force_host_platform_device_count" not in \
                os.environ.get("XLA_FLAGS", ""):
            os.environ["XLA_FLAGS"] = (
                os.environ.get("XLA_FLAGS", "")
                + " --xla_force_host_platform_device_count=8").strip()
        result = _serving_bench()
        print(json.dumps(result))
        if args.check_speedup is not None:
            failures = []
            if (result.get("speedup") or 0.0) < args.check_speedup:
                failures.append(
                    f"continuous-batching speedup "
                    f"{result.get('speedup')}x < required "
                    f"{args.check_speedup}x")
            if not result.get("results_identical"):
                failures.append(
                    "continuous and static schedulers produced "
                    "different completions (batch-composition "
                    "invariance broken)")
            if not result.get("bitwise_identical"):
                failures.append(
                    "engine prefill+decode rollout diverges from the "
                    "non-incremental serving_forward")
            if failures:
                for f in failures:
                    print(f"FAIL: {f}", file=sys.stderr)
                return 1
        if args.check_spec_speedup is not None:
            failures = []
            spec = result.get("speculative", {})
            prefix = result.get("prefix_cache", {})
            if (spec.get("speedup") or 0.0) < args.check_spec_speedup:
                failures.append(
                    f"speculative speedup {spec.get('speedup')}x < "
                    f"required {args.check_spec_speedup}x")
            if not spec.get("bitwise_greedy"):
                failures.append(
                    "speculative completions diverge from "
                    "non-speculative greedy (bitwise-greedy acceptance "
                    "broken)")
            if (spec.get("verify_dispatches_per_iteration") != 1
                    or spec.get("propose_dispatches_per_iteration") != 1
                    or spec.get("eager_dispatches_per_iteration") != 0):
                failures.append(
                    f"speculative steady state is not 1 propose + 1 "
                    f"verify dispatch with zero eager launches "
                    f"(got propose="
                    f"{spec.get('propose_dispatches_per_iteration')}, "
                    f"verify="
                    f"{spec.get('verify_dispatches_per_iteration')}, "
                    f"eager="
                    f"{spec.get('eager_dispatches_per_iteration')})")
            if not prefix.get("bitwise_identical"):
                failures.append(
                    "prefix-cache completions diverge from cache-off")
            if (prefix.get("prefill_tokens_saved") or 0) <= 0:
                failures.append(
                    "repeated-prefix trace saved no prefill tokens")
            if failures:
                for f in failures:
                    print(f"FAIL: {f}", file=sys.stderr)
                return 1
        return 0

    # mode resnet: one process, one JSON line, no child.
    if args.smoke:
        from horovod_tpu.models.resnet import ResNet18Thin

        device = device_info()
        result = run(batch_size=8, image_size=32, warmup=1, iters=3,
                     model_ctor=ResNet18Thin, num_classes=16)
    else:
        import functools

        from horovod_tpu.models.resnet import ResNet50

        device = require_tpu()
        result = run(batch_size=args.batch_size,
                     image_size=args.image_size,
                     warmup=args.warmup, iters=args.iters,
                     model_ctor=functools.partial(
                         ResNet50, space_to_depth=args.space_to_depth))
        result["mfu"] = round(
            result["tflops_per_chip"] * 1e12
            / chip_peak_flops(device["device_kind"]), 4)
    value = result.pop("value")
    out = {
        "metric": "resnet50_images_per_sec_per_chip",
        "value": round(value, 2),
        "unit": "images/sec/chip",
        "vs_baseline": round(value / BASELINE_IMAGES_PER_SEC_PER_CHIP, 3),
        "device": device,
    }
    out.update(result)
    print(json.dumps(out))
    return 0


if __name__ == "__main__":
    sys.exit(main())
