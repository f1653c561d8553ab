"""Chip smoke: the system's main paths, once, on every chip jax finds.

    python chip_smoke.py            # on the chip (through the chip tool)
    python chip_smoke.py --dry-run  # tiny widths on the 8-device CPU mesh

One process, no children, no ``JAX_PLATFORMS`` override.  Nine legs run
through the entry points a user calls, at full width per chip:

  A  ResNet-50 data-parallel trainer (the BASELINE.json workload):
     hvd.init -> broadcast_parameters -> make_train_step_with_state ->
     shard_batch, default HVD_TPU_OVERLAP (the one-program step with
     the in-program psum: this one process owns every chip).
  B  GPT-2-small LM trainer with the Pallas flash-attention kernels
     (what examples/transformer_lm.py --bench does), one long-context
     step through the streaming kernels, the kernels against a float32
     ``highest`` dense reference, and dp x tp on four chips.
  C  the eager path: allreduce / allgather / broadcast / async + poll +
     synchronize, the torch in-place round trip, one fused cycle of 64
     async allreduces (a megakernel launch).
  D  the server answers: InferenceEngine + LMServer as
     examples/serve_lm.py --serve builds them, /healthz, /generate over
     HTTP (two concurrent, one repeated), logits against
     serving_forward.
  E  the latent-attention mixture of experts the benchmark serves
     (benchmark/configs/axk1-ep16.json, one chip's share of a 16-chip
     expert-parallel group): the cut model built from the seed, two
     requests through InferenceEngine, and the LOGITS its own prefill
     and decode executables produced through the latent paged cache
     against the benchmark's plain float32 reference.
  F  the decoder-hybrid-decoder the benchmark serves
     (benchmark/configs/phi4-mini-flash.json, whole on one chip:
     state-space, window, full, gated-memory and cross layers): the same
     comparison through its two paged layer groups (the window group a
     ring of pages a slot, both attended where they lie by the paged
     kernel) and its two per-slot stores, the scan kernel compiled.
  G  the shortcut-connected mixture of experts the benchmark serves
     (benchmark/configs/longcat-flash-omni-ep32.json, one chip's share
     of a 32-chip expert-parallel group: two latent attentions and two
     dense feed-forwards a layer, zero-compute experts beside the held
     ones): the same comparison through two cache layers a decoder
     layer, and the pairs that went to zero-compute experts counted.
  H  the Mamba-2 hybrid the benchmark serves
     (benchmark/configs/granite-4.0-h-micro.json, whole on one chip: 36
     state-space layers of a matrix state a head, 4 grouped-query
     attention layers): the same comparison through four paged layers
     (attended where they lie, ops/gqa_paged_attention.py) and two
     per-slot stores, a prompt longer than two chunks of the scan, both
     kernels of ops/ssd.py compiled, the state a decode iteration moves
     counted.
  I  the kernel that walks the page table over the latent store
     (ops/latent_paged_attention.py) against its plain twin (every
     slot's table row gathered whole and attended under the lengths),
     at the shapes of the two cells that decode through it (64 heads,
     640-wide entries, 16-token pages; 47 of 128 and 14 of 64 slots
     alive): the same attention, and the kernel's time beside what its
     live bytes need at the HBM peak.

The run fails at the first leg that fails, names it, prints no result
line and exits non-zero.  It fails before any leg unless jax found a TPU
whose ``device_kind`` is in benchmark.device.PEAKS: a CPU fallback is an
error, not a slower run.  On success stdout ends with two JSON lines:
the report (versions, compile-cache counters, every leg's numbers,
``"claim": null``) and then, last, the verdict with exactly these keys:
``{"ok": true, "device": {"platform": "tpu", "kind": "...", "count": n}}``.
Step and request times in the report are orientation, not a benchmark.

``--dry-run`` exists for the test suite: the same legs at toy widths on
whatever platform jax has, Pallas in interpret mode.  Its verdict says
``"ok": false`` and names the platform, and its report says
``"dry_run": "passed"``, so it cannot be read as a pass on the chip.
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import threading
import time
import traceback
import urllib.request

import jax
import jax.numpy as jnp
import numpy as np

T_START = time.perf_counter()

# Widths.  "chip" is the contract: one model the repo supports at its
# full width per chip.  "dry" only has to reach every code path quickly.
CHIP = {
    "resnet": dict(image=224, per_chip=128, classes=1000, steps=6),
    "lm": dict(vocab=32768, d_model=768, heads=12, layers=12, d_ff=3072,
               bf16=True, per_chip=8, seq=1024, block=256, steps=5,
               long_seq=8192, loss_chunk=1024),
    # [batch, heads, seq, head_dim]; the streaming shape lies beyond
    # HVD_TPU_FLASH_RESIDENT_SEQ (4096) and is cut in heads so the dense
    # float32 reference (4 score-sized matrices) fits beside it.
    # The resident shape is the benchmark's gpt2m-train-1k cell's own.
    "flash": dict(resident=(8, 16, 1024, 64), streaming=(1, 2, 8192, 64),
                  block=256),
    "eager": dict(fused=64, elems=1 << 16),
    "serve": dict(max_seq=1024, slots=8, prompts=(16, 48, 128), new=16),
}
DRY = {
    "resnet": dict(image=32, per_chip=4, classes=16, steps=4),
    "lm": dict(vocab=256, d_model=64, heads=4, layers=2, d_ff=128,
               bf16=False, per_chip=2, seq=64, block=32, steps=4,
               long_seq=256, loss_chunk=64),
    "flash": dict(resident=(1, 2, 64, 64), streaming=(1, 1, 256, 32),
                  block=32),
    "eager": dict(fused=64, elems=64),
    "serve": dict(max_seq=128, slots=8, prompts=(16, 24, 48), new=8),
}

# Stated tolerances (max abs error over max abs reference).
# Flash kernels: bf16 operands, f32 accumulation, P and dS rounded to
# bf16 before their second matmul, outputs rounded to bf16: a few bf16
# ulps (2^-8 each) of the largest magnitude.  Measured on the v5e
# (PR 21): at most 4.0e-3 for o/dq/dk/dv and 7.6e-6 for lse.
FLASH_REL_TOL = 2.0 ** -6
FLASH_LSE_ABS_TOL = 1e-3
# Serving: prefill + decode against the non-incremental forward.  The
# bitwise contract of serving/engine.py is an XLA:CPU contract; on the
# chip the two programs tile their bf16 matmuls differently, so the
# float32 logits agree to bf16 rounding carried through the layers.
# Measured on the v5e (PR 21): 1.0e-2 of the largest logit.
SERVE_REL_TOL = 2.0 ** -5
# Latent MoE: bf16 program against the float32 ``highest`` reference.
# The root-mean-square error of the logits, over their spread, is what
# bf16 rounding carried through 7 layers gives (measured on the v5e, PR
# 27: 0.03-0.055; the reference itself computed in bf16 reads 0.028, in
# fp8 0.25).  Single logits move by far more wherever a near-tied
# router choice flips, so the largest error is reported, not judged.
LATENT_RMS_REL_TOL = 0.12
# Shortcut MoE: the same comparison through 4 layers of two attentions,
# two dense feed-forwards and one expert layer each (measured on the v5e,
# PR 33: 0.028, 0.031; the largest single error 0.30 of the spread).  The
# latent model's limit, for the same reason.
SHORTCUT_RMS_REL_TOL = 0.12
# Hybrid state-space decoder: the same comparison through 32 layers with
# no router to flip a choice (measured on the v5e, PR 31: 0.027, 0.030;
# the largest single error 0.17 of the spread).  The latent model's
# limit: its readings say where bf16 ends and fp8 begins.
HYBRID_RMS_REL_TOL = 0.12
# Paged latent attention against its gathered twin, bfloat16 entries: both
# round the probabilities and the attended latent to bfloat16, at other
# points of the sum; a few units in the last place of the largest output.
PAGED_REL_TOL = 2.0 ** -6
# Mamba-2 hybrid: the same comparison through 40 layers, 36 of them the
# chunked recurrence over the prompt and the one-step kernel in decode
# (no router either).  The latent model's limit, for the same reason.
MAMBA2_RMS_REL_TOL = 0.12


class LegFailed(Exception):
    pass


def check(cond, msg):
    if not cond:
        raise LegFailed(msg)


def peak_bytes():
    """Per-device high-water mark so far (None where the backend keeps
    no statistics, i.e. the CPU dry run)."""
    return [(d.memory_stats() or {}).get("peak_bytes_in_use")
            for d in jax.devices()]


def grew(before, after, name):
    """How far counter ``name`` moved between two hvd.metrics()
    snapshots."""
    return (after.get(name, {}).get("value", 0)
            - before.get(name, {}).get("value", 0))


def timed_steps(fn, n):
    """Seconds per call of ``fn`` over ``n`` calls; ``fn`` returns what
    to block on."""
    t0 = time.perf_counter()
    for _ in range(n):
        out = fn()
    jax.block_until_ready(out)
    return (time.perf_counter() - t0) / n


# ---------------------------------------------------------------------------
# Leg A — ResNet-50 data-parallel trainer
# ---------------------------------------------------------------------------

def leg_resnet(w, dry):
    import optax

    import horovod_tpu as hvd
    from horovod_tpu.memory.ledger import tree_nbytes
    from horovod_tpu.models import resnet as R
    from horovod_tpu.parallel import overlap
    from horovod_tpu.parallel.training import (make_train_step_with_state,
                                               shard_batch)

    n = hvd.size()
    ctor = R.ResNet18Thin if dry else \
        (lambda **kw: R.ResNet50(space_to_depth=True, **kw))
    model = ctor(num_classes=w["classes"])
    params, stats = R.init_resnet(model, image_size=w["image"],
                                  batch_size=w["per_chip"])
    params = hvd.broadcast_parameters(params, root_rank=0)
    param_bytes = tree_nbytes(params)
    opt = optax.sgd(0.1, momentum=0.9)
    step = make_train_step_with_state(R.resnet_loss_fn(model), opt)
    schedule = overlap.resolve_mode(None, hvd.mesh())
    images, labels = R.synthetic_imagenet(w["per_chip"] * n,
                                          image_size=w["image"],
                                          num_classes=w["classes"])
    batch = shard_batch((jnp.asarray(images), jnp.asarray(labels)))
    shard_devices = {s.device for s in batch[0].addressable_shards}
    check(len(shard_devices) == n,
          f"batch shards sit on {len(shard_devices)} devices, not {n}")
    opt_state = opt.init(params)
    before = hvd.metrics()

    state = [params, stats, opt_state]
    losses = []

    def one():
        state[0], state[1], state[2], loss = step(*state, batch)
        return loss

    t0 = time.perf_counter()
    losses.append(float(one()))
    compile_s = time.perf_counter() - t0
    for _ in range(w["steps"] - 1):
        losses.append(float(one()))
    step_s = timed_steps(one, 3)
    check(all(np.isfinite(losses)), f"non-finite loss: {losses}")
    check(losses[-1] < losses[0], f"loss did not fall: {losses}")

    after = hvd.metrics()
    info = {"schedule": schedule, "compile_s": round(compile_s, 2),
            "step_s": round(step_s, 4), "losses": [round(x, 4)
                                                   for x in losses],
            "param_bytes": param_bytes,
            "peak_bytes": peak_bytes()}
    if n > 1:
        # One process drives every chip here, so ``auto`` is the
        # one-program step; the knob, where set (the dry run forces
        # ``on``), wins.
        if overlap.overlap_mode() == "auto":
            check(schedule == "off",
                  f"the overlap schedule resolved to {schedule!r} on {n} "
                  f"devices of one process, expected 'off'")
        info["overlap_fallbacks"] = grew(before, after,
                                         "overlap.fallbacks")
        info["overlap_buckets"] = grew(before, after,
                                       "overlap.buckets_dispatched")
        check(info["overlap_fallbacks"] == 0,
              f"overlap fell back {info['overlap_fallbacks']}x")
        if schedule == "off":
            check(info["overlap_buckets"] == 0,
                  f"the one-program step dispatched "
                  f"{info['overlap_buckets']} buckets")
        else:
            check(info["overlap_buckets"] > 0,
                  f"the {schedule} schedule dispatched no bucket")
    if not dry:
        for d, pk in zip(jax.devices(), info["peak_bytes"]):
            check(pk is not None and pk > param_bytes,
                  f"{d}: peak {pk} B is not above the parameter "
                  f"footprint {param_bytes} B")
    return info


# ---------------------------------------------------------------------------
# Leg B — GPT-2-small LM trainer with the Pallas kernels
# ---------------------------------------------------------------------------

def _lm_cfg(w, seq, **kw):
    from horovod_tpu.models.transformer import TransformerConfig

    return TransformerConfig(
        vocab_size=w["vocab"], d_model=w["d_model"], n_heads=w["heads"],
        n_layers=w["layers"], d_ff=w["d_ff"], max_seq_len=seq,
        dtype=jnp.bfloat16 if w["bf16"] else jnp.float32,
        block_q=w["block"], block_k=w["block"], **kw)


def _lm_train(cfg, mesh, ax, global_batch, seq, steps, want_kernel):
    """make_loss_fn -> make_parallel_train_step -> shard_parallel_batch,
    ``steps`` steps on one fixed batch.  Returns (losses, compile_s,
    step_s)."""
    import optax
    from jax.sharding import PartitionSpec as P

    from horovod_tpu.models.transformer import (init_transformer,
                                                make_loss_fn,
                                                synthetic_lm_batch)
    from horovod_tpu.parallel.training import (make_parallel_train_step,
                                               shard_parallel_batch)

    params = init_transformer(jax.random.PRNGKey(0), cfg)
    loss_fn = make_loss_fn(cfg, ax, mesh_axes=mesh.axis_names)
    opt = optax.adamw(3e-4)
    step = make_parallel_train_step(loss_fn, opt, mesh, P("data", None),
                                    donate=False)
    tokens, targets = synthetic_lm_batch(jax.random.PRNGKey(1),
                                         global_batch, seq,
                                         cfg.vocab_size)
    data = shard_parallel_batch((tokens, targets), mesh, P("data", None))
    opt_state = opt.init(params)
    if want_kernel:
        text = step.lower(params, opt_state, data).as_text()
        check("tpu_custom_call" in text,
              "the lowered LM step holds no Mosaic custom call: the "
              "dense fallback or interpret mode was traced")
    state = [params, opt_state]

    def one():
        state[0], state[1], loss = step(*state, data)
        return loss

    t0 = time.perf_counter()
    losses = [float(one())]
    compile_s = time.perf_counter() - t0
    for _ in range(steps - 1):
        losses.append(float(one()))
    step_s = timed_steps(one, 2) if steps > 1 else None
    check(all(np.isfinite(losses)), f"non-finite LM loss: {losses}")
    if steps > 1:
        check(losses[-1] < losses[0], f"LM loss did not fall: {losses}")
    return ([round(x, 4) for x in losses], round(compile_s, 2),
            None if step_s is None else round(step_s, 4))


def _flash_check(shape, block, dry, qkv_entry=False):
    """Kernel (o, lse) and (dq, dk, dv) against the dense math in
    float32 at ``highest`` precision, causal.  Returns the measured
    relative errors.  ``shape`` is ``[batch, heads, seq, head_dim]``;
    with ``qkv_entry`` the kernels are the resident ones, reached as the
    model reaches them: on the fused ``[batch, seq, 3 x heads x
    head_dim]`` projection (``flash_attention_qkv``'s forward and
    backward), else the ``[b, h, s, d]`` entry's."""

    from horovod_tpu.ops import flash_attention as F

    dtype = jnp.float32 if dry else jnp.bfloat16
    ks = jax.random.split(jax.random.PRNGKey(7), 4)
    q, k, v, g = (jax.random.normal(kk, shape, dtype) for kk in ks)
    scale = shape[-1] ** -0.5
    interpret = bool(dry)

    @jax.jit
    def kernel(q, k, v, g):
        if qkv_entry:
            b, h, s, _ = shape
            check(F._resident_ok(h, shape[-1], s, s, 0),
                  f"flash {shape}: not a shape of the resident kernels")
            qkv = jnp.concatenate([F._to_rows(x) for x in (q, k, v)], -1)
            o, res = F._flash_qkv_fwd(qkv, h, scale, True, interpret)
            (dqkv,) = F._flash_qkv_bwd(h, scale, True, interpret, res,
                                       F._to_rows(g))
            return (F._to_heads(o, h), res[2].reshape(b, h, -1)[:, :, :s],
                    *(F._to_heads(x, h) for x in jnp.split(dqkv, 3, -1)))
        o, lse = F._flash_forward(q, k, v, scale, True, block, block, 0,
                                  interpret)
        dq, dk, dv = F._flash_backward(
            (q, k, v, o, lse), g, sm_scale=scale, causal=True,
            block_q=block, block_k=block, q_block_offset=0,
            interpret=interpret)
        return o, lse, dq, dk, dv

    @jax.jit
    def dense(q, k, v, g):
        q, k, v, g = (x.astype(jnp.float32) for x in (q, k, v, g))
        with jax.default_matmul_precision("highest"):
            o, lse = F._dense_forward(q, k, v, scale, True, 0)
            dq, dk, dv = F._dense_backward(
                (q, k, v, o, lse), g, sm_scale=scale, causal=True,
                q_block_offset=0)
        return o, lse, dq, dk, dv

    if not dry:
        check("tpu_custom_call" in kernel.lower(q, k, v, g).as_text(),
              "flash check lowered without a Mosaic custom call")
    got = jax.block_until_ready(kernel(q, k, v, g))
    ref = jax.block_until_ready(dense(q, k, v, g))
    errs = {}
    for name, a, b in zip(("o", "lse", "dq", "dk", "dv"), got, ref):
        a = np.asarray(a, np.float32)
        b = np.asarray(b, np.float32)
        check(np.isfinite(a).all(), f"flash {name} {shape}: non-finite")
        err = float(np.abs(a - b).max())
        if name == "lse":
            check(err <= FLASH_LSE_ABS_TOL,
                  f"flash lse {shape}: abs err {err:.3e} > "
                  f"{FLASH_LSE_ABS_TOL}")
            errs[name] = err
        else:
            rel = err / float(np.abs(b).max())
            check(rel <= FLASH_REL_TOL,
                  f"flash {name} {shape}: rel err {rel:.3e} > "
                  f"{FLASH_REL_TOL:.3e}")
            errs[name] = rel
    return {k_: float(f"{v_:.3e}") for k_, v_ in errs.items()}


def leg_lm(w, wf, dry):
    from horovod_tpu.core.topology import make_mesh
    from horovod_tpu.models.transformer import ParallelAxes

    n = jax.device_count()
    on_chip = not dry
    info = {}

    mesh = make_mesh(data=n)
    ax = ParallelAxes(data="data")
    losses, c, s = _lm_train(_lm_cfg(w, w["seq"]), mesh, ax,
                             w["per_chip"] * n, w["seq"], w["steps"],
                             on_chip)
    info[f"dp{n}"] = {"losses": losses, "compile_s": c, "step_s": s,
                      "batch": w["per_chip"] * n, "seq": w["seq"]}

    # One long-context step: beyond HVD_TPU_FLASH_RESIDENT_SEQ the
    # streaming kernels run, with remat and the chunked loss.
    losses, c, _ = _lm_train(
        _lm_cfg(w, w["long_seq"], remat=True, loss_chunk=w["loss_chunk"]),
        mesh, ax, n, w["long_seq"], 1, on_chip)
    info["long"] = {"losses": losses, "compile_s": c, "batch": n,
                    "seq": w["long_seq"]}

    info["flash_resident"] = dict(
        shape=wf["resident"], **_flash_check(wf["resident"],
                                             wf["block"], dry,
                                             qkv_entry=True))
    info["flash_streaming"] = dict(
        shape=wf["streaming"], **_flash_check(wf["streaming"],
                                              wf["block"], dry))

    if n >= 4 and n % 2 == 0:
        dp = n // 2
        mesh = make_mesh(data=dp, model=2)
        ax = ParallelAxes(data="data", model="model")
        losses, c, s = _lm_train(_lm_cfg(w, w["seq"]), mesh, ax,
                                 w["per_chip"] * dp, w["seq"], 3, on_chip)
        info[f"dp{dp}xtp2"] = {"losses": losses, "compile_s": c,
                               "step_s": s, "batch": w["per_chip"] * dp,
                               "seq": w["seq"]}
    info["peak_bytes"] = peak_bytes()
    return info


# ---------------------------------------------------------------------------
# Leg C — the eager path
# ---------------------------------------------------------------------------

def leg_eager(w):
    import torch

    import horovod_tpu as hvd
    from horovod_tpu.frontends import torch as hvd_torch

    n = hvd.size()
    check(n == jax.device_count(),
          f"hvd.size()={n} but jax sees {jax.device_count()} devices")
    t0 = time.perf_counter()
    x = jnp.arange(8.0)
    np.testing.assert_allclose(
        np.asarray(hvd.allreduce(x, average=False)), np.arange(8.0) * n)
    check(hvd.allgather(x).shape[0] == 8 * n, "allgather shape")
    np.testing.assert_allclose(np.asarray(hvd.broadcast(x, 0)),
                               np.arange(8.0))
    h = hvd.allreduce_async(x, average=True)
    deadline = time.monotonic() + 60.0
    while not hvd.poll(h):
        check(time.monotonic() < deadline, "allreduce_async never polled "
                                           "ready")
        time.sleep(0.001)
    np.testing.assert_allclose(np.asarray(hvd.synchronize(h)),
                               np.arange(8.0))
    t = torch.arange(8, dtype=torch.float32)
    hvd_torch.allreduce_(t, average=False)
    np.testing.assert_allclose(t.numpy(), np.arange(8.0) * n)
    first_s = time.perf_counter() - t0

    # One fused cycle: many async allreduces enqueued inside one tick
    # window fuse into megakernel launches.
    launches0 = hvd.metrics()["megakernel.launches"]["value"]
    xs = [jnp.full((w["elems"],), float(i + 1), jnp.float32)
          for i in range(w["fused"])]

    def cycle():
        hs = [hvd.allreduce_async(v, average=False, name=f"smoke.fused.{i}")
              for i, v in enumerate(xs)]
        return [hvd.synchronize(h) for h in hs]

    t0 = time.perf_counter()
    outs = cycle()
    jax.block_until_ready(outs)
    fused_first_s = time.perf_counter() - t0
    for i, o in enumerate(outs):
        check(float(o[0]) == (i + 1) * n and float(o[-1]) == (i + 1) * n,
              f"fused allreduce {i}: got {float(o[0])}, want {(i + 1) * n}")
    fused_s = timed_steps(cycle, 3)
    launches = hvd.metrics()["megakernel.launches"]["value"] - launches0
    check(launches >= 1, "no megakernel launched in the fused cycle")
    return {"size": n, "first_ops_s": round(first_s, 2),
            "fused_first_s": round(fused_first_s, 2),
            "fused_cycle_s": round(fused_s, 4),
            "megakernel_launches": int(launches),
            "peak_bytes": peak_bytes()}


# ---------------------------------------------------------------------------
# Leg D — the server answers
# ---------------------------------------------------------------------------

def _http(port, path, payload=None, timeout=300.0):
    data = None if payload is None else json.dumps(payload).encode()
    req = urllib.request.Request(f"http://127.0.0.1:{port}{path}",
                                 data=data)
    with urllib.request.urlopen(req, timeout=timeout) as resp:
        return resp.status, resp.read().decode()


def leg_serve(w, wl):
    import horovod_tpu as hvd
    from horovod_tpu.models.transformer import (init_transformer,
                                                serving_forward)
    from horovod_tpu.serving import InferenceEngine, LMServer

    cfg = _lm_cfg(wl, w["max_seq"])
    params = init_transformer(jax.random.PRNGKey(0), cfg)
    # As examples/serve_lm.py --serve builds them (tp=1: no mesh, so the
    # engine occupies jax.devices()[0]).
    engine = InferenceEngine(params, cfg, mesh=None, max_slots=w["slots"])
    rng = np.random.default_rng(11)
    prompts = [[int(t) for t in rng.integers(0, cfg.vocab_size, size=k)]
               for k in w["prompts"]]
    new = w["new"]
    before = hvd.metrics()

    t0 = time.perf_counter()
    server = LMServer(engine, port=0).start()
    warm_s = time.perf_counter() - t0
    try:
        port = server.port
        status, body = _http(port, "/healthz")
        check(status == 200 and engine.ready,
              f"/healthz after warm_start: {status} {body[:200]}")

        def generate(prompt):
            status, body = _http(port, "/generate",
                                 {"tokens": prompt, "max_tokens": new})
            check(status == 200, f"/generate: {status} {body[:200]}")
            out = json.loads(body)
            check(len(out["tokens"]) == new,
                  f"/generate returned {len(out['tokens'])} tokens, "
                  f"asked for {new}")
            return out

        t0 = time.perf_counter()
        first = generate(prompts[0])
        first_request_s = time.perf_counter() - t0

        # Two concurrent requests share the decode batch.
        results = {}

        def worker(i):
            try:
                results[i] = generate(prompts[i])
            except BaseException as e:  # noqa: BLE001 — re-raised below
                results[i] = e

        threads = [threading.Thread(target=worker, args=(i,))
                   for i in (1, 2)]
        for t in threads:
            t.start()
        for t in threads:
            t.join(timeout=600.0)
            check(not t.is_alive(), "a concurrent /generate never returned")
        for i in (1, 2):
            if isinstance(results[i], BaseException):
                raise results[i]

        # The longest prompt twice more: its header pages are in the
        # prefix cache now, so only the suffix is prefilled.
        t0 = time.perf_counter()
        hit = generate(prompts[2])
        hit_request_s = time.perf_counter() - t0
        hit_again = generate(prompts[2])
    finally:
        server.close()
    after = hvd.metrics()
    check(grew(before, after, "serving.prefix_hits") >= 2,
          "the repeated prompt did not hit the prefix cache")
    # Same executables, same cached pages, same tokens in: the two
    # prefix-hit runs must agree to the token on any backend.
    check(hit_again["tokens"] == hit["tokens"],
          "two identical prefix-hit requests returned different "
          "completions")

    # Logits: prefill + decode through the engine's executables against
    # the non-incremental forward, on a prompt whose header is cached.
    rows = []
    orig_dec, orig_pf = engine._decode_iteration, engine._prefill

    def dec(active):
        logits = orig_dec(active)
        rows.append(np.array(logits[active[0][0]], np.float32))
        return logits

    def pf(slot, r, *a, **kw):
        out = orig_pf(slot, r, *a, **kw)
        rows.append(np.array(out[2], np.float32))  # (token, tokens, last)
        return out

    engine._decode_iteration, engine._prefill = dec, pf
    req = engine.submit(prompts[1], max_new_tokens=new)
    engine.run_until_idle()
    engine._decode_iteration, engine._prefill = orig_dec, orig_pf
    direct = req.result(0)
    check(len(rows) == new, f"captured {len(rows)} logit rows, want {new}")

    # One batched reference forward over every completion (causal, so
    # right padding to a common length changes nothing before it).
    seqs = {"first": prompts[0] + first["tokens"],
            "concurrent_1": prompts[1] + results[1]["tokens"],
            "concurrent_2": prompts[2] + results[2]["tokens"],
            "prefix_hit": prompts[2] + hit["tokens"],
            "direct": prompts[1] + direct}
    width = max(len(v) for v in seqs.values())
    tokens = np.zeros((len(seqs), width), np.int32)
    for r, v in enumerate(seqs.values()):
        tokens[r, :len(v)] = v
    sf = jax.jit(serving_forward, static_argnums=(2, 3))
    ref = np.asarray(sf(engine.params, jnp.asarray(tokens), cfg,
                        engine.capacity), np.float32)
    scale = float(np.abs(ref).max())
    tol = SERVE_REL_TOL * scale

    p = len(prompts[1])
    row = list(seqs).index("direct")
    err = max(float(np.abs(got - ref[row, p - 1 + i]).max())
              for i, got in enumerate(rows))
    check(np.isfinite(err) and err <= tol,
          f"engine logits differ from serving_forward by {err:.3e} "
          f"(max |logit| {scale:.3e}, tolerance {SERVE_REL_TOL:.3e} "
          f"relative)")

    # Greedy completions: bitwise-equal rollouts are an XLA:CPU
    # contract.  Within the logit tolerance, every token served must be
    # a maximum of the reference row up to twice that tolerance (once
    # for each of the two programs).
    worst = 0.0
    for r, (name, seq) in enumerate(seqs.items()):
        p = len(seq) - new
        for i, tok in enumerate(seq[p:]):
            logits = ref[r, p - 1 + i]
            gap = float(logits.max() - logits[tok])
            worst = max(worst, gap)
            check(gap <= 2 * tol,
                  f"{name}: token {i} trails the reference maximum by "
                  f"{gap:.3e} > 2 x {tol:.3e}")
    return {
        "devices": [str(d) for d in engine._mesh_key()],
        "warm_start_s": round(warm_s, 2),
        "first_request_s": round(first_request_s, 3),
        "prefix_hit_request_s": round(hit_request_s, 3),
        "ttft_ms": first.get("ttft_ms"),
        "prefix_hits": int(grew(before, after, "serving.prefix_hits")),
        "prefills": int(grew(before, after, "serving.prefills")),
        "decode_iterations": int(grew(before, after,
                                      "serving.decode_iterations")),
        "cold_equals_prefix_hit": hit["tokens"] == results[2]["tokens"],
        "http_equals_direct": direct == results[1]["tokens"],
        "logits_rel_tol": SERVE_REL_TOL,
        "logits_max_abs_err": float(f"{err:.3e}"),
        "logits_max_abs": float(f"{scale:.3e}"),
        "greedy_worst_gap": float(f"{worst:.3e}"),
        "peak_bytes": peak_bytes(),
    }


def _leg_served_model(dry, builder, fixture, config_file, seed, engine_kw,
                      lengths, new, tol, stores_ok):
    """A configuration of the benchmark through ``InferenceEngine``: the
    model built from the seed by the benchmark's own builder, two requests,
    and the LOGITS the engine's own prefill and decode executables
    produced against the benchmark's plain float32 reference, by their
    root-mean-square over the reference's spread.  ``stores_ok(engine)``
    says what the cache manager must hold, and why."""
    import gc

    import horovod_tpu as hvd
    from benchmark import cells
    from horovod_tpu.serving import InferenceEngine

    path = os.path.join(cells.HERE, *(
        ("tests", "fixtures", "configs", fixture) if dry
        else ("configs", config_file)))
    with open(path) as f:
        config = json.load(f)
    m = config["model"]
    ref = cells.load_module("refs", config["ref"])
    cfg = builder.config_of(m)
    jax.clear_caches()
    gc.collect()
    t0 = time.perf_counter()
    params = builder.seeded_params(m, cfg, seed, ref)
    engine = InferenceEngine(params, cfg, mesh=None, **engine_kw)
    engine.warm_start()
    setup_s = time.perf_counter() - t0
    check(*stores_ok(engine))

    rows = {}
    orig_prefill, orig_decode = engine._prefill, engine._decode_iteration

    def prefill(slot, req, *a, **kw):
        out = orig_prefill(slot, req, *a, **kw)
        rows.setdefault(req.rid, []).append(np.asarray(out[2]))
        return out

    def decode(active):
        owners = {slot: req.rid for slot, req in active}
        logits = orig_decode(active)
        for slot, rid in owners.items():
            rows[rid].append(np.asarray(logits[slot]))
        return logits

    engine._prefill, engine._decode_iteration = prefill, decode
    rng = np.random.default_rng(seed % 1000)
    prompts = [[int(t) for t in rng.integers(0, m["vocab_size"], size=k)]
               for k in lengths]
    before = hvd.metrics()
    t0 = time.perf_counter()
    reqs = [engine.submit(p, max_new_tokens=new) for p in prompts]
    engine.run_until_idle()
    serve_s = time.perf_counter() - t0
    after = hvd.metrics()
    tokens = [r.result(0) for r in reqs]
    check(all(len(t) == new for t in tokens), "every request served whole")
    peak = peak_bytes()
    del engine
    gc.collect()

    want = ref.served_logits(m, params, [p + t for p, t in
                                         zip(prompts, tokens)], "f32")
    rms, worst = [], 0.0
    for p, r, w in zip(prompts, reqs, want):
        got = np.stack(rows[r.rid])
        w = w[len(p) - 1:len(p) - 1 + new]
        rms.append(float(np.sqrt(np.mean((got - w) ** 2)) / w.std()))
        worst = max(worst, float(np.abs(got - w).max() / w.std()))
    check(max(rms) <= tol,
          f"logits through the engine's stores differ from the reference: "
          f"rms/std {rms} > {tol}")
    return {"config": config["name"], "setup_s": round(setup_s, 1),
            "serve_s": round(serve_s, 2), "logit_rms_over_std": rms,
            "logit_max_over_std": worst, "peak_bytes": peak}, before, after


def leg_latent_moe(dry):
    """Leg E.  The benchmark's own configuration, builder and reference:
    the cut model at the published widths on the chip, its toy fixture
    in the dry run."""
    from benchmark.builders import latent_moe

    out, before, after = _leg_served_model(
        dry, latent_moe, "tiny-axk1.json", "axk1-ep16.json", 2_400_000_027,
        dict(max_slots=4 if dry else 64, page_size=8 if dry else 16,
             capacity=256 if dry else 4096),
        (40, 100) if dry else (700, 150), 8 if dry else 24,
        LATENT_RMS_REL_TOL,
        lambda e: (not e.cache.prefix_enabled and len(e.cache.pages) == 1,
                   "the latent model caches one store, prefix cache off"))
    pairs = grew(before, after, "serving.moe_assignments")
    check(pairs > 0, "the decode program's expert counts reach the counters")
    return dict(out, pairs_on_held_experts=pairs)


def leg_hybrid_ssm(dry):
    """Leg F.  The decoder-hybrid-decoder of benchmark/configs/
    phi4-mini-flash.json, whole and uncut on the chip (its toy fixture in
    the dry run): one request longer than the window and one shorter, so
    the rings wrap in the prefill of one and under the decode of
    neither; on the chip the scan kernel runs compiled."""
    from benchmark.builders import hybrid_ssm

    out, before, after = _leg_served_model(
        dry, hybrid_ssm, "tiny-phi4flash.json", "phi4-mini-flash.json",
        3_100_000_031,
        dict(max_slots=4 if dry else 64, page_size=4 if dry else 16,
             capacity=128 if dry else 6144),
        (40, 6) if dry else (700, 150), 8 if dry else 24,
        HYBRID_RMS_REL_TOL,
        lambda e: (not e.cache.prefix_enabled and len(e.cache.pages) == 2
                   and e.cache.group_names == ("full", "window")
                   and len(e.cache.slot_state) == 2
                   and e.cache.n_layers == 1,
                   "one full and one window group of pages and two "
                   "per-slot stores, prefix cache off"))
    shared = grew(before, after, "serving.shared_kv_tokens")
    check(shared > 0 and grew(before, after, "serving.state_slot_resets") == 2,
          "the cache manager counts what it holds and replaces")
    return dict(out, shared_kv_tokens=shared)


def leg_shortcut_moe(dry):
    """Leg G.  The shortcut-connected mixture of experts of
    benchmark/configs/longcat-flash-omni-ep32.json, the cut model at the
    published widths on the chip (its toy fixture in the dry run): the
    one latent store holds two layers a decoder layer, and the decode
    program's counts of pairs on zero-compute experts and of all pairs
    reach the counters."""
    from benchmark.builders import shortcut_moe

    out, before, after = _leg_served_model(
        dry, shortcut_moe, "tiny-longcat.json",
        "longcat-flash-omni-ep32.json", 3_300_000_033,
        dict(max_slots=4 if dry else 128, page_size=8 if dry else 16,
             capacity=256 if dry else 2048),
        (40, 100) if dry else (700, 150), 8 if dry else 24,
        SHORTCUT_RMS_REL_TOL,
        lambda e: (not e.cache.prefix_enabled and len(e.cache.pages) == 1
                   and e.cache.n_layers == 2 * len(e.params["layers"]),
                   "one latent store, two layers of it a decoder layer, "
                   "prefix cache off"))
    routed = grew(before, after, "serving.moe_routed_pairs")
    zero = grew(before, after, "serving.moe_zero_assignments")
    held = grew(before, after, "serving.moe_assignments")
    check(0 < zero < routed and 0 < held and zero + held <= routed,
          "the decode program's counts of zero-compute and of all pairs "
          "reach the counters")
    return dict(out, pairs_on_held_experts=held, pairs_on_zero_experts=zero,
                pairs_routed=routed)


def leg_mamba2_hybrid(dry):
    """Leg H.  The Mamba-2 hybrid of benchmark/configs/
    granite-4.0-h-micro.json, whole and uncut on the chip (its toy
    fixture in the dry run): one prompt that crosses two edges of the
    chunked scan and one inside its first chunk, then decode through the
    one-step kernel with 62 of the 64 slots idle, whose state the kernel
    must neither move nor count."""
    from benchmark.builders import mamba2_hybrid

    out, before, after = _leg_served_model(
        dry, mamba2_hybrid, "tiny-granite4h.json", "granite-4.0-h-micro.json",
        3_900_000_039,
        dict(max_slots=4 if dry else 64, page_size=4 if dry else 16,
             capacity=128 if dry else 3072),
        (40, 6) if dry else (700, 150), 8 if dry else 24,
        MAMBA2_RMS_REL_TOL,
        lambda e: (not e.cache.prefix_enabled and len(e.cache.pages) == 2
                   and len(e.cache.slot_state) == 2
                   and e.cache.n_layers == e.model.n_attention,
                   "a paged layer an attention layer and two per-slot "
                   "stores (no scratch view), prefix cache off"))
    moved = grew(before, after, "serving.state_bytes_moved")
    check(moved > 0 and grew(before, after, "serving.shared_kv_tokens") > 0
          and grew(before, after, "serving.state_slot_resets") == 2,
          "the model counts what a decode iteration attends and moves")
    return dict(out, state_bytes_moved=moved)


def leg_latent_paged_attn(dry):
    """Leg I.  One decode step's attention over every cache layer of a
    seeded store through ``latent_moe.paged_attend`` (the kernel; in the
    dry run its interpreter) and through ``gathered_attend`` (the plain
    twin: every slot's table row gathered whole), at the two latent cells'
    shapes and the slots alive that PERF.md gives for them, lengths drawn
    from the cells' mixes."""
    from horovod_tpu.models import latent_moe as lm
    from horovod_tpu.ops import latent_paged_attention as lpa

    cfg = (lm.LatentMoEConfig(num_attention_heads=8, kv_lora_rank=96,
                              qk_rope_head_dim=32, qk_nope_head_dim=16,
                              v_head_dim=16, dtype=jnp.float32)
           if dry else lm.LatentMoEConfig())
    page = 8 if dry else 16
    shapes = ({"toy": dict(slots=8, pps=32, layers=2, alive=3, median=60)}
              if dry else
              {"longcat-serve-turns": dict(slots=128, pps=128, layers=8,
                                           alive=47, median=440),
               "axk1-serve-decode": dict(slots=64, pps=256, layers=7,
                                         alive=14, median=610)})
    h, w, dt = cfg.num_attention_heads, cfg.entry_width, cfg.dtype
    out = {}
    for name, c in shapes.items():
        slots, pps, layers = c["slots"], c["pps"], c["layers"]
        rng = np.random.RandomState(40)
        lengths = np.full(slots, -1, np.int32)
        lengths[rng.choice(slots, c["alive"], replace=False)] = np.clip(
            rng.lognormal(np.log(c["median"]), 0.8, c["alive"]), 1,
            pps * page - 1)
        ks = jax.random.split(jax.random.PRNGKey(40), 5)
        # A slot's pages in order, the slots' runs shuffled: what the
        # free list hands a fresh engine.
        table = jnp.asarray(1 + rng.permutation(slots)[:, None] * pps
                            + np.arange(pps)[None, :], jnp.int32)
        args = (jnp.asarray(lengths),
                jax.random.normal(ks[0], (layers, slots * pps + 1, page, w),
                                  dt), table,
                jax.random.normal(ks[1], (slots, 1, h, cfg.qk_nope_head_dim),
                                  dt),
                jax.random.normal(ks[2], (slots, 1, h, cfg.qk_rope_head_dim),
                                  dt),
                jax.random.normal(ks[3], (slots, 1, w), dt),
                # The weights an ARGUMENT, as the engine's are.
                {"w_ukv": (jax.random.normal(
                    ks[4], (cfg.kv_lora_rank, h * (cfg.qk_nope_head_dim
                                                   + cfg.v_head_dim)))
                           * 0.04).astype(dt)})

        def every_layer(make):
            def f(lengths, store, table, q_nope, q_rope, entry, ap):
                attend = make(lengths, store, table)
                return jnp.stack([attend(layer, q_nope, q_rope, entry, ap)
                                  for layer in range(layers)])
            return jax.jit(f)

        kernel = every_layer(lambda n, s, t: lm.paged_attend(
            n, s, t, cfg, True if dry else None))
        twin = every_layer(lambda n, s, t: lm.gathered_attend(n, s, t, cfg))
        got = kernel(*args).astype(jnp.float32)
        want = twin(*args).astype(jnp.float32)
        on = lengths >= 0
        err = float(jnp.max(jnp.abs(got - want)[:, on]))
        top = float(jnp.max(jnp.abs(want)))
        check(err <= (1e-5 if dry else PAGED_REL_TOL) * top,
              f"{name}: kernel and plain twin differ by {err} of {top}")
        check(not np.asarray(got)[:, ~on].any(),
              f"{name}: an idle slot's attention is not zero")
        live_bytes = (int(lengths[on].sum()) * w * jnp.dtype(dt).itemsize
                      * layers)
        out[name] = {
            "alive": int(on.sum()), "live_tokens": int(lengths[on].sum()),
            "kernel_tokens_a_layer": lpa.tokens_read(lengths, page),
            "max_err_over_max": err / top,
            "live_bytes_at_819_gb_s_ms": round(live_bytes / 819e9 * 1e3, 4)}
        if not dry:       # a time off the chip is no time
            out[name]["kernel_ms"] = round(
                timed_steps(lambda: kernel(*args), 5) * 1e3, 3)
    return out


# ---------------------------------------------------------------------------

def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--dry-run", action="store_true",
                    help="toy widths on whatever platform jax has; the "
                         "result line says ok=false and names the "
                         "platform (for the test suite, never a pass)")
    ap.add_argument("--legs", default="ABCDEFGHI",
                    help="subset of legs to run while debugging, e.g. "
                         "AD; anything short of all nine is not a pass")
    args = ap.parse_args()
    dry = args.dry_run

    from benchmark import device as bench_device

    # A TPU whose device_kind is in the peak table, or no run at all.
    device = (bench_device.device_info() if dry
              else bench_device.require_tpu(1))
    if dry:
        # Streaming kernels at a toy length, and the stream schedule
        # (auto is the one-program step on every mesh one process
        # owns: no other leg would run the bucketed path).
        os.environ["HVD_TPU_FLASH_RESIDENT_SEQ"] = "128"
        os.environ["HVD_TPU_OVERLAP"] = "on"

    cache = {"requests": 0, "hits": 0, "misses": 0}

    def on_event(name, **kw):
        if name == "/jax/compilation_cache/compile_requests_use_cache":
            cache["requests"] += 1
        elif name == "/jax/compilation_cache/cache_hits":
            cache["hits"] += 1
        elif name == "/jax/compilation_cache/cache_misses":
            cache["misses"] += 1

    jax.monitoring.register_event_listener(on_event)

    import jaxlib

    import horovod_tpu as hvd
    from horovod_tpu.core import state as hvd_state

    hvd.init()
    cache_dir = hvd_state.compile_cache_dir()

    def entries():
        if not cache_dir or not os.path.isdir(cache_dir):
            return 0
        return len(os.listdir(cache_dir))

    entries_before = entries()
    try:
        import libtpu

        libtpu_version = getattr(libtpu, "__version__", "unknown")
    except ImportError:
        libtpu_version = None

    w = DRY if dry else CHIP
    legs = {}
    plan = (("A_resnet_dp", lambda: leg_resnet(w["resnet"], dry)),
            ("B_lm_pallas", lambda: leg_lm(w["lm"], w["flash"], dry)),
            ("C_eager", lambda: leg_eager(w["eager"])),
            ("D_serve", lambda: leg_serve(w["serve"], w["lm"])),
            ("E_latent_moe", lambda: leg_latent_moe(dry)),
            ("F_hybrid_ssm", lambda: leg_hybrid_ssm(dry)),
            ("G_shortcut_moe", lambda: leg_shortcut_moe(dry)),
            ("H_mamba2_hybrid", lambda: leg_mamba2_hybrid(dry)),
            ("I_latent_paged_attn", lambda: leg_latent_paged_attn(dry)))
    for name, fn in plan:
        if name[0] not in args.legs.upper():
            continue
        t0 = time.perf_counter()
        print(f"chip_smoke: leg {name} ...", file=sys.stderr, flush=True)
        try:
            legs[name] = fn()
        except Exception:  # noqa: BLE001 — boundary: name the leg, fail
            traceback.print_exc(file=sys.stderr)
            print(f"chip_smoke: leg {name} FAILED after "
                  f"{time.perf_counter() - t0:.1f}s", file=sys.stderr)
            hvd.shutdown()
            return 1
        legs[name]["wall_s"] = round(time.perf_counter() - t0, 2)
        print(f"chip_smoke: leg {name} ok {json.dumps(legs[name])}",
              file=sys.stderr, flush=True)
    hvd.shutdown()

    # The report: orientation for whoever reads the run, second to last.
    report = {
        "platform": device["platform"],
        "device_kind": device["kind"],
        "n": device["count"],
        "versions": {"jax": jax.__version__, "jaxlib": jaxlib.__version__,
                     "libtpu": libtpu_version},
        "native_built": hvd.native_built(),
        "compile_cache": dict(cache, dir=cache_dir,
                              entries_before=entries_before,
                              entries_after=entries()),
        "wall_s": round(time.perf_counter() - T_START, 1),
        "legs": legs,
    }
    if dry:
        report["dry_run"] = "passed"
    report["claim"] = None
    print(json.dumps(report))
    # The verdict: the last line, these two keys and no others.
    print(json.dumps({"ok": not dry and len(legs) == len(plan),
                      "device": device}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
