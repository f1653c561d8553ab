"""Read, by hand, whether a cell's ``correct`` can SEE a learned sparse
attention's selection, in ONE process on the chip.

    python -m benchmark.tools.selection_controls --workload <cell> \
        --seeds 1,2 [--seconds 12] [--overlap-tokens 8192]

For every seed, on the sample a short window at the cell's own load leaves
(``serve.check_sample``, as ``tools/calibrate.py`` takes it):

* ``program``: the served tokens' gap against the float32 reference, what a
  sound run reads;
* ``all`` / ``recent``: the reference computed under another selection rule
  (every cached position; the last ``index_topk``) and put in the program's
  place, as ``calibrate.py`` puts a lower precision there: the tokens that
  rule would serve, judged against the TRUE reference.  Both must lie over
  the cell's limit, else the check cannot tell the model's selection from
  none;
* ``selected_overlap``: for one prompt of ``--overlap-tokens`` tokens, layer
  by layer, the share of the positions the float32 reference selects that
  the program's own prefill (served type, its own hidden states) selects
  too, over the rows where the selection binds.  What is missing flipped at
  the ``index_topk``-th place on a rounding.

Chip only, like a run.  The configuration's reference takes ``select=``
(``benchmark/refs/deepseek-v32-exp-ep16.py``).
"""

from __future__ import annotations

import argparse
import gc
import json
import sys

import jax
import jax.numpy as jnp
import numpy as np

from benchmark import cells, compare, device, harness, loadgen, serve
from benchmark.cachecount import CacheCounter


def control_gap(ref, model, params, planned, results, sample, select):
    """``serve.served_gap`` with the tokens of the reference under
    ``select`` in the served tokens' place."""
    seqs = [planned[i].prompt + results[i].tokens for i in sample]
    best = ref.served_logits(model, params, seqs, "f32")
    other = ref.served_logits(model, params, seqs, "f32", select=select)
    worst = 0.0
    for k, i in enumerate(sample):
        p, t = len(planned[i].prompt), len(results[i].tokens)
        rows = best[k][p - 1:p - 1 + t]
        tokens = other[k][p - 1:p - 1 + t].argmax(axis=-1).tolist()
        worst = max(worst, max(compare.served_token_gaps(rows, tokens)))
    return worst


def selected_overlap(ref, model, cfg, params, tokens):
    """Per layer: ``|reference's selection & program's| / |reference's|``
    over the rows past ``index_topk``, and how many positions a row lost
    on average."""
    from horovod_tpu.models import latent_moe as lm

    top = model["index_topk"]
    toks = jnp.asarray(tokens, jnp.int32)[None]
    s = toks.shape[1]
    pos = jnp.arange(s, dtype=jnp.int32)
    grabbed = []

    def attend(layer, h, ap):
        c_q, entry = lm.mla_latents(h, ap, cfg, pos[None])
        k_i, w_i = lm.index_keys(h, ap, cfg, pos[None])
        allowed = lm.prefill_selection(c_q[0], k_i[0], w_i[0], ap, cfg, pos)
        grabbed.append(np.asarray(allowed))
        y = lm.selected_rebuilt_attention(c_q[0], entry[0], allowed, ap, cfg,
                                          pos)
        return y[None], (entry, k_i)

    # Un-jitted: the layers' pieces run as the programs they are, and the
    # masks come back between them.
    lm._layers(params, toks, pos[None], cfg, attend,
               jnp.ones((1, s), bool), rows=jnp.asarray([s - 1]))
    out = []
    eps = model["rms_norm_eps"]
    with jax.default_matmul_precision("highest"):
        x = params["embed"][toks[0]].astype(jnp.float32)
        prog = ref._programs(json.dumps(model, sort_keys=True), "f32", "topk")
        for i, lp in enumerate(params["layers"]):
            ap = ref._f32(lp["attn"])
            h = ref._rms(x, ap["norm"], eps)
            c_q = ref._rms(jnp.dot(h, ap["w_dq"]), ap["q_norm"], eps)
            want = np.asarray(jax.jit(
                lambda ap, h, c_q: ref.selection_mask(
                    model, ap, h, c_q, "f32", "topk"))(ap, h, c_q))
            del ap, h, c_q
            got = grabbed[i]
            both = (want & got)[top:].sum()
            out.append({"layer": i,
                        "share": float(both / want[top:].sum()),
                        "lost_a_row": float((want[top:].sum() - both)
                                            / (s - top))})
            x = ref._layer(model, prog, lp, x,
                           i < model["first_k_dense_replace"])
    return out


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--seconds", type=float, default=12.0)
    ap.add_argument("--overlap-tokens", type=int, default=8192)
    args = ap.parse_args(argv)
    resolved = cells.resolve(cells.load_benchmark(), args.workload)
    config, traffic, ref = (resolved["config"], resolved["traffic"],
                            resolved["ref"])
    model = config["model"]
    chips = resolved["cell"]["chips"]
    device.require_tpu(chips)
    import horovod_tpu as hvd

    CacheCounter().install()
    hvd.init()
    build = cells.resolve_callable(config["serve_builder"])
    try:
        for seed in (int(s) for s in args.seeds.split(",") if s):
            prog = build(config, traffic, seed, chips, ref)
            try:
                loadgen.drive(loadgen.warmup_plan(
                    traffic, seed, model["vocab_size"]), prog.send)
                planned = loadgen.plan(traffic, args.seconds, seed,
                                       model["vocab_size"])
                results = loadgen.drive(planned, prog.send)
                params, cfg = prog.params(), prog.engine.cfg
            finally:
                prog.close()
            gc.collect()
            sample = serve.check_sample(planned, results, seed,
                                        traffic["check_requests"])
            out = {"seed": seed,
                   "failed": sum(1 for r in results if not r.ok),
                   "program": serve.served_gap(ref, model, params, planned,
                                               results, sample)}
            for rule in ("all", "recent"):
                out[rule] = control_gap(ref, model, params, planned,
                                        results, sample, rule)
            harness.say("selection", out)
            if args.overlap_tokens:
                tokens = np.random.default_rng([seed, 0x5E1]).integers(
                    0, model["vocab_size"], size=args.overlap_tokens)
                harness.say("selection", {
                    "seed": seed, "overlap_tokens": args.overlap_tokens,
                    "selected_overlap": selected_overlap(
                        ref, model, cfg, params, tokens.tolist())})
            del params
            gc.collect()
    finally:
        hvd.shutdown()
    return 0


if __name__ == "__main__":
    sys.exit(main())
