"""The decoder-only LM, trained and served through the program's normal
entry points.

Training: ``make_mesh`` -> ``make_loss_fn`` -> ``make_parallel_train_step``
-> ``shard_parallel_batch`` (what ``examples/transformer_lm.py`` does).
Serving: ``InferenceEngine`` + ``LMServer`` answering ``/generate`` over
HTTP inside this process (what ``examples/serve_lm.py --serve`` builds).
"""

from __future__ import annotations

import http.client
import json
import time

import jax
import jax.numpy as jnp

from benchmark.builders.base import TrainProgram, check_tree
from benchmark.loadgen import Done, Planned


def _cfg(m: dict):
    from horovod_tpu.models.transformer import TransformerConfig

    return TransformerConfig(
        vocab_size=m["vocab_size"], d_model=m["n_embd"], n_heads=m["n_head"],
        n_layers=m["n_layer"], d_ff=m["n_inner"], max_seq_len=m["n_positions"],
        dtype=jnp.dtype(m["dtype"]), block_q=m["flash_block"],
        block_k=m["flash_block"])


def _seeded_params(m, cfg, seed, ref):
    from horovod_tpu.models.transformer import init_transformer

    params = ref.init_params(m, seed)
    check_tree(params, jax.eval_shape(
        lambda: init_transformer(jax.random.PRNGKey(0), cfg)),
        "transformer parameters")
    return params


class _AdamProgram(TrainProgram):
    def __init__(self, *a, b1):
        super().__init__(*a)
        self._b1 = b1

    def first_gradient(self):
        # Adam keeps mu_1 = (1 - b1) * g_1.
        for part in self.state[-1]:
            if hasattr(part, "mu"):
                return jax.tree_util.tree_map(
                    lambda m: m.astype(jnp.float32) / (1 - self._b1), part.mu)
        raise RuntimeError("no Adam moment in the optimizer state")


def build_train(config: dict, job: dict, seed: int, chips: int, ref):
    import optax
    from jax.sharding import PartitionSpec as P

    import horovod_tpu as hvd
    from horovod_tpu.core.topology import make_mesh
    from horovod_tpu.models.transformer import ParallelAxes, make_loss_fn
    from horovod_tpu.parallel.training import (make_parallel_train_step,
                                               shard_parallel_batch)

    m = config["model"]
    n = hvd.size()
    if n != chips:
        raise RuntimeError(f"hvd.size() is {n}, the cell asks for {chips}")
    cfg = _cfg(m)
    params = _seeded_params(m, cfg, seed, ref)
    mesh = make_mesh(data=n)
    loss_fn = make_loss_fn(cfg, ParallelAxes(data="data"),
                           mesh_axes=mesh.axis_names)
    o = job["optimizer"]
    opt = optax.adamw(o["learning_rate"], b1=o["b1"], b2=o["b2"],
                      eps=o["eps"], weight_decay=o["weight_decay"])
    step = make_parallel_train_step(loss_fn, opt, mesh, P("data", None))
    rows = job["per_chip_batch"] * n
    batch = shard_parallel_batch(ref.make_batch(m, job, seed, rows), mesh,
                                 P("data", None))
    return _AdamProgram(step, [params, opt.init(params)], batch,
                        rows * job["seq_len"],
                        {"schedule": "in-program psum", "chips": n},
                        b1=o["b1"])


class ServeProgram:
    """The served path: an engine behind ``LMServer``'s ``/generate``."""

    def __init__(self, engine, server, vocab_size):
        self.engine = engine
        self.server = server
        self.port = server.port
        self.vocab_size = vocab_size
        self.slots = engine.max_slots

    def send(self, p: Planned) -> Done:
        """One blocking ``/generate`` (greedy), as a client makes it."""
        body = json.dumps({"tokens": p.prompt, "max_tokens": p.max_tokens,
                           "timeout": 300.0})
        conn = http.client.HTTPConnection("127.0.0.1", self.port,
                                          timeout=330.0)
        try:
            conn.request("POST", "/generate", body=body,
                         headers={"Content-Type": "application/json"})
            resp = conn.getresponse()
            raw = resp.read()
            responded = time.perf_counter()
        finally:
            conn.close()
        d = Done(responded=responded, status=resp.status)
        try:
            out = json.loads(raw)
        except ValueError:
            d.error = f"unparseable body: {raw[:120]!r}"
            return d
        d.tokens = [int(t) for t in out.get("tokens") or []]
        d.ttft_ms, d.total_ms = out.get("ttft_ms"), out.get("total_ms")
        d.ok = (resp.status == 200 and len(d.tokens) == p.max_tokens
                and d.ttft_ms is not None and d.total_ms is not None)
        if not d.ok:
            d.error = str(out.get("error") or f"status {resp.status}, "
                          f"{len(d.tokens)} of {p.max_tokens} tokens")
        return d

    def params(self):
        return self.engine.params

    def close(self):
        if self.server is not None:
            self.server.close()
        self.server = self.engine = None


def build_serve(config: dict, traffic: dict, seed: int, chips: int, ref):
    from horovod_tpu.serving import InferenceEngine, LMServer

    m = config["model"]
    e = traffic["engine"]
    cfg = _cfg(m)
    params = _seeded_params(m, cfg, seed, ref)
    engine = InferenceEngine(params, cfg, mesh=None, max_slots=e["slots"],
                             page_size=e["page_size"], capacity=e["capacity"])
    server = LMServer(engine, port=0).start()
    return ServeProgram(engine, server, m["vocab_size"])
