"""The latent-attention mixture-of-experts decoder WITH a lightning indexer
(``models/latent_moe.py``, ``deepseek_v32``: learned sparse attention,
group-limited expert choice) served through the program's normal entry
points: ``InferenceEngine`` + ``LMServer`` answering ``/generate`` over HTTP
inside this process, the same engine, scheduler and page table as the other
serving builders build for theirs; its two stores (latent entries, index
keys) share ONE page pool that the memory planner sizes from the traffic
file's byte budget."""

from __future__ import annotations

import dataclasses

import jax

from benchmark.builders import latent_moe as _family
from benchmark.builders.base import check_tree
from benchmark.builders.lm import ServeProgram


def config_of(m: dict):
    """The family's config from a configuration file's ``model``, and the
    keys this model adds to it."""
    if m["model_type"] != "deepseek_v32" or m["topk_method"] != "noaux_tc":
        raise ValueError("the program serves deepseek_v32 with group-limited "
                         "expert choice (noaux_tc)")
    cfg = _family.config_of(m)
    if not hasattr(cfg, "index_topk"):
        raise ValueError("this program's latent attention has no indexer")
    return dataclasses.replace(
        cfg, index_n_heads=m["index_n_heads"],
        index_head_dim=m["index_head_dim"], index_topk=m["index_topk"],
        group_limited=True, n_group=m["n_group"],
        topk_group=m["topk_group"])


def seeded_params(m: dict, cfg, seed: int, ref):
    from horovod_tpu.models.latent_moe import init_latent_moe

    params = ref.init_params(m, seed)
    check_tree(params, jax.eval_shape(
        lambda: init_latent_moe(jax.random.PRNGKey(0), cfg)),
        "sparse latent MoE parameters")
    return params


def build_serve(config: dict, traffic: dict, seed: int, chips: int, ref):
    from horovod_tpu.serving import InferenceEngine, LMServer

    m = config["model"]
    e = traffic["engine"]
    cfg = config_of(m)       # before the weights: a program without the
    params = seeded_params(m, cfg, seed, ref)    # indexer fails at once
    engine = InferenceEngine(
        params, cfg, mesh=None, max_slots=e["slots"],
        page_size=e["page_size"], capacity=e["capacity"],
        kv_pool_bytes=e.get("kv_pool_bytes"),
        kv_expected_tokens=e.get("kv_expected_tokens"))
    server = LMServer(engine, port=0).start()
    return ServeProgram(engine, server, m["vocab_size"])
