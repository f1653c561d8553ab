"""The latent-attention mixture-of-experts decoder (``models/latent_moe.py``)
served through the program's normal entry points: ``InferenceEngine`` +
``LMServer`` answering ``/generate`` over HTTP inside this process, the
same engine, scheduler, page tables and view ladder as ``builders/lm.py``
builds for the dense decoder."""

from __future__ import annotations

import jax
import jax.numpy as jnp

from benchmark.builders.base import check_tree
from benchmark.builders.lm import ServeProgram


def config_of(m: dict):
    """The program's config from a configuration file's ``model``: the
    published keys under their names; ``n_routed_experts`` counts the
    experts HELD, ``n_routed_experts_published`` is the router's width."""
    from horovod_tpu.models.latent_moe import LatentMoEConfig

    r = m["rope_scaling"]
    if r["type"] != "yarn" or m["scoring_func"] != "sigmoid":
        raise ValueError("the program computes YaRN rotary positions and "
                         "sigmoid router scores only")
    return LatentMoEConfig(
        vocab_size=m["vocab_size"], hidden_size=m["hidden_size"],
        intermediate_size=m["intermediate_size"],
        moe_intermediate_size=m["moe_intermediate_size"],
        num_hidden_layers=m["num_hidden_layers"],
        first_k_dense_replace=m["first_k_dense_replace"],
        num_attention_heads=m["num_attention_heads"],
        q_lora_rank=m["q_lora_rank"], kv_lora_rank=m["kv_lora_rank"],
        qk_nope_head_dim=m["qk_nope_head_dim"],
        qk_rope_head_dim=m["qk_rope_head_dim"], v_head_dim=m["v_head_dim"],
        n_routed_experts=m["n_routed_experts_published"],
        n_shared_experts=m["n_shared_experts"],
        num_experts_per_tok=m["num_experts_per_tok"],
        routed_scaling_factor=m["routed_scaling_factor"],
        norm_topk_prob=m["norm_topk_prob"], rms_norm_eps=m["rms_norm_eps"],
        rope_theta=float(m["rope_theta"]), rope_factor=float(r["factor"]),
        beta_fast=float(r["beta_fast"]), beta_slow=float(r["beta_slow"]),
        mscale=float(r["mscale"]), mscale_all_dim=float(r["mscale_all_dim"]),
        original_max_position_embeddings=r[
            "original_max_position_embeddings"],
        max_position_embeddings=m["max_position_embeddings"],
        dtype=jnp.dtype(m["dtype"]), experts_held=m["n_routed_experts"],
        expert_offset=m["expert_offset"])


def seeded_params(m: dict, cfg, seed: int, ref):
    from horovod_tpu.models.latent_moe import init_latent_moe

    params = ref.init_params(m, seed)
    check_tree(params, jax.eval_shape(
        lambda: init_latent_moe(jax.random.PRNGKey(0), cfg)),
        "latent MoE parameters")
    return params


def build_serve(config: dict, traffic: dict, seed: int, chips: int, ref):
    from horovod_tpu.serving import InferenceEngine, LMServer

    m = config["model"]
    e = traffic["engine"]
    cfg = config_of(m)
    params = seeded_params(m, cfg, seed, ref)
    engine = InferenceEngine(params, cfg, mesh=None, max_slots=e["slots"],
                             page_size=e["page_size"], capacity=e["capacity"])
    server = LMServer(engine, port=0).start()
    return ServeProgram(engine, server, m["vocab_size"])

