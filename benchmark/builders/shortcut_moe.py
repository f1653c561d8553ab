"""The shortcut-connected mixture-of-experts decoder
(``models/shortcut_moe.py``) served through the program's normal entry
points: ``InferenceEngine`` + ``LMServer`` answering ``/generate`` over
HTTP inside this process, the same engine, scheduler, page tables, view
ladder and run-ahead decode loop as ``builders/latent_moe.py`` builds for
its family."""

from __future__ import annotations

import jax
import jax.numpy as jnp

from benchmark.builders.base import check_tree
from benchmark.builders.lm import ServeProgram


def config_of(m: dict):
    """The program's config from a configuration file's ``model``: the
    published keys under their names; ``n_routed_experts`` counts the
    real experts HELD, ``n_routed_experts_published`` those the router
    scores before its ``zero_expert_num`` zero-compute outputs."""
    from horovod_tpu.models.shortcut_moe import ShortcutMoEConfig

    if (m["attention_method"] != "MLA" or m["zero_expert_type"] != "identity"
            or m["attention_bias"] or "rope_scaling" in m):
        raise ValueError("the program computes latent attention without "
                         "bias, plain rotary positions and identity "
                         "zero-compute experts only")
    return ShortcutMoEConfig(
        vocab_size=m["vocab_size"], hidden_size=m["hidden_size"],
        ffn_hidden_size=m["ffn_hidden_size"],
        expert_ffn_hidden_size=m["expert_ffn_hidden_size"],
        num_layers=m["num_layers"],
        num_attention_heads=m["num_attention_heads"],
        q_lora_rank=m["q_lora_rank"], kv_lora_rank=m["kv_lora_rank"],
        qk_nope_head_dim=m["qk_nope_head_dim"],
        qk_rope_head_dim=m["qk_rope_head_dim"], v_head_dim=m["v_head_dim"],
        mla_scale_q_lora=m["mla_scale_q_lora"],
        mla_scale_kv_lora=m["mla_scale_kv_lora"],
        n_routed_experts=m["n_routed_experts_published"],
        zero_expert_num=m["zero_expert_num"], moe_topk=m["moe_topk"],
        routed_scaling_factor=float(m["routed_scaling_factor"]),
        rms_norm_eps=m["rms_norm_eps"], rope_theta=float(m["rope_theta"]),
        max_position_embeddings=m["max_position_embeddings"],
        dtype=jnp.dtype(m["dtype"]), experts_held=m["n_routed_experts"],
        expert_offset=m["expert_offset"])


def seeded_params(m: dict, cfg, seed: int, ref):
    from horovod_tpu.models.shortcut_moe import init_shortcut_moe

    params = ref.init_params(m, seed)
    check_tree(params, jax.eval_shape(
        lambda: init_shortcut_moe(jax.random.PRNGKey(0), cfg)),
        "shortcut MoE parameters")
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
