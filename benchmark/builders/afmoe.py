"""The ``afmoe`` decoder (``models/afmoe.py``: gated grouped-query attention
in sliding and full layers by the published ``layer_types``, leading dense
layers, then expert layers of which this chip holds a share) served through
the program's normal entry points: ``InferenceEngine`` + ``LMServer``
answering ``/generate`` over HTTP inside this process, the same engine,
scheduler and run-ahead decode loop as the other serving builders build for
theirs, its paged store in two layer groups whose pools the memory planner
sizes from the traffic file's byte budget."""

from __future__ import annotations

import jax
import jax.numpy as jnp

from benchmark.builders.base import check_tree
from benchmark.builders.lm import ServeProgram


def config_of(m: dict):
    """The program's config from a configuration file's ``model``: the
    published keys under their names."""
    from horovod_tpu.models.afmoe import AfmoeConfig

    if (m["model_type"] != "afmoe" or m["hidden_act"] != "silu"
            or m["score_func"] != "sigmoid" or m["tie_word_embeddings"]
            or m["rope_scaling"] is not None
            or m["num_expert_groups"] != 1 or m["n_group"] != 1
            or m["topk_group"] != 1 or m["num_limited_groups"] != 1):
        raise ValueError("the program serves afmoe with SwiGLU experts, "
                         "sigmoid scores in one group, an untied head and "
                         "unscaled rotary positions")
    return AfmoeConfig(
        vocab_size=m["vocab_size"], hidden_size=m["hidden_size"],
        intermediate_size=m["intermediate_size"],
        moe_intermediate_size=m["moe_intermediate_size"],
        num_hidden_layers=m["num_hidden_layers"],
        num_dense_layers=m["num_dense_layers"],
        layer_types=tuple(m["layer_types"]),
        num_attention_heads=m["num_attention_heads"],
        num_key_value_heads=m["num_key_value_heads"],
        head_dim=m["head_dim"], sliding_window=m["sliding_window"],
        rope_theta=m["rope_theta"], rms_norm_eps=m["rms_norm_eps"],
        num_experts=m["n_routed_experts_published"],
        num_experts_per_tok=m["num_experts_per_tok"],
        num_shared_experts=m["num_shared_experts"],
        route_norm=m["route_norm"], route_scale=m["route_scale"],
        mup_enabled=m["mup_enabled"],
        max_position_embeddings=m["max_position_embeddings"],
        experts_held=m["n_routed_experts"],
        expert_offset=m["expert_offset"], dtype=jnp.dtype(m["dtype"]),
        **({"decode_chunk_tokens": m["decode_chunk_tokens"]}
           if "decode_chunk_tokens" in m else {}))


def seeded_params(m: dict, cfg, seed: int, ref):
    from horovod_tpu.models.afmoe import init_afmoe

    params = ref.init_params(m, seed)
    check_tree(params, jax.eval_shape(
        lambda: init_afmoe(jax.random.PRNGKey(0), cfg)),
        "afmoe parameters")
    return params


def build_serve(config: dict, traffic: dict, seed: int, chips: int, ref):
    from horovod_tpu.serving import InferenceEngine, LMServer

    m = config["model"]
    e = traffic["engine"]
    cfg = config_of(m)       # before the weights: a program without the
    params = seeded_params(m, cfg, seed, ref)    # family fails at once
    engine = InferenceEngine(
        params, cfg, mesh=None, max_slots=e["slots"],
        page_size=e["page_size"], capacity=e["capacity"],
        kv_pool_bytes=e.get("kv_pool_bytes"),
        kv_expected_tokens=e.get("kv_expected_tokens"))
    server = LMServer(engine, port=0).start()
    return ServeProgram(engine, server, m["vocab_size"])
