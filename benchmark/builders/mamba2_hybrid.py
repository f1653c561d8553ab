"""The Mamba-2 hybrid decoder (``models/mamba2_hybrid.py``: state-space
and grouped-query attention layers by the published ``layer_types``)
served through the program's normal entry points: ``InferenceEngine`` +
``LMServer`` answering ``/generate`` over HTTP inside this process, the
same engine, scheduler, page table and run-ahead decode loop as the other
serving builders build for theirs."""

from __future__ import annotations

import jax
import jax.numpy as jnp

from benchmark.builders.base import check_tree
from benchmark.builders.lm import ServeProgram


def config_of(m: dict):
    """The program's config from a configuration file's ``model``: the
    published keys under their names."""
    from horovod_tpu.models.mamba2_hybrid import Mamba2HybridConfig

    if (m["model_type"] != "granitemoehybrid" or m["num_local_experts"]
            or m["hidden_act"] != "silu" or not m["tie_word_embeddings"]
            or m["attention_bias"] or m["mamba_proj_bias"]
            or not m["mamba_conv_bias"]
            or m["position_embedding_type"] != "nope"
            or m["normalization_function"] != "rmsnorm"
            or m["shared_intermediate_size"] != m["intermediate_size"]):
        raise ValueError("the program serves granitemoehybrid without "
                         "experts: SwiGLU with silu, RMSNorm, a tied head, "
                         "no positions, no bias but the convolution's")
    return Mamba2HybridConfig(
        vocab_size=m["vocab_size"], hidden_size=m["hidden_size"],
        intermediate_size=m["intermediate_size"],
        num_hidden_layers=m["num_hidden_layers"],
        layer_types=tuple(m["layer_types"]),
        num_attention_heads=m["num_attention_heads"],
        num_key_value_heads=m["num_key_value_heads"],
        attention_multiplier=m["attention_multiplier"],
        embedding_multiplier=m["embedding_multiplier"],
        residual_multiplier=m["residual_multiplier"],
        logits_scaling=m["logits_scaling"], rms_norm_eps=m["rms_norm_eps"],
        mamba_n_heads=m["mamba_n_heads"], mamba_d_head=m["mamba_d_head"],
        mamba_d_state=m["mamba_d_state"], mamba_d_conv=m["mamba_d_conv"],
        mamba_expand=m["mamba_expand"], mamba_n_groups=m["mamba_n_groups"],
        mamba_chunk_size=m["mamba_chunk_size"],
        max_position_embeddings=m["max_position_embeddings"],
        dtype=jnp.dtype(m["dtype"]),
        **({"decode_chunk_tokens": m["decode_chunk_tokens"]}
           if "decode_chunk_tokens" in m else {}))


def seeded_params(m: dict, cfg, seed: int, ref):
    from horovod_tpu.models.mamba2_hybrid import init_mamba2_hybrid

    params = ref.init_params(m, seed)
    check_tree(params, jax.eval_shape(
        lambda: init_mamba2_hybrid(jax.random.PRNGKey(0), cfg)),
        "Mamba-2 hybrid parameters")
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
