"""The decoder-hybrid-decoder (``models/hybrid_ssm.py``: state-space,
window, full, gated-memory and cross layers in one stack) served through
the program's normal entry points: ``InferenceEngine`` + ``LMServer``
answering ``/generate`` over HTTP inside this process, the same engine,
scheduler, page table and run-ahead decode loop as ``builders/lm.py`` and
``builders/latent_moe.py`` build for theirs."""

from __future__ import annotations

import jax
import jax.numpy as jnp

from benchmark.builders.base import check_tree
from benchmark.builders.lm import ServeProgram


def config_of(m: dict):
    """The program's config from a configuration file's ``model``: the
    published keys under their names, the state-space sizes the file
    lists as ``assumed`` beside them."""
    from horovod_tpu.models.hybrid_ssm import HybridSSMConfig

    if (m["hidden_act"] != "silu" or not m["tie_word_embeddings"]
            or m["mlp_bias"] or m["lm_head_bias"]):
        raise ValueError("the program computes SwiGLU with silu, a tied "
                         "head and no bias in the MLP or the head only")
    return HybridSSMConfig(
        vocab_size=m["vocab_size"], hidden_size=m["hidden_size"],
        intermediate_size=m["intermediate_size"],
        num_hidden_layers=m["num_hidden_layers"],
        num_attention_heads=m["num_attention_heads"],
        num_key_value_heads=m["num_key_value_heads"],
        sliding_window=m["sliding_window"],
        layer_norm_eps=m["layer_norm_eps"], mb_per_layer=m["mb_per_layer"],
        d_state=m["d_state"], d_conv=m["d_conv"], expand=m["expand"],
        dt_rank=m["dt_rank"],
        max_position_embeddings=m["max_position_embeddings"],
        dtype=jnp.dtype(m["dtype"]),
        **({"decode_chunk_tokens": m["decode_chunk_tokens"]}
           if "decode_chunk_tokens" in m else {}))


def seeded_params(m: dict, cfg, seed: int, ref):
    from horovod_tpu.models.hybrid_ssm import init_hybrid_ssm

    params = ref.init_params(m, seed)
    check_tree(params, jax.eval_shape(
        lambda: init_hybrid_ssm(jax.random.PRNGKey(0), cfg)),
        "hybrid state-space parameters")
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
