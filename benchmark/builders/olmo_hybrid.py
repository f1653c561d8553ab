"""The ``olmo_hybrid`` decoder (``models/olmo_hybrid.py``: gated delta-rule
linear attention and multi-head full attention by the published
``layer_types``) served through the program's normal entry points:
``InferenceEngine`` + ``LMServer`` answering ``/generate`` over HTTP inside
this process, the same engine, scheduler, page table and run-ahead decode
loop as the other serving builders build for theirs, its one paged group a
POOL the memory planner sizes from the traffic file's byte budget, beside
the per-slot state."""

from __future__ import annotations

import jax
import jax.numpy as jnp

from benchmark.builders.base import check_tree
from benchmark.builders.lm import ServeProgram


def config_of(m: dict):
    """The program's config from a configuration file's ``model``: the
    published keys under their names."""
    from horovod_tpu.models.olmo_hybrid import OlmoHybridConfig

    if (m["model_type"] != "olmo_hybrid" or m["hidden_act"] != "silu"
            or m["tie_word_embeddings"] or m["attention_bias"]
            or m["rope_parameters"]["rope_theta"] is not None):
        raise ValueError("the program serves olmo_hybrid with SwiGLU and "
                         "silu, an untied head, no bias and no rotary "
                         "positions (rope_theta null)")
    return OlmoHybridConfig(
        vocab_size=m["vocab_size"], hidden_size=m["hidden_size"],
        intermediate_size=m["intermediate_size"],
        num_hidden_layers=m["num_hidden_layers"],
        layer_types=tuple(m["layer_types"]),
        num_attention_heads=m["num_attention_heads"],
        num_key_value_heads=m["num_key_value_heads"],
        rms_norm_eps=m["rms_norm_eps"],
        linear_num_key_heads=m["linear_num_key_heads"],
        linear_num_value_heads=m["linear_num_value_heads"],
        linear_key_head_dim=m["linear_key_head_dim"],
        linear_value_head_dim=m["linear_value_head_dim"],
        linear_conv_kernel_dim=m["linear_conv_kernel_dim"],
        linear_allow_neg_eigval=m["linear_allow_neg_eigval"],
        max_position_embeddings=m["max_position_embeddings"],
        dtype=jnp.dtype(m["dtype"]),
        **({"linear_chunk_size": m["linear_chunk_size"]}
           if "linear_chunk_size" in m else {}))


def seeded_params(m: dict, cfg, seed: int, ref):
    from horovod_tpu.models.olmo_hybrid import init_olmo_hybrid

    params = ref.init_params(m, seed)
    check_tree(params, jax.eval_shape(
        lambda: init_olmo_hybrid(jax.random.PRNGKey(0), cfg)),
        "olmo_hybrid parameters")
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
