"""Bytes and operations the latent-attention mixture-of-experts family
needs, counted from shapes and from what the program counted.

One decode iteration is bound by what it must READ: every matrix of the
attention, the dense layers, the shared experts, the routers and the head
once, the matrices of the routed experts that got a token, and the cached
entries of the sequences alive.  ``param_counts`` gives the parameter
counts by part; the configuration file's arithmetic is these numbers.  A
multiply-add counts as 2 operations.
"""

from __future__ import annotations

import math

BYTES = 2          # bfloat16 parameters, cache and activations


def param_counts(model: dict) -> dict:
    """Parameters by part; ``mla``, ``shared``, ``router`` and ``expert``
    (ONE routed expert) are a layer's."""
    d, f, fm = (model["hidden_size"], model["intermediate_size"],
                model["moe_intermediate_size"])
    h_n, rq, rkv = (model["num_attention_heads"], model["q_lora_rank"],
                    model["kv_lora_rank"])
    nope, rp, vd = (model["qk_nope_head_dim"], model["qk_rope_head_dim"],
                    model["v_head_dim"])
    mla = (d * rq + rq * h_n * (nope + rp) + d * (rkv + rp)
           + rkv * h_n * (nope + vd) + h_n * vd * d)
    return {"mla": mla, "dense_ffn": 3 * d * f,
            "shared": 3 * d * fm * model["n_shared_experts"],
            "router": d * model["n_routed_experts_published"],
            "expert": 3 * d * fm,
            "embed": model["vocab_size"] * d, "head": d * model["vocab_size"]}


def layer_counts(model: dict) -> tuple:
    nd = model["first_k_dense_replace"]
    return nd, model["num_hidden_layers"] - nd


def total_params(model: dict) -> int:
    p = param_counts(model)
    nd, nm = layer_counts(model)
    return (nd * (p["mla"] + p["dense_ffn"])
            + nm * (p["mla"] + p["shared"] + p["router"]
                    + model["n_routed_experts"] * p["expert"])
            + p["embed"] + p["head"])


def entry_bytes(model: dict) -> int:
    """What a token must leave in the cache in one layer: the latent and
    the rotated key (the store's padding to a lane row is not needed)."""
    return (model["kv_lora_rank"] + model["qk_rope_head_dim"]) * BYTES


def decode_iteration_bytes(model: dict, experts_touched: float,
                           cache_tokens: float, slots: int = 0) -> float:
    """Least bytes one decode iteration reads.  ``experts_touched``: held
    experts with a token, summed over the expert layers (from the
    program's counter); ``cache_tokens``: cached tokens of the sequences
    alive, summed over them; ``slots``: rows of the embedding read."""
    p = param_counts(model)
    nd, nm = layer_counts(model)
    weights = (nd * (p["mla"] + p["dense_ffn"])
               + nm * (p["mla"] + p["shared"] + p["router"])
               + experts_touched * p["expert"] + p["head"]
               + slots * model["hidden_size"])
    cache = cache_tokens * entry_bytes(model) * model["num_hidden_layers"]
    return weights * BYTES + cache


def expected_touched(model: dict, tokens: float) -> float:
    """Held experts of ONE layer expected to get at least one of
    ``tokens`` tokens under balanced routing."""
    p = model["num_experts_per_tok"] / model["n_routed_experts_published"]
    return model["n_routed_experts"] * (1.0 - (1.0 - p) ** tokens)


def moe_ffn_work(model: dict, assignments: float,
                 experts_touched: float) -> dict:
    """The ROUTED experts' products (what the trace can name: the shared
    expert's are plain matmul fusions): ``assignments`` (token, expert)
    pairs through a SwiGLU of width ``moe_intermediate_size``; the
    matrices of the ``experts_touched`` experts read once, a pair's input
    read and its output written once in bfloat16.  Both arguments are
    sums over layers and passes."""
    p = param_counts(model)
    return {"flops": 2.0 * assignments * p["expert"],
            "bytes": BYTES * (experts_touched * p["expert"]
                              + assignments * 2 * model["hidden_size"])}


def mean_prompt_tokens(traffic: dict) -> float:
    """Mean of the traffic file's clipped lognormal, by its quantiles."""
    from statistics import NormalDist

    t = traffic["prompt_tokens"]
    nd = NormalDist()
    n = 400
    v = [min(max(t["median"] * math.exp(t["sigma"] * nd.inv_cdf((i + .5) / n)),
                 t["min"]), t["max"]) for i in range(n)]
    return sum(v) / n


# How to find the layer's operations in the device trace (benchmark/xtrace.py
# sees an op's own name, numbered suffix dropped, and gives an instant to the
# innermost op).  Read off a traced run with tools/trace_lines.py and off the
# decode program's compiled text (PR 27):
# - the routed experts' grouped products are XLA's ``ragged-dot-none`` custom
#   calls (``ragged-dot-metadata`` prepares their group offsets);
# - of the latent attention only the ops whose names occur nowhere but inside
#   the view ladder's conditional can be told apart: the masked softmax
#   (``bitcast_reduce_fusion``, ``maximum_bitcast_fusion``,
#   ``iota_compare_fusion``, ``is-finite_select_fusion``), the view's relayout
#   (``copy_bitcast_fusion``) and the conditional's own remainder.  The view's
#   gather and the two products are plain ``fusion``s, like every matmul.
# ``sample`` is one op class the pattern matches (the tests' hand-made traces).
KERNELS = [
    {"name": "moe_ffn", "match": r"^ragged-dot", "sample": "ragged-dot-none"},
    {"name": "latent_attn",
     "match": r"^(cond(\.|$)|conditional|bitcast_reduce_fusion$"
              r"|maximum_bitcast_fusion$|iota_compare_fusion$"
              r"|is-finite_select_fusion$|copy_bitcast_fusion$)",
     "sample": "bitcast_reduce_fusion"},
]
