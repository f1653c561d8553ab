"""Bytes and operations ``deepseek-ai/DeepSeek-V3.2-Exp`` needs (latent
attention with a lightning indexer, dense and expert layers), counted from
shapes and from what the program counted.

``param_counts`` gives the parameter counts by part; the configuration
file's arithmetic is these numbers.  Of a decode iteration's sparse
attention: the indexer's key of EVERY cached token of the sequences alive is
read (256 B a layer: the scores come before the selection), then the latent
entries the attention reads.  A multiply-add counts as 2 operations.
"""

from __future__ import annotations

import math

BYTES = 2          # bfloat16 parameters, cache and activations
# The store's rows are padded to a lane multiple (640 values for 576): a
# kernel that copies rows or pages moves the padding too.
LATENT_ROW_BYTES = 640 * BYTES


def param_counts(model: dict) -> dict:
    """Parameters by part; ``mla``, ``indexer``, ``shared``, ``router`` and
    ``expert`` (ONE routed expert) are a layer's."""
    d, f, fm = (model["hidden_size"], model["intermediate_size"],
                model["moe_intermediate_size"])
    h_n, rq, rkv = (model["num_attention_heads"], model["q_lora_rank"],
                    model["kv_lora_rank"])
    nope, rp, vd = (model["qk_nope_head_dim"], model["qk_rope_head_dim"],
                    model["v_head_dim"])
    ih, idim = model["index_n_heads"], model["index_head_dim"]
    mla = (d * rq + rq * h_n * (nope + rp) + d * (rkv + rp)
           + rkv * h_n * (nope + vd) + h_n * vd * d)
    return {"mla": mla, "indexer": rq * ih * idim + d * idim + d * ih,
            "dense_ffn": 3 * d * f,
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
    attn = p["mla"] + p["indexer"]
    return (nd * (attn + p["dense_ffn"])
            + nm * (attn + p["shared"] + p["router"]
                    + model["n_routed_experts"] * p["expert"])
            + p["embed"] + p["head"])


def index_key_bytes(model: dict) -> int:
    """What the indexer keeps of a token in one layer."""
    return model["index_head_dim"] * BYTES


def entry_bytes(model: dict) -> int:
    """What a token must leave in the cache in one layer: the latent, the
    rotated key and the indexer's key (the latent store's padding to a
    lane row is not needed)."""
    return ((model["kv_lora_rank"] + model["qk_rope_head_dim"]) * BYTES
            + index_key_bytes(model))


def index_score_bytes(model: dict, scored_tokens: float) -> float:
    """Least bytes the scoring reads: the key of every (position, layer)
    scored (``serving.dsa_scored_tokens`` counts those pairs)."""
    return scored_tokens * index_key_bytes(model)


def sparse_attn_bytes(model: dict, scored_tokens: float) -> float:
    """Bytes the attention over the selection reads IN THE FORM THE TREE
    HOLDS, whole pages under a mask: the padded latent row of every
    (position, layer) cached, selected or not.  (Reading the selected rows
    alone would be ``serving.dsa_selected_tokens`` x 1280 B;
    ``dsa_selected_share_pct`` is the ratio of the two.)"""
    return scored_tokens * LATENT_ROW_BYTES


def expected_touched(model: dict, tokens: float) -> float:
    """Held experts of ONE layer expected to get at least one of
    ``tokens`` tokens under balanced routing."""
    p = model["num_experts_per_tok"] / model["n_routed_experts_published"]
    return model["n_routed_experts"] * (1.0 - (1.0 - p) ** tokens)


def moe_ffn_work(model: dict, assignments: float,
                 experts_touched: float) -> dict:
    """The ROUTED experts' products: ``assignments`` (token, expert) pairs
    through a SwiGLU of width ``moe_intermediate_size``; the matrices of
    the ``experts_touched`` experts read once, a pair's input read and its
    output written once in bfloat16.  Both arguments are sums over layers
    and passes."""
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


# How to find the layers' operations in the device trace (benchmark/xtrace.py
# sees an op's own name, numbered suffix dropped): the three kernels of
# ``ops/sparse_latent_attention.py`` by their names; ``latent_attn`` is the
# latent attention over the store, which here is ``dsa_sparse_attn`` (the
# family's ``latent_paged_attn`` where a program without an indexer runs);
# the routed experts' grouped products are XLA's ``ragged-dot`` custom calls.
# A PROMPT's selection is XLA's (a threshold inside mapped loops, plain
# fusions by name): ``dsa_select`` names the decode's kernel alone.
KERNELS = [
    {"name": "moe_ffn", "match": r"^ragged-dot", "sample": "ragged-dot-none"},
    {"name": "latent_attn", "match": r"^(dsa_sparse_attn|latent_paged_attn)",
     "sample": "dsa_sparse_attn"},
    {"name": "dsa_index_score", "match": r"^dsa_index_score",
     "sample": "dsa_index_score"},
    {"name": "dsa_select", "match": r"^dsa_select", "sample": "dsa_select"},
    {"name": "dsa_sparse_attn", "match": r"^dsa_sparse_attn",
     "sample": "dsa_sparse_attn"},
]
