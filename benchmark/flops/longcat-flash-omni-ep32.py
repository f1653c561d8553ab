"""Bytes and operations the shortcut-connected mixture-of-experts decoder
needs, counted from shapes and from what the program counted.

A decoder layer holds TWO latent attentions, TWO dense SwiGLU
feed-forwards, one router (over the real experts and the zero-compute
ones, which hold no weights) and the real experts held here.  One decode
iteration is bound by what it must READ: every matrix of the attentions,
the dense feed-forwards, the routers and the head once, the matrices of
the held experts that got a token, and the cached entries of the
sequences alive, two cache layers a decoder layer.  A multiply-add counts
as 2 operations.  The signatures are ``flops/axk1-ep16.py``'s, so the
accepted readers of the expert layer and of a decode iteration take their
arithmetic from here.
"""

from __future__ import annotations

from benchmark.cells import load_module

BYTES = 2          # bfloat16 parameters, cache and activations
SUBLAYERS = 2      # attentions, dense feed-forwards and cache layers a layer


def router_outputs(model: dict) -> int:
    return model["n_routed_experts_published"] + model["zero_expert_num"]


def param_counts(model: dict) -> dict:
    """Parameters by part; ``mla`` and ``dense_ffn`` are ONE of a layer's
    two, ``router`` a layer's, ``expert`` ONE real expert's."""
    d, f, fm = (model["hidden_size"], model["ffn_hidden_size"],
                model["expert_ffn_hidden_size"])
    h_n, rq, rkv = (model["num_attention_heads"], model["q_lora_rank"],
                    model["kv_lora_rank"])
    nope, rp, vd = (model["qk_nope_head_dim"], model["qk_rope_head_dim"],
                    model["v_head_dim"])
    mla = (d * rq + rq * h_n * (nope + rp) + d * (rkv + rp)
           + rkv * h_n * (nope + vd) + h_n * vd * d)
    return {"mla": mla, "dense_ffn": 3 * d * f,
            "router": d * router_outputs(model), "expert": 3 * d * fm,
            "embed": model["vocab_size"] * d, "head": d * model["vocab_size"]}


def layer_counts(model: dict) -> tuple:
    """``(leading dense layers, expert layers)``: every layer holds one
    expert layer."""
    return 0, model["num_layers"]


def layer_params_outside_experts(model: dict) -> int:
    p = param_counts(model)
    return SUBLAYERS * (p["mla"] + p["dense_ffn"]) + p["router"]


def total_params(model: dict) -> int:
    p = param_counts(model)
    return (model["num_layers"]
            * (layer_params_outside_experts(model)
               + model["n_routed_experts"] * p["expert"])
            + p["embed"] + p["head"])


def entry_bytes(model: dict) -> int:
    """What a token must leave in the cache in ONE cache layer: the latent
    and the rotated key (the store's padding to a lane row is not
    needed)."""
    return (model["kv_lora_rank"] + model["qk_rope_head_dim"]) * BYTES


def cache_layers(model: dict) -> int:
    return SUBLAYERS * model["num_layers"]


def decode_iteration_bytes(model: dict, experts_touched: float,
                           cache_tokens: float, slots: int = 0) -> float:
    """Least bytes one decode iteration reads.  ``experts_touched``: held
    experts with a token, summed over the expert layers (from the
    program's counter); ``cache_tokens``: cached tokens of the sequences
    alive, summed over them; ``slots``: rows of the embedding read."""
    p = param_counts(model)
    weights = (model["num_layers"] * layer_params_outside_experts(model)
               + experts_touched * p["expert"] + p["head"]
               + slots * model["hidden_size"])
    cache = cache_tokens * entry_bytes(model) * cache_layers(model)
    return weights * BYTES + cache


def held_pair_share(model: dict) -> float:
    """Pairs a token sends to the experts held here under balanced
    routing: ``moe_topk`` spread over ALL of the router's outputs, the
    zero-compute ones among them."""
    return (model["moe_topk"] * model["n_routed_experts"]
            / router_outputs(model))


def expected_touched(model: dict, tokens: float) -> float:
    """Held experts of ONE layer expected to get at least one of
    ``tokens`` tokens under balanced routing."""
    p = model["moe_topk"] / router_outputs(model)
    return model["n_routed_experts"] * (1.0 - (1.0 - p) ** tokens)


def moe_ffn_work(model: dict, assignments: float,
                 experts_touched: float) -> dict:
    """The held REAL experts' products (what the trace can name):
    ``assignments`` (token, expert) pairs through a SwiGLU of width
    ``expert_ffn_hidden_size``; the matrices of the ``experts_touched``
    experts read once, a pair's input read and its output written once in
    bfloat16.  A pair on a zero-compute expert is no work and is not in
    ``assignments``.  Both arguments are sums over layers and passes."""
    p = param_counts(model)
    return {"flops": 2.0 * assignments * p["expert"],
            "bytes": BYTES * (experts_touched * p["expert"]
                              + assignments * 2 * model["hidden_size"])}


# The mix's mean prompt and how to find the layer's operations in the
# device trace are ``flops/axk1-ep16.py``'s: the expert layer's core and the
# view ladder are the same code, so the same op classes name them (the held
# experts' grouped products are XLA's ``ragged-dot-none`` custom calls; of
# the latent attention only what occurs nowhere but inside the ladder's
# conditional can be told apart).
_axk1 = load_module("flops", "axk1-ep16")
mean_prompt_tokens = _axk1.mean_prompt_tokens
KERNELS = _axk1.KERNELS
