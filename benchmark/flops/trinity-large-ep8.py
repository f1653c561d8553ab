"""Bytes and operations one chip's share of the ``afmoe`` decoder of
``Trinity-Large-Preview`` needs, counted from shapes and from what the
program counted.

One decode pass is bound by what it must MOVE: every matrix outside the
routed experts once (attention with its gate, the dense layer, the shared
experts, the routers, the head), the matrices of the held experts that got
a token, and the cached keys and values the live slots attend: every
position in the full layers, at most the window in the sliding ones.
``param_counts`` gives the parameter counts by part; the configuration
file's arithmetic (``assumed.bytes``) is these numbers.  A multiply-add
counts as 2 operations.

The names ``layer_counts``, ``moe_ffn_work``, ``expected_touched``,
``mean_prompt_tokens`` and the ``KERNELS`` entry ``moe_ffn`` are what
``benchmark/metrics/moe_ffn_roofline.py`` and its siblings read.
"""

from __future__ import annotations

import math

BYTES = 2          # bfloat16 parameters, cache and activations


def param_counts(model: dict) -> dict:
    """Parameters by part; ``attention``, ``shared``, ``router`` and
    ``expert`` (ONE routed expert) are a layer's.  The norms (four ``d``
    a layer, two ``head_dim``, the last ``d``) are left out: 62 thousand
    of 4.3 billion."""
    d, hd = model["hidden_size"], model["head_dim"]
    qw = model["num_attention_heads"] * hd
    kvw = model["num_key_value_heads"] * hd
    fe = model["moe_intermediate_size"]
    return {"attention": d * (2 * qw + 2 * kvw) + qw * d,
            "dense_ffn": 3 * d * model["intermediate_size"],
            "shared": 3 * d * fe * model["num_shared_experts"],
            "router": d * model["n_routed_experts_published"],
            "expert": 3 * d * fe,
            "embed": model["vocab_size"] * d,
            "head": d * model["vocab_size"]}


def layer_counts(model: dict) -> tuple:
    """``(dense layers, expert layers)``."""
    nd = model["num_dense_layers"]
    return nd, model["num_hidden_layers"] - nd


def kind_counts(model: dict) -> tuple:
    """``(full layers, sliding layers)``."""
    kinds = list(model["layer_types"])
    return kinds.count("full_attention"), kinds.count("sliding_attention")


def total_params(model: dict) -> int:
    p = param_counts(model)
    nd, nm = layer_counts(model)
    return (nd * (p["attention"] + p["dense_ffn"])
            + nm * (p["attention"] + p["shared"] + p["router"]
                    + model["n_routed_experts"] * p["expert"])
            + p["embed"] + p["head"])


def token_bytes(model: dict) -> int:
    """Keys and values one cached position holds in ONE layer: 2 x 8 x 128
    bfloat16 = 4096."""
    return 2 * model["num_key_value_heads"] * model["head_dim"] * BYTES


def cache_bytes(model: dict, length: int, page: int = 0) -> int:
    """What a live slot of ``length`` positions holds in pages of ``page``
    positions (0: not rounded): all of them in each full layer, at most
    the window and a page in each sliding one."""
    n_full, n_win = kind_counts(model)
    held = min(length, model["sliding_window"] + page)
    if page:
        length, held = (-(-x // page) * page for x in (length, held))
    return token_bytes(model) * (n_full * length + n_win * held)


def decode_pass_bytes(model: dict, experts_touched: float,
                      full_tokens: float, window_tokens: float,
                      alive: float = 0) -> float:
    """Least bytes one decode pass moves.  ``experts_touched``: held
    experts with a token, summed over the expert layers (the program's
    counter); ``full_tokens`` / ``window_tokens``: positions the slots
    alive attend in a full / a sliding layer, summed over them (the
    program's counters); ``alive``: rows of the embedding read."""
    p = param_counts(model)
    nd, nm = layer_counts(model)
    n_full, n_win = kind_counts(model)
    weights = (nd * (p["attention"] + p["dense_ffn"])
               + nm * (p["attention"] + p["shared"] + p["router"])
               + experts_touched * p["expert"] + p["head"]
               + alive * model["hidden_size"])
    cache = token_bytes(model) * (n_full * full_tokens
                                  + n_win * window_tokens)
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


def prompt_attention_pairs(model: dict, n: int) -> int:
    """(query, key) pairs ONE head's causal attention over a prompt of
    ``n`` tokens must score, summed over the layers: ``n (n + 1) / 2`` in a
    full layer, at most the window's keys a query in a sliding one."""
    n_full, n_win = kind_counts(model)
    w = min(n, model["sliding_window"])
    whole = n * (n + 1) // 2
    return n_full * whole + n_win * (w * (w + 1) // 2 + (n - w) * w)


def gqa_flash_work(model: dict, pairs: float, tokens: float) -> dict:
    """The prompt's attention kernel (``ops/flash_attention.py``
    ``gqa_flash_fwd``): ``pairs`` (query, key) pairs a head (summed over
    layers and prompts) through ``q . k`` and ``p v`` in every query head,
    2 x head_dim multiply-adds each; queries and outputs of ``tokens``
    prompt tokens (summed over layers) read and written once, keys and
    values read once.  What the kernel does beyond that (a bucket's
    padding, whole blocks on the diagonal and at the window's edge, keys
    re-read by every block of queries) is not counted.  No per-layer metric
    reads it yet: a few traced seconds hold a handful of prompts of 256 to
    8192 tokens, and the window's counters scaled to the traced share read
    anything from 30 to 140% (PERF.md section 7)."""
    hd, h, g = (model["head_dim"], model["num_attention_heads"],
                model["num_key_value_heads"])
    return {"flops": 2.0 * 2 * hd * h * pairs,
            "bytes": BYTES * tokens * hd * (2 * h + 2 * g)}


# How to find the layers' operations in the device trace (benchmark/xtrace.py
# sees an op's own name, numbered suffix dropped, and gives an instant to the
# innermost op):
# - the routed experts' grouped products are XLA's ``ragged-dot`` custom
#   calls, as in the other expert configurations;
# - the prompt's attention is ONE Pallas kernel a layer, ``gqa_flash_fwd``;
# - of the decode's attention the trace can name the op classes that occur
#   under the programs' ``gqa_attention`` scope and nowhere outside it, read
#   off the scheduled instructions of the decode program and the prefill
#   programs compiled for a described v5e at the cell's sizes (PERF.md
#   section 6, PR 41): the view's gather (``maximum_dynamic-update-slice``:
#   2.98 of a 14.96 ms pass at 24 slots alive), parts of the masked softmax
#   (``maximum_convert``, ``multiply_subtract``, ``maximum_bitcast``,
#   ``pad_maximum``), the rotation and the q/k norms (``multiply_cosine``,
#   ``rsqrt_multiply``, ``multiply_multiply``), the view's relayout and the
#   rung's conditional.  The projections, a rung's two products, the masked
#   maximum (``select_reduce_fusion``, which the head's argmax shares) are
#   ``fusion``s and classes that occur elsewhere too, so ``gqa_attn`` is a
#   floor of the layer's share.
# ``sample`` is one op class the pattern matches (the tests' hand-made traces).
KERNELS = [
    {"name": "moe_ffn", "match": r"^ragged-dot", "sample": "ragged-dot-none"},
    {"name": "gqa_flash", "match": r"^gqa_flash_fwd",
     "sample": "gqa_flash_fwd"},
    {"name": "gqa_attn",
     "match": r"^(gqa_flash_fwd|cond|conditional"
              r"|maximum_dynamic-update-slice_fusion"
              r"|multiply_multiply_fusion|multiply_subtract_fusion"
              r"|maximum_convert_fusion|maximum_bitcast_fusion"
              r"|convert_bitcast_fusion|pad_maximum_fusion"
              r"|multiply_cosine_fusion|rsqrt_multiply_fusion"
              r"|broadcast_bitcast_fusion|copy_bitcast_fusion"
              r"|iota_convert_fusion)$",
     "sample": "maximum_dynamic-update-slice_fusion"},
]
