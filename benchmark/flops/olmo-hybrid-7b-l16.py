"""Bytes and operations the hybrid decoder of ``Olmo-Hybrid-7B`` needs,
counted from shapes and from what the program counted.

One decode iteration is bound by what it must MOVE: every matrix once (the
head is untied: embedding rows for the live slots, the head whole), the
cached keys and values of each full layer's own paged layer once, and the
delta-rule state of every live slot in every linear layer, read AND
written: 2.21 MB a layer a slot, against 15 KB a cached token a full layer.
``param_counts`` gives the parameter counts by kind of layer; the
configuration file's arithmetic is these numbers.
"""

from __future__ import annotations

BYTES = 2          # bfloat16 parameters, stores and activations
STATE_BYTES = 4    # the delta-rule state: float32
LINEAR, FULL = "linear_attention", "full_attention"


def sizes(model: dict) -> dict:
    d = model["hidden_size"]
    h = model["linear_num_value_heads"]
    dk, dv = model["linear_key_head_dim"], model["linear_value_head_dim"]
    hd = d // model["num_attention_heads"]
    return {"d": d, "H": h, "dk": dk, "dv": dv, "key_width": h * dk,
            "value_width": h * dv, "conv": 2 * h * dk + h * dv, "hd": hd,
            "kv_width": model["num_key_value_heads"] * hd}


def param_counts(model: dict) -> dict:
    """Parameters of ONE layer of each kind (mixer, its two output norms
    and the MLP), of the embedding and of the head with the last norm."""
    s = sizes(model)
    d, h, k = s["d"], s["H"], model["linear_conv_kernel_dim"]
    mlp = 3 * d * model["intermediate_size"] + 2 * d       # and two norms
    return {
        LINEAR: (d * (s["conv"] + s["value_width"] + 2 * h) + k * s["conv"]
                 + 2 * h + s["dv"] + s["value_width"] * d + mlp),
        FULL: d * (d + 2 * s["kv_width"]) + 2 * d + d * d + mlp,
        "embed": model["vocab_size"] * d,
        "head": model["vocab_size"] * d + d}


def layer_counts(model: dict) -> dict:
    kinds = list(model["layer_types"])
    return {k: kinds.count(k) for k in (LINEAR, FULL)}


def total_params(model: dict) -> int:
    p, n = param_counts(model), layer_counts(model)
    return sum(n[k] * p[k] for k in n) + p["embed"] + p["head"]


def position_bytes(model: dict) -> int:
    """Keys and values one cached position holds over ALL full layers: 4 x
    2 x 3840 bfloat16 = 61,440 in the cut."""
    return layer_counts(model)[FULL] * 2 * sizes(model)["kv_width"] * BYTES


def slot_state_bytes(model: dict) -> int:
    """Recurrent state of ONE slot in ONE linear layer: the float32
    ``[heads, d_k, d_v]`` state and the convolution's tail (2,280,960 at
    the published sizes)."""
    s = sizes(model)
    return (s["H"] * s["dk"] * s["dv"] * STATE_BYTES
            + (model["linear_conv_kernel_dim"] - 1) * s["conv"] * BYTES)


def store_bytes(model: dict, slots: int, pool_tokens: int) -> dict:
    """What the cache manager holds for ``slots`` slots and a page pool of
    ``pool_tokens`` positions, by store."""
    return {"paged": pool_tokens * position_bytes(model),
            "state": layer_counts(model)[LINEAR] * slots
            * slot_state_bytes(model)}


def decode_iteration_bytes(model: dict, kv_tokens: float,
                           window_tokens: float, alive: float) -> float:
    """Least bytes one decode iteration moves.  ``kv_tokens``: cached
    positions attended, summed over the slots alive (every full layer
    reads its own layer of them once: ``position_bytes`` counts all of
    them); ``window_tokens``: unused (no window layer; the signature is
    ``flops/phi4-mini-flash.py``'s); ``alive``: slots decoding (a row of
    the embedding each, and their recurrent state read and written).  The
    embedding's other rows are not read: the head is untied."""
    del window_tokens
    weights = (total_params(model) - param_counts(model)["embed"]
               + alive * sizes(model)["d"])
    state = 2 * alive * layer_counts(model)[LINEAR] * slot_state_bytes(model)
    return weights * BYTES + position_bytes(model) * kv_tokens + state


def gdn_step_bytes(model: dict, slot_layers: float) -> float:
    """Least bytes the one-step kernel moves for ``slot_layers`` live
    (slot, layer) pairs: the float32 state read and written.  (Its small
    operands, under 1% of that, and the tails, which the kernel does not
    touch, are left out: the share reads a little LOW for it.)"""
    s = sizes(model)
    return 2 * slot_layers * s["H"] * s["dk"] * s["dv"] * STATE_BYTES


CHUNK = 64         # steps of one chunk of the chunked scan


def gdn_chunk_flops(model: dict, tokens: float) -> float:
    """Operations of the chunked delta rule for ``tokens`` steps of ONE
    linear layer at chunks of ``CHUNK`` (``C``), whatever the kernel
    executes: a step a head takes part in ``K K^T`` and ``Q K^T`` (``2 C
    d_k`` each), the two reads of the carried state ``K S`` and ``Q S``
    (``2 d_k d_v`` each), the triangular system's solution applied to the
    values and the masked scores times it (``2 C d_v`` each), and the
    state's update ``K^T U`` (``2 d_k d_v``).  Forming the triangular
    inverse itself (ten ``C^3`` products a chunk in float32) and the
    decays' exponentials are not counted: the share reads LOW for them."""
    s = sizes(model)
    dk, dv = s["dk"], s["dv"]
    return tokens * s["H"] * (4 * CHUNK * dk + 6 * dk * dv + 4 * CHUNK * dv)


# How to find the layer's operations in the device trace (benchmark/xtrace.py
# sees an op's own name, numbered suffix dropped): each kernel's Mosaic
# custom call carries the name its ``pallas_call`` gives it
# (horovod_tpu/ops/gated_delta.py, ops/gqa_paged_attention.py).  ``sample``
# is one op class the pattern matches (the tests' hand-made traces).
KERNELS = [
    {"name": "gdn_step", "match": r"^gdn_step", "sample": "gdn_step"},
    {"name": "gdn_chunk_scan", "match": r"^gdn_chunk_scan",
     "sample": "gdn_chunk_scan"},
    {"name": "gqa_paged_attn", "match": r"^gqa_paged_attn",
     "sample": "gqa_paged_attn"},
]
