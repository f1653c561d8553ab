"""Bytes and operations the Mamba-2 hybrid decoder of
``granite-4.0-h-micro`` needs, counted from shapes and from what the
program counted.

One decode iteration is bound by what it must MOVE: every matrix once (the
head is the embedding: its 100352 x 2048 once), the cached keys and values
of each attention layer's own paged layer once, and the recurrent state of
every live slot in every state-space layer, read AND written.  The state
is the large term: 2.10 MB a layer a slot, 76.4 MB a slot, against 8 KB a
cached token.  ``param_counts`` gives the parameter counts by kind of
layer; the configuration file's arithmetic is these numbers.
"""

from __future__ import annotations

BYTES = 2          # bfloat16 parameters, stores and activations
STATE_BYTES = 4    # the state-space state: float32


def sizes(model: dict) -> dict:
    d = model["hidden_size"]
    hd = d // model["num_attention_heads"]
    di = model["mamba_n_heads"] * model["mamba_d_head"]
    return {"d": d, "d_inner": di, "hd": hd,
            "conv": di + 2 * model["mamba_n_groups"] * model["mamba_d_state"],
            "kv_width": model["num_key_value_heads"] * hd}


def param_counts(model: dict) -> dict:
    """Parameters of ONE layer of each kind (mixer, norms and MLP) and of
    the embedding, which is the head."""
    s = sizes(model)
    d, di, cw = s["d"], s["d_inner"], s["conv"]
    h, k = model["mamba_n_heads"], model["mamba_d_conv"]
    mlp = 3 * d * model["intermediate_size"] + 2 * d       # and two norms
    return {
        "mamba": (d * (di + cw + h) + k * cw + cw + 3 * h + di + di * d
                  + mlp),
        "attention": d * (d + 2 * s["kv_width"]) + d * d + mlp,
        "embed": model["vocab_size"] * d + d}              # and the last norm


def layer_counts(model: dict) -> dict:
    kinds = list(model["layer_types"])
    return {k: kinds.count(k) for k in ("mamba", "attention")}


def total_params(model: dict) -> int:
    p, n = param_counts(model), layer_counts(model)
    return sum(n[k] * p[k] for k in n) + p["embed"]


def position_bytes(model: dict) -> int:
    """Keys and values one cached position holds over ALL attention
    layers: 4 x 2 x 512 bfloat16 = 8192."""
    return (layer_counts(model)["attention"] * 2 * sizes(model)["kv_width"]
            * BYTES)


def slot_state_bytes(model: dict) -> int:
    """Recurrent state of ONE slot in ONE state-space layer: the float32
    ``[heads, d_head, d_state]`` state and the convolution's tail
    (2,123,264 at the published sizes)."""
    s = sizes(model)
    return (s["d_inner"] * model["mamba_d_state"] * STATE_BYTES
            + (model["mamba_d_conv"] - 1) * s["conv"] * BYTES)


def store_bytes(model: dict, slots: int, capacity: int) -> dict:
    """What the cache manager holds for ``slots`` slots of ``capacity``
    positions, by store; ``view`` is ONE attention layer's gathered keys
    and values, the decode program's scratch."""
    n = layer_counts(model)
    return {"paged": slots * capacity * position_bytes(model),
            "state": n["mamba"] * slots * slot_state_bytes(model),
            "view": slots * capacity * position_bytes(model)
            // n["attention"]}


def decode_iteration_bytes(model: dict, kv_tokens: float,
                           window_tokens: float, alive: float) -> float:
    """Least bytes one decode iteration moves.  ``kv_tokens``: cached
    positions attended, summed over the slots alive (every attention layer
    reads its own layer of them once: ``position_bytes`` counts all four);
    ``window_tokens``: unused (no window layer; the signature is
    ``flops/phi4-mini-flash.py``'s); ``alive``: slots decoding (a row of
    the embedding each, and their recurrent state read and written)."""
    del window_tokens
    weights = total_params(model) + alive * sizes(model)["d"]
    state = 2 * alive * layer_counts(model)["mamba"] * slot_state_bytes(model)
    return weights * BYTES + position_bytes(model) * kv_tokens + state


def ssd_step_bytes(model: dict, slot_layers: float) -> float:
    """Least bytes the one-step kernel moves for ``slot_layers`` live
    (slot, layer) pairs: the float32 state read and written.  (Its small
    operands, under 1% of that, and the tails, which the kernel does not
    touch, are left out: the share reads a little LOW for it.)"""
    return (2 * slot_layers * sizes(model)["d_inner"] * model["mamba_d_state"]
            * STATE_BYTES)


def ssd_chunk_work(model: dict, tokens: float, calls: float = 1) -> dict:
    """Operations and least bytes of the chunked scan for ``tokens`` steps
    in ``calls`` calls (one a state-space layer a prefill).  A head's
    chunk of ``Q`` steps is four products: ``C B^T`` (once a chunk, shared
    by the heads: ``2 Q N`` a step), ``(L o C B^T) x`` (``2 Q P`` a step a
    head), ``C S`` and ``x^T B`` (``2 N P`` each).  Bytes: ``x`` in
    (bfloat16), ``y`` out (float32), ``B``, ``C``, and ``dt`` with its
    running sum in both layouts; a call's state in and out."""
    h, p = model["mamba_n_heads"], model["mamba_d_head"]
    n, q = model["mamba_d_state"], model["mamba_chunk_size"]
    flops = tokens * (2 * q * n + h * (2 * q * p + 4 * n * p))
    nbytes = (tokens * (h * p * (BYTES + STATE_BYTES) + 2 * n * BYTES
                        + 4 * h * STATE_BYTES)
              + calls * 2 * h * p * n * STATE_BYTES)
    return {"flops": flops, "bytes": nbytes}


# How to find the layer's operations in the device trace (benchmark/xtrace.py
# sees an op's own name, numbered suffix dropped): each kernel's Mosaic
# custom call carries the name its ``pallas_call`` gives it
# (horovod_tpu/ops/ssd.py).  ``sample`` is one op class the pattern matches
# (the tests' hand-made traces).
KERNELS = [
    {"name": "ssd_step", "match": r"^ssd_step", "sample": "ssd_step"},
    {"name": "ssd_chunk_scan", "match": r"^ssd_chunk_scan",
     "sample": "ssd_chunk_scan"},
]
