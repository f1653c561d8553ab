"""Bytes the decoder-hybrid-decoder of ``Phi-4-mini-flash-reasoning`` needs,
counted from shapes and from what the program counted.

One decode iteration is bound by what it must READ: every matrix once (the
head is the embedding: its 200064 x 2560 once), the cached keys and values
of the ONE shared store once for each of its readers (the full layer and the
cross layers), the window rings once for their own layer, and the recurrent
state, read and written.  ``param_counts`` gives the parameter counts by
kind of layer; the configuration file's arithmetic is these numbers.
"""

from __future__ import annotations

BYTES = 2          # bfloat16 parameters, stores and activations
STATE_BYTES = 4    # the state-space state and the scan's operands: float32


def layer_kinds(n_layers: int) -> list:
    h = n_layers // 2
    return [("ssm" if l <= h else "gmu") if l % 2 == 0 else
            ("window" if l < h else "full" if l == h + 1 else "cross")
            for l in range(n_layers)]


def sizes(model: dict) -> dict:
    d = model["hidden_size"]
    hd = d // model["num_attention_heads"]
    return {"d": d, "d_inner": model["expand"] * d,
            "kv_width": model["num_key_value_heads"] * hd, "hd": hd}


def param_counts(model: dict) -> dict:
    """Parameters of ONE layer of each kind (mixer, norms and MLP) and of
    the embedding, which is the head."""
    s = sizes(model)
    d, di, kvw, hd = s["d"], s["d_inner"], s["kv_width"], s["hd"]
    n, k, r = model["d_state"], model["d_conv"], model["dt_rank"]
    mlp = 3 * d * model["intermediate_size"] + 4 * d       # and two norms
    lam = 4 * hd + 2 * hd
    return {
        "ssm": (d * 2 * di + k * di + di + di * (r + 2 * n) + r * di + di
                + n * di + di + di * d) + mlp,
        "window": d * (d + 2 * kvw) + d + 2 * kvw + d * d + d + lam + mlp,
        "full": d * (d + 2 * kvw) + d + 2 * kvw + d * d + d + lam + mlp,
        "gmu": 2 * d * di + mlp,
        "cross": 2 * (d * d + d) + lam + mlp,
        "embed": model["vocab_size"] * d + 2 * d}          # and the last norm


def layer_counts(model: dict) -> dict:
    kinds = layer_kinds(model["num_hidden_layers"])
    return {k: kinds.count(k) for k in ("ssm", "window", "full", "gmu",
                                        "cross")}


def total_params(model: dict) -> int:
    p, n = param_counts(model), layer_counts(model)
    return sum(n[k] * p[k] for k in n) + p["embed"]


def position_bytes(model: dict) -> int:
    """Keys and values one cached position holds in one store: 2 x 1280
    bfloat16."""
    return 2 * sizes(model)["kv_width"] * BYTES


def slot_state_bytes(model: dict) -> int:
    """Recurrent state of ONE slot in ONE state-space layer: the float32
    state and the convolution's tail (358 KB at the published sizes)."""
    di = sizes(model)["d_inner"]
    return (model["d_state"] * di * STATE_BYTES
            + (model["d_conv"] - 1) * di * BYTES)


def store_bytes(model: dict, slots: int, capacity: int) -> dict:
    """What the cache manager holds for ``slots`` slots of ``capacity``
    positions, by store."""
    n = layer_counts(model)
    return {"paged": slots * capacity * position_bytes(model),
            "window": (n["window"] * slots * model["sliding_window"]
                       * position_bytes(model)),
            "state": n["ssm"] * slots * slot_state_bytes(model)}


def decode_iteration_bytes(model: dict, shared_kv_tokens: float,
                           window_tokens: float, alive: float) -> float:
    """Least bytes one decode iteration moves.  ``shared_kv_tokens``:
    positions of the one store attended, summed over the slots alive (each
    of its readers reads them once); ``window_tokens``: the same for a
    window ring (each window layer reads its own); ``alive``: slots
    decoding (a row of the embedding each, and their recurrent state read
    and written)."""
    n = layer_counts(model)
    weights = total_params(model) + alive * sizes(model)["d"]
    readers = n["full"] + n["cross"]
    cache = position_bytes(model) * (readers * shared_kv_tokens
                                     + n["window"] * window_tokens)
    state = 2 * alive * n["ssm"] * slot_state_bytes(model)
    return weights * BYTES + cache + state


def ssm_scan_bytes(model: dict, tokens: float, calls: float = 1) -> float:
    """Least bytes the scan kernel moves for ``tokens`` steps in ``calls``
    calls (one a state-space layer a prefill): the convolved input, ``dt``
    and the output ``[tokens, d_inner]``, ``B`` and ``C`` ``[tokens, n]``,
    all float32 as the recurrence is computed; a call's ``A`` and its state
    in and out."""
    di, n = sizes(model)["d_inner"], model["d_state"]
    return STATE_BYTES * (tokens * (3 * di + 2 * n) + calls * 3 * n * di)


# How to find the layer's operations in the device trace (benchmark/xtrace.py
# sees an op's own name, numbered suffix dropped): the scan kernel's Mosaic
# custom call carries the name its ``pallas_call`` gives it
# (horovod_tpu/ops/ssm_scan.py).  ``sample`` is one op class the pattern
# matches (the tests' hand-made traces).
KERNELS = [
    {"name": "ssm_scan", "match": r"^ssm_scan", "sample": "ssm_scan"},
]
