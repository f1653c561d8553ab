"""Operations and bytes the GPT-2 family needs, counted from shapes.

``train_flops_per_item``: forward and backward of one token at the job's
sequence length, a multiply-add counted as 2, backward twice the forward,
causal attention counted at the half of the square it needs, nothing
recomputed.  ``KERNELS`` gives, for each hand-written kernel on the path,
how to find its calls in the device trace and the least work one training
step asks of it.
"""

from __future__ import annotations


def forward_flops_per_item(model: dict, seq_len: int) -> float:
    d, f, n, v = (model["n_embd"], model["n_inner"], model["n_layer"],
                  model["vocab_size"])
    per_layer = 2.0 * d * 3 * d + 2.0 * d * d + 2.0 * 2 * d * f
    attention = 2.0 * 2 * d * seq_len / 2.0        # QK^T and PV, causal half
    return n * (per_layer + attention) + 2.0 * d * v


def train_flops_per_item(model: dict, job: dict) -> float:
    return 3.0 * forward_flops_per_item(model, job["seq_len"])


def _flash_step_work(model: dict, job: dict, rows: int) -> dict:
    """Flash attention over one training step on one chip: the forward's
    two matmuls and the backward's five (S, dP, dV, dQ, dK), over the
    causal half; bytes are q, k, v, o, do read and o, dq, dk, dv written
    once each in bfloat16 (the least any schedule moves)."""
    s, d, n = job["seq_len"], model["n_embd"], model["n_layer"]
    square = 2.0 * rows * s * s * d / 2.0          # one matmul, causal half
    tensor = rows * s * d * 2.0                    # one [rows, s, d] bf16
    return {"flops": n * 7.0 * square, "bytes": n * 9.0 * tensor}


# The program's Pallas kernels carry no name of their own: their Mosaic custom
# calls reach the trace named after the jax transformation that produced them,
# ``jvp__`` (forward) and ``transpose_jvp___`` (the two backward kernels).
KERNELS = [
    {"name": "flash_attn", "match": r"^(transpose_)?jvp_+$",
     "work": _flash_step_work},
]
