"""The comparison that decides ``correct``: norms by the worst leaf, the
loss, and a served token's gap below the reference's best.

Every number compared is printed beside its limit in every run."""

from __future__ import annotations

import statistics
from typing import Dict, List, Sequence


def worst_leaf_gap(got: Dict[str, float], ref: Dict[str, float]) -> dict:
    """The gap between the program's norm and the reference's (NOT the norm
    of their difference), measured against the reference's norm of that
    leaf or of the median leaf, whichever is larger: some gradients are all
    but zero."""
    if set(got) != set(ref):
        raise ValueError(f"leaves differ: {sorted(set(got) ^ set(ref))}")
    med = statistics.median(ref.values())
    worst, where = 0.0, None
    for k, r in ref.items():
        g = got[k]
        if not (g == g):                      # NaN never passes
            return {"gap": float("inf"), "leaf": k}
        gap = abs(g - r) / max(r, med, 1e-30)
        if gap >= worst:
            worst, where = gap, k
    return {"gap": worst, "leaf": where}


def loss_gap(got: Sequence[float], ref: Sequence[float]) -> float:
    """Largest relative difference over the steps followed."""
    return max(abs(g - r) / max(abs(r), 1e-30) if g == g else float("inf")
               for g, r in zip(got, ref))


def verdict(numbers: Dict[str, float], limits: Dict[str, float]) -> dict:
    """Each number beside its limit, and whether all are inside."""
    rows = []
    ok = True
    for name, value in numbers.items():
        limit = limits[name]
        inside = bool(value == value and value <= limit)
        ok = ok and inside
        rows.append({"number": name, "value": value, "limit": limit,
                     "inside": inside})
    return {"correct": ok, "compared": rows}


def served_token_gaps(ref_logits, served: List[int]) -> List[float]:
    """For each served (greedy) token, how far its reference logit lies
    below the reference's best at that position.  ``ref_logits`` is
    ``[len(served), vocab]``."""
    import numpy as np

    ref_logits = np.asarray(ref_logits, np.float32)
    idx = np.arange(len(served))
    return (ref_logits.max(axis=-1) - ref_logits[idx, served]).tolist()
