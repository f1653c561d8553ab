"""The ONE prompt-header chain-hash scheme, shared router ↔ replica.

Prefix-affinity routing only works if the router derives EXACTLY the
keys the replica's shared-prefix index holds: the page-aligned chain
hash of ``serving/kv_cache.py``.  A silent scheme divergence (different
dtype, different page alignment, a missing fingerprint seed) would not
error — it would quietly zero the affinity hit rate while the router
believes it is routing warm.  So the scheme lives HERE, in the jax-free
routing tier, and :meth:`~horovod_tpu.serving.kv_cache.PagedKVCache.
_chain_hashes` delegates to it — byte-identical by construction, and
CI-gated by tests/test_routing.py against a live cache.

The scheme: ``h = sha256(fingerprint)``, then per page ``j`` the hash
absorbs that page's token ids as little-endian int32 bytes and emits
its digest — ``h_j`` commits to the model fingerprint AND every token
of pages ``0..j``, so a hit on page ``j`` implies the whole prefix
matches with no token comparison.  ``fingerprint`` is the engine's
model-identity JSON (``serving/models.py``'s ``identity()``, sorted keys),
exported verbatim in ``/healthz`` so the router self-configures from
the replicas it fronts.
"""

from __future__ import annotations

import hashlib
from typing import List, Sequence

import numpy as np


def chain_hashes(fingerprint: bytes, tokens: Sequence[int],
                 page_size: int, n_pages: int) -> List[bytes]:
    """Chain hash per page boundary over ``tokens[:n_pages *
    page_size]`` — the index keys of ``PagedKVCache`` (which delegates
    its ``_chain_hashes`` here)."""
    h = hashlib.sha256(fingerprint)
    out: List[bytes] = []
    ps = int(page_size)
    for j in range(n_pages):
        h.update(np.asarray(tokens[j * ps:(j + 1) * ps],
                            np.int32).tobytes())
        out.append(h.digest())
    return out


def prompt_header_hashes(fingerprint: bytes, tokens: Sequence[int],
                         page_size: int,
                         pages_per_slot: int) -> List[str]:
    """Hex chain hashes of a prompt's page-aligned STRICT-prefix header
    — the router-side mirror of ``PagedKVCache.lookup_prefix``'s key
    sequence (same ``min((len - 1) // page_size, pages_per_slot)``
    bound: at least one suffix token always remains for the replica to
    prefill)."""
    if not tokens:
        return []
    max_pages = min((len(tokens) - 1) // int(page_size),
                    int(pages_per_slot))
    if max_pages <= 0:
        return []
    return [d.hex() for d in chain_hashes(fingerprint, tokens,
                                          page_size, max_pages)]


def published_page_hashes(fingerprint: bytes, tokens: Sequence[int],
                          page_size: int,
                          pages_per_slot: int) -> List[str]:
    """Hex chain hashes of the pages a replica PUBLISHES after fully
    prefilling ``tokens`` (``PagedKVCache.publish_prefix``'s key set:
    every page entirely covered by the prompt, NOT the strict-prefix
    bound) — what the router adds to its model of a replica's index
    after a completed dispatch."""
    n_full = min(len(tokens) // int(page_size), int(pages_per_slot))
    if n_full <= 0:
        return []
    return [d.hex() for d in chain_hashes(fingerprint, tokens,
                                          page_size, n_full)]
