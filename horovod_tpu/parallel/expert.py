"""Expert parallelism: Mixture-of-Experts with all_to_all token routing.

Beyond-parity extension (SURVEY.md §2.3 "Expert parallelism: NO").  The
design is the standard Switch/GShard formulation mapped onto a mesh axis:

* every device holds ``num_experts / axis_size`` expert MLPs,
* a router picks top-k experts per token with a capacity limit,
* tokens are dispatched to their experts with ONE ``all_to_all`` (the
  ICI-native equivalent of the reference's point-to-point sends — there
  are none in the reference; MPI_Alltoall would be the analogue),
* expert outputs return with a second ``all_to_all`` and are combined by
  router weight.

Everything is dense einsums over static shapes (dispatch/combine one-hot
tensors), so XLA tiles it onto the MXU and overlaps the two collectives —
no scalar gather/scatter loops.

**Fused hot path** (hvd-fuse, arXiv:2305.06942; ops/fused.py): the
dispatch all_to_all → expert FFN GEMMs → combine all_to_all pipeline is
chunked along the CAPACITY axis — each chunk runs the full round trip,
so chunk *i*'s all_to_all legs fly while chunk *i+1*'s FFN computes,
inside ONE XLA program.  Routing (router GEMM, top-k dispatch, aux
loss) stays whole: it is the producer every chunk depends on.  The
chunked output is BITWISE-identical to the unfused reference
(tests/test_fused.py): capacity rows are reduction-free, each chunk's
einsums keep the unfused contraction order, and the combine all_to_all
inverts the dispatch all_to_all's tiled row permutation chunk-by-chunk
so the concatenation restores the exact unfused layout.
``HVD_TPU_FUSE=off`` (or ``fuse=False``) pins the unfused reference
program; ``HVD_TPU_FUSE_CHUNKS`` bounds the chunk count (both knobs
ride the HELLO env fingerprint).

Conventionally EP rides the *data* axis (expert groups = DP groups):
pass ``axis_name="data"``; a dedicated ``expert`` axis works identically.
"""

from __future__ import annotations

from typing import NamedTuple, Optional

import jax
import jax.numpy as jnp

from ..ops import fused as _fused


class MoEOutput(NamedTuple):
    out: jnp.ndarray          # [tokens, d_model]
    aux_loss: jnp.ndarray     # scalar load-balancing loss
    dropped_fraction: jnp.ndarray  # scalar, tokens beyond capacity


def init_moe_params(key, num_experts: int, d_model: int, d_hidden: int,
                    dtype=jnp.float32) -> dict:
    """Full (unsharded) expert stack + router; shard the leading expert
    axis over the EP mesh axis before use (or index with
    :func:`local_experts`)."""
    k1, k2, k3 = jax.random.split(key, 3)
    scale_in = d_model ** -0.5
    scale_out = d_hidden ** -0.5
    return {
        "router": jax.random.normal(k1, (d_model, num_experts),
                                    dtype) * scale_in,
        "w_in": jax.random.normal(k2, (num_experts, d_model, d_hidden),
                                  dtype) * scale_in,
        "w_out": jax.random.normal(k3, (num_experts, d_hidden, d_model),
                                   dtype) * scale_out,
    }


def local_experts(params: dict, *, axis_name: str) -> dict:
    """Slice this device's expert shard (inside shard_map) from replicated
    full params; the router stays replicated."""
    n = jax.lax.axis_size(axis_name)
    idx = jax.lax.axis_index(axis_name)

    def shard(leaf):
        size = leaf.shape[0] // n
        return jax.lax.dynamic_slice_in_dim(leaf, idx * size, size, axis=0)

    return {"router": params["router"],
            "w_in": shard(params["w_in"]),
            "w_out": shard(params["w_out"])}


def _top_k_dispatch(probs, k: int, capacity: int):
    """Greedy top-k routing with per-expert capacity.

    Returns dispatch ``[t, E, C]`` (0/1) and combine ``[t, E, C]``
    (gate-weighted) tensors, plus the dropped-token fraction.
    """
    tokens, num_experts = probs.shape
    remaining = probs
    dispatch = jnp.zeros((tokens, num_experts, capacity), probs.dtype)
    combine = jnp.zeros((tokens, num_experts, capacity), probs.dtype)
    # Tokens already admitted per expert (running fill count).
    fill = jnp.zeros((num_experts,), jnp.int32)
    routed = jnp.zeros((tokens,), probs.dtype)
    for _ in range(k):
        choice = jnp.argmax(remaining, axis=-1)              # [t]
        gate = jnp.take_along_axis(remaining, choice[:, None],
                                   axis=-1)[:, 0]            # [t]
        onehot = jax.nn.one_hot(choice, num_experts,
                                dtype=probs.dtype)           # [t, E]
        # Position of each token within its chosen expert's buffer:
        # earlier tokens first (cumsum order), offset by the current fill.
        pos = (jnp.cumsum(onehot, axis=0) - 1.0
               + fill[None, :].astype(probs.dtype))          # [t, E]
        pos_tok = jnp.sum(pos * onehot, axis=-1)             # [t]
        keep = pos_tok < capacity
        pos_oh = jax.nn.one_hot(
            jnp.clip(pos_tok, 0, capacity - 1).astype(jnp.int32),
            capacity, dtype=probs.dtype)                     # [t, C]
        d = (onehot * keep[:, None].astype(probs.dtype))[:, :, None] \
            * pos_oh[:, None, :]
        dispatch = dispatch + d
        combine = combine + d * gate[:, None, None]
        fill = fill + jnp.sum(
            onehot * keep[:, None].astype(probs.dtype),
            axis=0).astype(jnp.int32)
        routed = routed + keep.astype(probs.dtype)
        # Exclude the chosen expert from the next round.
        remaining = remaining * (1.0 - onehot)
    dropped = 1.0 - jnp.mean(routed) / k
    return dispatch, combine, dropped


def moe_layer(x, params: dict, *, axis_name: str, num_experts: int,
              top_k: int = 2, capacity_factor: float = 1.25,
              activation=jax.nn.gelu,
              aux_loss_weight: float = 1e-2,
              fuse: Optional[bool] = None,
              fuse_chunks: Optional[int] = None) -> MoEOutput:
    """Sharded mixture-of-experts FFN (inside shard_map over
    ``axis_name``).

    Args:
      x: ``[tokens_local, d_model]`` — this shard's tokens.
      params: ``router [d, E]`` (replicated), ``w_in [E_local, d, h]``,
        ``w_out [E_local, h, d]`` — expert leading axes already sharded
        (e.g. via :func:`local_experts`).
      num_experts: global expert count E (must divide by the axis size).
      fuse: override the ``HVD_TPU_FUSE`` knob for this layer —
        ``False`` pins the unfused reference program (bitwise-identical
        output either way; see the module docstring).
      fuse_chunks: override ``HVD_TPU_FUSE_CHUNKS`` — capacity-axis
        chunks of the fused dispatch→FFN→combine round trip.
    """
    n = jax.lax.axis_size(axis_name)
    tokens, d_model = x.shape
    e_local = num_experts // n
    if e_local * n != num_experts:
        raise ValueError(f"num_experts ({num_experts}) must divide by the "
                         f"'{axis_name}' axis size ({n})")
    if params["w_in"].shape[0] != e_local:
        raise ValueError(
            f"params carry {params['w_in'].shape[0]} local experts but "
            f"num_experts/axis_size = {e_local}; shard them with "
            f"local_experts() first")
    capacity = max(1, int(tokens * capacity_factor * top_k / num_experts))

    logits = jnp.dot(x, params["router"],
                     preferred_element_type=jnp.float32)
    probs = jax.nn.softmax(logits, axis=-1)
    dispatch, combine, dropped = _top_k_dispatch(probs, top_k, capacity)

    # Load-balancing auxiliary loss (Switch Transformer eq. 4): fraction
    # of tokens per expert × mean router probability per expert.
    token_frac = jnp.mean(jnp.sum(dispatch, axis=-1), axis=0)
    prob_frac = jnp.mean(probs, axis=0)
    aux = aux_loss_weight * num_experts * jnp.sum(
        token_frac * prob_frac)

    # Dispatch: [t, d] x [t, E, C] -> [E, C, d]; ship each device its
    # experts' buffers from every peer.
    expert_in = jnp.einsum("td,tec->ecd", x.astype(jnp.float32),
                           dispatch.astype(jnp.float32))
    w_in = params["w_in"].astype(jnp.float32)
    w_out = params["w_out"].astype(jnp.float32)

    def roundtrip(buf):
        # One capacity chunk's full trip: route out, compute, route
        # back.  [E, c, d] -> [E_local, n*c, d] -> ... -> [E, c, d].
        buf = jax.lax.all_to_all(buf, axis_name, split_axis=0,
                                 concat_axis=1, tiled=True)
        # Run the local experts on everyone's tokens.
        h = jnp.einsum("ecd,edh->ech", buf, w_in)
        h = activation(h)
        o = jnp.einsum("ech,ehd->ecd", h, w_out)
        # Return trip: the inverse all_to_all undoes the dispatch
        # leg's tiled row permutation within the chunk.
        return jax.lax.all_to_all(o, axis_name, split_axis=1,
                                  concat_axis=0, tiled=True)

    # hvd-fuse: emit the round trip per capacity chunk inside this one
    # program — chunk i's all_to_all legs overlap chunk i+1's FFN.
    # One chunk (or fuse=False) IS the unfused reference program.
    expert_out = _fused.chunked_map(roundtrip, expert_in, axis=1,
                                    chunks=fuse_chunks, fuse=fuse)
    out = jnp.einsum("ecd,tec->td", expert_out,
                     combine.astype(jnp.float32))
    return MoEOutput(out.astype(x.dtype), aux.astype(jnp.float32),
                     dropped.astype(jnp.float32))


# ---------------------------------------------------------------------------
# One chip's share of an expert-parallel group, with no mesh axis at all
# (docs/inference.md "Latent-attention mixture of experts").  The layer is
# TOLD which experts it holds, routes over all of them, drops nothing, and
# computes the part of the result its own experts give.  Work follows the
# assignments: (token, expert) pairs are sorted by expert and run through
# grouped matrix products (``jax.lax.ragged_dot``) in fixed-size chunks of
# rows, as many chunks as the pairs on held experts need.
# ---------------------------------------------------------------------------


class HeldExpertsOutput(NamedTuple):
    out: jnp.ndarray      # [tokens, d_model] float32: shared + held experts
    #                       + the zero-compute experts' ``w x``
    counts: jnp.ndarray   # [experts_held] int32: assignments computed here
    zero_pairs: jnp.ndarray    # int32: pairs that went to zero-compute experts
    routed_pairs: jnp.ndarray  # int32: all pairs routed (live tokens x top_k)


def route_sigmoid_top_k(x, router, top_k: int, routed_scale: float = 1.0,
                        norm_topk: bool = True):
    """Sigmoid scores over ALL experts, the ``top_k`` largest, weights
    ``g_e / sum of the chosen * routed_scale``.  The scores are computed
    in float32 at the highest matmul precision: a near-tied choice should
    flip on the hidden state's rounding, not on the router's own.
    Returns ``(experts [t, k] int32, weights [t, k] float32)``."""
    scores = jax.nn.sigmoid(jnp.dot(
        x.astype(jnp.float32), router.astype(jnp.float32),
        precision=jax.lax.Precision.HIGHEST))
    gate, idx = jax.lax.top_k(scores, top_k)
    if norm_topk:
        gate = gate / jnp.sum(gate, axis=-1, keepdims=True)
    return idx.astype(jnp.int32), gate * routed_scale


def route_softmax_top_k(x, router, bias, top_k: int,
                        routed_scale: float = 1.0):
    """Softmax scores over ALL of the router's outputs; the ``top_k``
    largest of ``scores + bias`` are CHOSEN (``bias [outputs]``: a
    trained score-correction buffer that balances the load) and weighted
    by the UNBIASED scores ``* routed_scale``, not renormalised.  float32
    at the highest matmul precision, as :func:`route_sigmoid_top_k`.
    Returns ``(experts [t, k] int32, weights [t, k] float32)``."""
    scores = jax.nn.softmax(jnp.dot(
        x.astype(jnp.float32), router.astype(jnp.float32),
        precision=jax.lax.Precision.HIGHEST), axis=-1)
    _, idx = jax.lax.top_k(scores + bias.astype(jnp.float32), top_k)
    gate = jnp.take_along_axis(scores, idx, axis=-1)
    return idx.astype(jnp.int32), gate * routed_scale


def route_sigmoid_bias_top_k(x, router, bias, top_k: int,
                             routed_scale: float = 1.0,
                             norm_topk: bool = True):
    """Sigmoid scores over ALL of the router's outputs; the ``top_k``
    largest of ``scores + bias`` are CHOSEN (``bias [outputs]``: the
    load-balancing buffer) and weighted by the UNBIASED scores,
    normalised over the chosen (``/ (their sum + 1e-20)``, with
    ``norm_topk``) and ``* routed_scale``: the bias moves the choice and
    never the weight.  float32 at the highest matmul precision, as
    :func:`route_sigmoid_top_k`.  Returns ``(experts [t, k] int32,
    weights [t, k] float32)``."""
    scores = jax.nn.sigmoid(jnp.dot(
        x.astype(jnp.float32), router.astype(jnp.float32),
        precision=jax.lax.Precision.HIGHEST))
    _, idx = jax.lax.top_k(scores + bias.astype(jnp.float32), top_k)
    gate = jnp.take_along_axis(scores, idx, axis=-1)
    if norm_topk:
        gate = gate / (jnp.sum(gate, axis=-1, keepdims=True) + 1e-20)
    return idx.astype(jnp.int32), gate * routed_scale


def route_sigmoid_bias_group_top_k(x, router, bias, top_k: int,
                                   n_group: int, topk_group: int,
                                   routed_scale: float = 1.0,
                                   norm_topk: bool = True):
    """:func:`route_sigmoid_bias_top_k` with the choice limited to groups
    (DeepSeek-V3's ``noaux_tc``): the router's outputs are ``n_group``
    groups of equal size; the ``topk_group`` groups with the largest sum
    of their two best ``scores + bias`` are kept, and the ``top_k``
    largest ``scores + bias`` among THEIR experts are chosen.  Weights
    from the unbiased scores, as there.  Returns ``(experts [t, k] int32,
    weights [t, k] float32)``."""
    scores = jax.nn.sigmoid(jnp.dot(
        x.astype(jnp.float32), router.astype(jnp.float32),
        precision=jax.lax.Precision.HIGHEST))
    t, n = scores.shape
    biased = (scores + bias.astype(jnp.float32)).reshape(t, n_group, -1)
    group_score = jnp.sum(jax.lax.top_k(biased, 2)[0], axis=-1)
    _, best = jax.lax.top_k(group_score, topk_group)
    kept = jnp.any(best[:, :, None] == jnp.arange(n_group)[None, None, :],
                   axis=1)
    _, idx = jax.lax.top_k(
        jnp.where(kept[:, :, None], biased, -jnp.inf).reshape(t, n), top_k)
    gate = jnp.take_along_axis(scores, idx, axis=-1)
    if norm_topk:
        gate = gate / (jnp.sum(gate, axis=-1, keepdims=True) + 1e-20)
    return idx.astype(jnp.int32), gate * routed_scale


def swiglu(x, w_gate, w_up, w_down):
    """``down(silu(gate(x)) * up(x))``, float32 accumulation."""
    g = jnp.dot(x, w_gate, preferred_element_type=jnp.float32)
    u = jnp.dot(x, w_up, preferred_element_type=jnp.float32)
    return jnp.dot((jax.nn.silu(g) * u).astype(x.dtype), w_down,
                   preferred_element_type=jnp.float32)


def held_chunk_rows(tokens: int, top_k: int, experts_held: int,
                    num_experts: int) -> int:
    """Rows of one chunk of sorted assignments: twice what balanced
    routing sends to the held experts, a power of two in [128, 2048] and
    no more than the layer can ever see.  A skewed batch takes more
    chunks, never fewer tokens."""
    want = 2 * tokens * top_k * experts_held // num_experts
    rows = 128
    while rows < min(want, 2048):
        rows *= 2
    return max(8, min(rows, -(-tokens * top_k // 8) * 8))


def moe_layer_held(x, params: dict, *, num_experts: int,
                   expert_offset: int, top_k: int,
                   routed_scale: float = 1.0, norm_topk: bool = True,
                   token_mask=None,
                   chunk_rows: Optional[int] = None,
                   routing=None,
                   zero_experts: int = 0) -> HeldExpertsOutput:
    """The share of a SwiGLU expert layer that ONE member of an
    expert-parallel group computes: no mesh axis, no exchange, no
    capacity, no dropped token.

    ``x``: ``[tokens, d_model]``.  ``params``: ``router [d, num_experts]``
    (whole, as every member holds it), ``w_gate``/``w_up [experts_held,
    d, f]`` and ``w_down [experts_held, f, d]``: experts ``expert_offset
    .. expert_offset + experts_held`` of the router's ``num_experts``
    outputs, and, where the model has one, ``shared`` (``w_gate``/``w_up``
    ``[d, f_s]``, ``w_down [f_s, d]``: counted once, by every member
    alike).  ``routing(x) -> (experts [t, top_k], weights [t, top_k])``
    is the caller's (:func:`route_softmax_top_k` with a choice bias);
    left out, it is :func:`route_sigmoid_top_k` over ``params["router"]``
    with ``routed_scale`` and ``norm_topk``.  The LAST ``zero_experts``
    of the router's outputs are zero-compute experts: they hold no
    weights, a chosen one returns its input, so the layer adds ``w x``
    where the token lives (every member alike: counted once when shares
    are added).  ``token_mask`` (``[tokens]`` bool) takes padding and
    idle rows out of the routing: they reach no expert and count
    nowhere.  What the absent experts would add is left out; the caller
    sends the partial result on.
    """
    t, d = x.shape
    held = params["w_gate"].shape[0]
    if not 0 <= expert_offset <= num_experts - zero_experts - held:
        raise ValueError(
            f"experts {expert_offset}..{expert_offset + held} are not "
            f"among the router's {num_experts - zero_experts}"
            + (f" (its last {zero_experts} outputs compute nothing)"
               if zero_experts else ""))
    if routing is None:
        idx, gate = route_sigmoid_top_k(x, params["router"], top_k,
                                        routed_scale, norm_topk)
    else:
        idx, gate = routing(x)
    local = idx - expert_offset
    here = (local >= 0) & (local < held)
    if token_mask is not None:
        here = here & token_mask[:, None]
    # Assignments sorted by held expert; everything else sorts last.
    flat = jnp.where(here, local, held).reshape(-1)
    order = jnp.argsort(flat, stable=True)
    counts = jnp.bincount(flat, length=held + 1)[:held].astype(jnp.int32)
    ends = jnp.cumsum(counts)
    starts = ends - counts
    n_rows = ends[-1]

    rows = chunk_rows or held_chunk_rows(t, top_k, held, num_experts)
    padded = -(-t * top_k // rows) * rows
    pad = padded - t * top_k
    tok = jnp.pad((order // top_k).astype(jnp.int32), (0, pad))
    w_sorted = jnp.pad(gate.reshape(-1)[order], (0, pad))

    def chunk(c, acc):
        base = c * rows
        tok_c = jax.lax.dynamic_slice(tok, (base,), (rows,))
        w_c = jax.lax.dynamic_slice(w_sorted, (base,), (rows,))
        valid = base + jnp.arange(rows, dtype=jnp.int32) < n_rows
        sizes = (jnp.clip(ends - base, 0, rows)
                 - jnp.clip(starts - base, 0, rows)).astype(jnp.int32)
        xs = x[tok_c]
        g = jax.lax.ragged_dot(xs, params["w_gate"], sizes,
                               preferred_element_type=jnp.float32)
        u = jax.lax.ragged_dot(xs, params["w_up"], sizes,
                               preferred_element_type=jnp.float32)
        y = jax.lax.ragged_dot((jax.nn.silu(g) * u).astype(x.dtype),
                               params["w_down"], sizes,
                               preferred_element_type=jnp.float32)
        # Rows past the chunk's last group hold whatever the grouped
        # product left there: selected away, not multiplied away.
        y = jnp.where(valid[:, None], y * w_c[:, None], 0.0)
        return acc.at[tok_c].add(y)

    with jax.named_scope("moe_routed"):
        routed = jax.lax.fori_loop(0, (n_rows + rows - 1) // rows, chunk,
                                   jnp.zeros((t, d), jnp.float32))
    out = routed
    if "shared" in params:
        with jax.named_scope("moe_shared"):
            s = params["shared"]
            shared = swiglu(x, s["w_gate"], s["w_up"], s["w_down"])
        out = shared + routed
    live = (jnp.ones((t,), bool) if token_mask is None else token_mask)
    zero_pairs = jnp.zeros((), jnp.int32)
    if zero_experts:
        to_zero = (idx >= num_experts - zero_experts) & live[:, None]
        with jax.named_scope("moe_zero"):
            w_zero = jnp.sum(jnp.where(to_zero, gate, 0.0), axis=-1)
            out = out + x.astype(jnp.float32) * w_zero[:, None]
        zero_pairs = jnp.sum(to_zero, dtype=jnp.int32)
    return HeldExpertsOutput(out, counts, zero_pairs,
                             jnp.sum(live, dtype=jnp.int32) * top_k)
