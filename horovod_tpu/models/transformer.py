"""Transformer LM family with composable 5-way parallelism.

Beyond-parity flagship for the long-context/distributed stack (the
reference has no models of its own — SURVEY.md §1 "no model zoo"; its
examples lean on TF/Keras/Torch zoos).  A decoder-only LM whose forward
is written for ``shard_map`` over a :func:`..core.topology.make_mesh`
mesh, composing:

* **DP** — batch sharded over ``data``; gradients reduce via shard_map AD
  (replicated-param transpose = psum, verified exact in tests).
* **TP** — attention heads + MLP hidden sharded over ``model``
  (column/row-parallel, :mod:`..parallel.tensor`).
* **SP** — sequence sharded over ``seq``; attention runs the Pallas ring
  attention (:mod:`..parallel.sequence`).
* **EP** — optional MoE FFN layers with experts sharded over the data
  axis (:mod:`..parallel.expert`), the conventional EP placement.
* **PP** — layers split into stages over ``pipe`` with GPipe
  microbatching (:mod:`..parallel.pipeline`).

Parameter storage is replicated; sharded *compute* slices its shard
in-trace (``local_shard`` / ``select_stage_params`` / ``local_experts``).
This keeps the optimizer and Horovod-parity broadcast/checkpoint paths
strategy-agnostic; for sharded parameter *storage* compose any loss with
the model-agnostic FSDP/ZeRO-3 builder (:mod:`..parallel.fsdp`).
"""

from __future__ import annotations

from dataclasses import dataclass, field
from functools import partial
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np

from ..core import topology as T
from ..parallel.expert import local_experts, moe_layer
from ..parallel.pipeline import gpipe
from ..parallel.sequence import ring_attention
from ..parallel.tensor import (column_parallel, local_shard, row_parallel,
                               tp_mlp)
from ..ops.flash_attention import flash_attention_qkv


@dataclass(frozen=True)
class TransformerConfig:
    vocab_size: int = 256
    d_model: int = 128
    n_heads: int = 8
    n_layers: int = 4
    d_ff: int = 512
    max_seq_len: int = 2048
    dtype: object = jnp.float32
    # Mixture-of-experts FFN (replaces the dense MLP on every layer when
    # num_experts > 0).
    num_experts: int = 0
    top_k: int = 2
    capacity_factor: float = 2.0
    # Attention kernel blocks (MXU-aligned on TPU).
    block_q: int = 128
    block_k: int = 128
    # Rematerialize each layer in the backward pass (jax.checkpoint):
    # activation memory drops from O(n_layers) to O(1) layers at ~1/3
    # more FLOPs — the standard trade for long sequences / deep stacks.
    remat: bool = False
    # Chunked cross-entropy: compute the loss (next_token_nll, below)
    # over sequence chunks of this many positions, rematerializing each
    # chunk's logits in the backward pass.  The [batch, seq, vocab]
    # float32 logits tensor — the dominant long-context allocation (e.g.
    # 8.6 GB at batch 8, seq 8192, vocab 32768) — never materializes;
    # peak extra memory is one chunk's logits.  0 = off (one full-logits
    # matmul, whose logits live until the loss's backward has read them).
    loss_chunk: int = 0


@dataclass(frozen=True)
class ParallelAxes:
    """Which mesh axis serves each strategy (None = strategy off)."""
    data: Optional[str] = T.DATA_AXIS
    model: Optional[str] = None
    seq: Optional[str] = None
    pipe: Optional[str] = None
    expert: Optional[str] = None  # conventionally = data
    num_microbatches: int = 2     # pipeline depth-filling factor


def init_transformer(key, cfg: TransformerConfig) -> dict:
    """Parameter pytree; per-layer leaves are stacked on a leading
    ``n_layers`` axis (scan/pipeline friendly)."""
    n, d, f, v = cfg.n_layers, cfg.d_model, cfg.d_ff, cfg.vocab_size
    keys = iter(jax.random.split(key, 16))
    dt = cfg.dtype
    s_d = d ** -0.5
    p = {
        "embed": jax.random.normal(next(keys), (v, d), dt) * 0.02,
        "pos_embed": jax.random.normal(next(keys),
                                       (cfg.max_seq_len, d), dt) * 0.02,
        "ln_f": {"scale": jnp.ones((d,), dt), "bias": jnp.zeros((d,), dt)},
        "unembed": jax.random.normal(next(keys), (d, v), dt) * s_d,
        "layers": {
            "ln1": {"scale": jnp.ones((n, d), dt),
                    "bias": jnp.zeros((n, d), dt)},
            "wq": jax.random.normal(next(keys), (n, d, d), dt) * s_d,
            "wk": jax.random.normal(next(keys), (n, d, d), dt) * s_d,
            "wv": jax.random.normal(next(keys), (n, d, d), dt) * s_d,
            "wo": jax.random.normal(next(keys), (n, d, d), dt) * s_d,
            "ln2": {"scale": jnp.ones((n, d), dt),
                    "bias": jnp.zeros((n, d), dt)},
        },
    }
    if cfg.num_experts > 0:
        e = cfg.num_experts
        p["layers"]["router"] = (
            jax.random.normal(next(keys), (n, d, e), dt) * s_d)
        p["layers"]["moe_w_in"] = (
            jax.random.normal(next(keys), (n, e, d, f), dt) * s_d)
        p["layers"]["moe_w_out"] = (
            jax.random.normal(next(keys), (n, e, f, d), dt)
            * (f ** -0.5))
    else:
        p["layers"]["w_in"] = (
            jax.random.normal(next(keys), (n, d, f), dt) * s_d)
        p["layers"]["b_in"] = jnp.zeros((n, f), dt)
        p["layers"]["w_out"] = (
            jax.random.normal(next(keys), (n, f, d), dt) * (f ** -0.5))
        p["layers"]["b_out"] = jnp.zeros((n, d), dt)
    return p


def _layernorm(x, scale, bias, eps=1e-5):
    mu = jnp.mean(x, axis=-1, keepdims=True)
    var = jnp.var(x, axis=-1, keepdims=True)
    return (x - mu) * jax.lax.rsqrt(var + eps) * scale + bias


def _attention_block(x, lp, cfg: TransformerConfig, ax: ParallelAxes,
                     aux_acc):
    """Pre-LN attention with TP head sharding + SP ring attention."""
    b, s_loc, d = x.shape
    h = _layernorm(x, lp["ln1"]["scale"], lp["ln1"]["bias"])

    if ax.model is not None:
        mp = jax.lax.axis_size(ax.model)
        if cfg.n_heads % mp != 0 or d % mp != 0:
            raise ValueError(
                f"tensor-parallel degree {mp} must divide both "
                f"n_heads ({cfg.n_heads}) and d_model ({d})")
        wq = local_shard(lp["wq"], 1, axis_name=ax.model)
        wk = local_shard(lp["wk"], 1, axis_name=ax.model)
        wv = local_shard(lp["wv"], 1, axis_name=ax.model)
        wo = local_shard(lp["wo"], 0, axis_name=ax.model)
    else:
        wq, wk, wv, wo = lp["wq"], lp["wk"], lp["wv"], lp["wo"]
        mp = 1
    heads_loc = cfg.n_heads // mp
    head_dim = d // cfg.n_heads

    # One fused [d, 3*d_local] projection instead of three separate
    # gemms: XLA does not merge gemms horizontally, and the wider
    # matmul tiles the MXU better at transformer widths.
    qkv = column_parallel(h, jnp.concatenate([wq, wk, wv], axis=-1),
                          axis_name=ax.model or T.MODEL_AXIS)
    if ax.seq is not None:
        def split_heads(y):
            return y.reshape(b, s_loc, heads_loc, head_dim).transpose(
                0, 2, 1, 3)

        q, k, v = (split_heads(y) for y in jnp.split(qkv, 3, axis=-1))
        attn = ring_attention(q, k, v, axis_name=ax.seq, causal=True,
                              block_q=cfg.block_q, block_k=cfg.block_k)
        attn = attn.transpose(0, 2, 1, 3).reshape(b, s_loc,
                                                  heads_loc * head_dim)
    else:
        # The kernels read q, k and v out of the projection as it lies
        # ([b, s, 3 x heads x head_dim]) wherever the shape allows; the
        # blocks only drive the streaming kernels of long sequences.
        attn = flash_attention_qkv(qkv, heads_loc, causal=True,
                                   block_q=cfg.block_q,
                                   block_k=cfg.block_k)
    if ax.model is not None:
        out = row_parallel(attn, wo, axis_name=ax.model)
    else:
        out = jnp.dot(attn, wo,
                      preferred_element_type=jnp.float32).astype(x.dtype)
    return x + out, aux_acc


def _ffn_block(x, lp, cfg: TransformerConfig, ax: ParallelAxes, aux_acc):
    """Pre-LN FFN: TP dense MLP, or MoE with EP over the expert axis."""
    b, s_loc, d = x.shape
    h = _layernorm(x, lp["ln2"]["scale"], lp["ln2"]["bias"])
    if cfg.num_experts > 0:
        flat = h.reshape(b * s_loc, d)
        params = {"router": lp["router"], "w_in": lp["moe_w_in"],
                  "w_out": lp["moe_w_out"]}
        ep_axis = ax.expert or ax.data
        if ep_axis is not None:
            params = local_experts(params, axis_name=ep_axis)
            out = moe_layer(flat, params, axis_name=ep_axis,
                            num_experts=cfg.num_experts, top_k=cfg.top_k,
                            capacity_factor=cfg.capacity_factor)
        else:
            raise ValueError("MoE needs an expert (or data) mesh axis")
        y = out.out.reshape(b, s_loc, d)
        aux_acc = aux_acc + out.aux_loss
    else:
        if ax.model is not None:
            y = tp_mlp(h, local_shard(lp["w_in"], 1, axis_name=ax.model),
                       local_shard(lp["b_in"], 0, axis_name=ax.model),
                       local_shard(lp["w_out"], 0, axis_name=ax.model),
                       lp["b_out"], axis_name=ax.model)
        else:
            hh = jax.nn.gelu(
                jnp.dot(h, lp["w_in"],
                        preferred_element_type=jnp.float32).astype(x.dtype)
                + lp["b_in"])
            y = (jnp.dot(hh, lp["w_out"],
                         preferred_element_type=jnp.float32).astype(x.dtype)
                 + lp["b_out"])
    return x + y, aux_acc


def _layer(x, lp, cfg, ax, aux_acc):
    x, aux_acc = _attention_block(x, lp, cfg, ax, aux_acc)
    return _ffn_block(x, lp, cfg, ax, aux_acc)


# Remat variant: recompute the layer's activations in the backward pass
# instead of storing them (cfg/ax are static trace-time configuration).
_layer_remat = jax.checkpoint(_layer, static_argnums=(2, 3))


def _layer_fn(cfg):
    return _layer_remat if cfg.remat else _layer


def _index_layer(layers: dict, i):
    return jax.tree_util.tree_map(lambda leaf: leaf[i], layers)


def _slice_layers(layers: dict, start, count: int):
    return jax.tree_util.tree_map(
        lambda leaf: jax.lax.dynamic_slice_in_dim(leaf, start, count,
                                                  axis=0), layers)


def forward(params: dict, tokens, cfg: TransformerConfig,
            ax: ParallelAxes = ParallelAxes(), return_hidden: bool = False):
    """Logits for local token shard; call inside shard_map.

    ``tokens``: ``[batch_local, seq_local]`` int32 — batch sharded over
    ``ax.data``, sequence sharded (shard-major) over ``ax.seq``.
    Returns ``(logits [b, s_loc, vocab], aux_loss scalar)`` — or, with
    ``return_hidden``, the final post-LN hidden states
    ``[b, s_loc, d_model]`` instead of logits (for chunked-loss callers
    that never materialize the full logits tensor).
    """
    b, s_loc = tokens.shape
    seq_off = 0
    global_seq = s_loc
    if ax.seq is not None:
        seq_off = jax.lax.axis_index(ax.seq) * s_loc
        global_seq = s_loc * jax.lax.axis_size(ax.seq)
    if global_seq > cfg.max_seq_len:
        raise ValueError(
            f"global sequence length {global_seq} exceeds "
            f"cfg.max_seq_len {cfg.max_seq_len}; positions would clamp "
            f"silently")
    pos = seq_off + jnp.arange(s_loc)
    x = params["embed"][tokens] + jnp.take(params["pos_embed"], pos,
                                           axis=0)
    aux = jnp.zeros((), jnp.float32)

    if ax.pipe is not None:
        n_stages = jax.lax.axis_size(ax.pipe)
        per_stage = cfg.n_layers // n_stages
        if per_stage * n_stages != cfg.n_layers:
            raise ValueError(
                f"n_layers {cfg.n_layers} not divisible by pipeline "
                f"stages {n_stages}")
        stage = jax.lax.axis_index(ax.pipe)
        mine = _slice_layers(params["layers"], stage * per_stage,
                             per_stage)

        # MoE aux loss inside the pipeline would need to ride the
        # activations; restrict PP to dense FFN layers for now.
        if cfg.num_experts > 0:
            raise ValueError("pipeline parallelism currently supports "
                             "dense FFN layers only (num_experts == 0)")

        def stage_fn(stage_params, x_mb):
            for i in range(per_stage):
                x_mb, _ = _layer_fn(cfg)(x_mb,
                                         _index_layer(stage_params, i),
                                         cfg, ax,
                                         jnp.zeros((), jnp.float32))
            return x_mb

        x = gpipe(stage_fn, mine, x,
                  num_microbatches=ax.num_microbatches,
                  axis_name=ax.pipe)
    else:
        for i in range(cfg.n_layers):
            x, aux = _layer_fn(cfg)(x, _index_layer(params["layers"], i),
                                    cfg, ax, aux)

    x = _layernorm(x, params["ln_f"]["scale"], params["ln_f"]["bias"])
    if return_hidden:
        return x, aux
    logits = jnp.dot(x, params["unembed"],
                     preferred_element_type=jnp.float32)
    return logits, aux


def _nll_and_grad(hidden, unembed, targets):
    """Per-row loss and ``d nll / d logits`` (bfloat16) from ONE set of
    float32 logits."""
    logits = jnp.dot(hidden, unembed, preferred_element_type=jnp.float32)
    m = jnp.max(logits, axis=-1, keepdims=True)
    lse = m + jnp.log(jnp.sum(jnp.exp(logits - m), axis=-1, keepdims=True))
    picked = jnp.take_along_axis(logits, targets[:, None], axis=-1)
    column = jax.lax.broadcasted_iota(jnp.int32, logits.shape, 1)
    g = (jnp.exp(logits - lse)
         - (column == targets[:, None])).astype(jnp.bfloat16)
    return (lse - picked)[:, 0], g


@jax.custom_vjp
def _rows_nll(hidden, unembed, targets):
    return _nll_and_grad(hidden, unembed, targets)[0]


def _rows_nll_fwd(hidden, unembed, targets):
    nll, g = _nll_and_grad(hidden, unembed, targets)
    return nll, (g, hidden, unembed)


def _rows_nll_bwd(res, c):
    # The cotangent scales the two [rows, d] arrays, never a pass over
    # [rows, vocab]; ``d unembed`` comes out as [d, vocab].
    g, hidden, unembed = res
    c = c[:, None]
    d_hidden = c * jax.lax.dot_general(
        g, unembed, (((1,), (1,)), ((), ())),
        preferred_element_type=jnp.float32)
    d_unembed = jax.lax.dot_general(
        (c * hidden).astype(hidden.dtype), g, (((0,), (0,)), ((), ())),
        preferred_element_type=jnp.float32)
    return (d_hidden.astype(hidden.dtype), d_unembed.astype(unembed.dtype),
            None)


_rows_nll.defvjp(_rows_nll_fwd, _rows_nll_bwd)


def next_token_nll(hidden, unembed, targets):
    """Next-token negative log-likelihood of every position: ``hidden``
    ``[..., d]`` times ``unembed`` ``[d, vocab]``, ``targets`` int
    ``[...]`` -> float32 ``[...]``.

    The one place the LM's loss lives (``make_loss_fn``'s dense and
    chunked forms, ``chained_lm_loss``'s head stage).  Logits, row
    maximum, sum and ``lse`` are float32, as the plain formula has them, and
    the log-probabilities are never written: the target's logit is
    gathered from the logits themselves.  The gradient of the logits is
    ``softmax - onehot`` in bfloat16 (what the MXU rounds a float32
    operand to anyway), formed in the forward by an ``iota`` compare: no
    scatter, no float32 ``[rows, vocab]`` array but the logits.  On the
    TPU the compiler folds that pass into the operand reads of the two
    backward products (PERF.md section 6, PR 48: 3.1 ms a step faster at
    ``[8192, 50257]`` than holding the bfloat16 array).
    """
    with jax.named_scope("next_token_loss"):
        nll = _rows_nll(hidden.reshape(-1, hidden.shape[-1]), unembed,
                        targets.reshape(-1))
    return nll.reshape(targets.shape)


def make_loss_fn(cfg: TransformerConfig, ax: ParallelAxes = ParallelAxes(),
                 mesh_axes: Optional[tuple] = None):
    """Local shard loss for use inside shard_map: next-token cross-entropy
    (:func:`next_token_nll`, dense or over ``cfg.loss_chunk`` positions at
    a time) pmean-ed over every mesh axis (a replicated logical scalar, so
    ``jax.grad`` outside the shard_map yields exact global gradients).

    ``mesh_axes``: all axis names of the mesh (defaults to the axes named
    in ``ax``).
    """
    axes = mesh_axes
    if axes is None:
        # dedup: ax.expert conventionally aliases ax.data.
        axes = tuple(dict.fromkeys(
            a for a in (ax.data, ax.model, ax.seq, ax.pipe, ax.expert)
            if a is not None))

    def dense_ce(params, tokens, targets):
        x, aux = forward(params, tokens, cfg, ax, return_hidden=True)
        return jnp.mean(next_token_nll(x, params["unembed"], targets)) + aux

    def chunked_ce(params, tokens, targets):
        x, aux = forward(params, tokens, cfg, ax, return_hidden=True)
        b, s_loc, d = x.shape
        chunk = min(cfg.loss_chunk, s_loc)
        if s_loc % chunk != 0:
            raise ValueError(
                f"local sequence length {s_loc} not divisible by "
                f"loss_chunk {chunk}")
        n = s_loc // chunk
        xs = x.reshape(b, n, chunk, d).transpose(1, 0, 2, 3)
        ts = targets.reshape(b, n, chunk).transpose(1, 0, 2)

        @jax.checkpoint
        def chunk_nll(xc, tc):
            return jnp.sum(next_token_nll(xc, params["unembed"], tc))

        def body(total, xt):
            return total + chunk_nll(*xt), None

        total, _ = jax.lax.scan(body, jnp.zeros((), jnp.float32),
                                (xs, ts))
        return total / (b * s_loc) + aux

    def loss_fn(params, batch):
        tokens, targets = batch
        ce = chunked_ce if cfg.loss_chunk > 0 else dense_ce
        loss = ce(params, tokens, targets)
        return jax.lax.pmean(loss, axes)

    return loss_fn


def chained_lm_loss(cfg: TransformerConfig):
    """The transformer LM as a :class:`~..parallel.overlap.ChainedLoss`
    — the segmentable form the backward/communication-overlap step
    streams gradient buckets out of (one backward program per stage:
    embedding, each decoder layer, final-LN+unembed+cross-entropy).

    Single-axis data parallelism with dense FFN layers only (the 5-way
    parallel composition keeps :func:`make_loss_fn`; pipeline/expert
    axes have their own schedules).  Calling the returned object
    evaluates the identical monolithic loss, so ``HVD_TPU_OVERLAP=off``
    differentiates the same math — the identity contract of
    tests/test_overlap.py.  Pair with :func:`chained_lm_params`.
    """
    from ..parallel.overlap import ChainedLoss

    if cfg.num_experts > 0:
        raise ValueError("chained_lm_loss supports dense FFN layers only "
                         "(num_experts == 0)")
    ax = ParallelAxes()

    def embed_stage(p, carry, batch):
        tokens, _targets = batch
        _b, s = tokens.shape
        if s > cfg.max_seq_len:
            raise ValueError(
                f"sequence length {s} exceeds cfg.max_seq_len "
                f"{cfg.max_seq_len}; positions would clamp silently")
        pos = jnp.arange(s)
        return p["embed"][tokens] + jnp.take(p["pos_embed"], pos, axis=0)

    def make_layer_stage():
        def layer_stage(p, carry, batch):
            x, _aux = _layer_fn(cfg)(carry, p, cfg, ax,
                                     jnp.zeros((), jnp.float32))
            return x
        return layer_stage

    def head_stage(p, carry, batch):
        _tokens, targets = batch
        x = _layernorm(carry, p["ln_f"]["scale"], p["ln_f"]["bias"])
        return jnp.mean(next_token_nll(x, p["unembed"], targets))

    stages = [embed_stage]
    stages += [make_layer_stage() for _ in range(cfg.n_layers)]
    stages.append(head_stage)
    return ChainedLoss(stages)


def chained_lm_params(params: dict, cfg: TransformerConfig) -> list:
    """Restructure an :func:`init_transformer` tree into the per-stage
    sequence :func:`chained_lm_loss` expects: ``[embed, layer_0, ...,
    layer_{n-1}, head]`` (per-layer leaves unstacked from their leading
    ``n_layers`` axis — each layer's gradients become their own overlap
    dispatch segment)."""
    out = [{"embed": params["embed"], "pos_embed": params["pos_embed"]}]
    out += [_index_layer(params["layers"], i)
            for i in range(cfg.n_layers)]
    out.append({"ln_f": params["ln_f"], "unembed": params["unembed"]})
    return out


def synthetic_lm_batch(key, global_batch: int, seq_len: int,
                       vocab_size: int):
    """Synthetic next-token data (tokens, shifted targets)."""
    tokens = jax.random.randint(key, (global_batch, seq_len + 1), 0,
                                vocab_size)
    return tokens[:, :-1].astype(jnp.int32), tokens[:, 1:].astype(jnp.int32)


# ---------------------------------------------------------------------------
# Serving path: incremental decode against a fixed-capacity KV view
# (hvd-serve, docs/inference.md).  These functions are the model half of
# horovod_tpu/serving/: no shard_map, no flash attention — a plain
# masked-softmax attention whose program is IDENTICAL between a
# multi-token prefill and a one-token decode step, so the serving
# engine's "prefill + N decode steps ≡ non-incremental forward" contract
# can be tested (and CI-gated) bitwise.  Tensor parallelism for serving
# comes from GSPMD sharding of the KV view's head axis
# (serving/kv_cache.py reuses the parallel/tensor.py head-sharding
# layout), not from shard_map.
# ---------------------------------------------------------------------------


def cache_attention(q, k_view, v_view, q_pos):
    """Masked attention of ``q`` against a fixed-capacity KV view.

    ``q``: ``[b, s, heads, head_dim]`` queries at global positions
    ``q_pos`` (``[b, s]`` int32).  ``k_view``/``v_view``:
    ``[b, capacity, heads, head_dim]`` — entry ``j`` holds the key/value
    of global position ``j`` (the serving engine gathers its paged store
    into this logical order first).  Cache-aware causal masking for
    ragged batches: entry ``j`` participates in row ``(b, i)`` iff
    ``j <= q_pos[b, i]`` — per-sequence lengths ride in through
    ``q_pos``, so one program serves every slot-length mix.

    Rows whose mask is empty (inactive serving slots with
    ``q_pos < 0``) come out all-zero instead of NaN; active rows are
    bitwise-unaffected by the guard (it only ever adds ``0.0``).
    Softmax runs in float32 over the full capacity axis; masked entries
    contribute exact zeros, so results do not depend on how much unused
    capacity follows a sequence.
    """
    b, s, h, hd = q.shape
    cap = k_view.shape[1]
    scale = hd ** -0.5
    scores = jnp.einsum(
        "bshd,bchd->bhsc", q.astype(jnp.float32),
        k_view.astype(jnp.float32)) * scale
    kv_pos = jnp.arange(cap, dtype=jnp.int32)
    mask = kv_pos[None, None, None, :] <= q_pos[:, None, :, None]
    scores = jnp.where(mask, scores, -jnp.inf)
    m = jnp.max(scores, axis=-1, keepdims=True)
    m = jnp.where(jnp.isfinite(m), m, 0.0)  # all-masked rows: exp(-inf)
    p = jnp.exp(scores - m)
    denom = jnp.sum(p, axis=-1, keepdims=True)
    denom = jnp.where(denom == 0.0, 1.0, denom)
    p = p / denom
    out = jnp.einsum("bhsc,bchd->bshd", p, v_view.astype(jnp.float32))
    return out.astype(q.dtype)


def view_attention(q, k, v, k_view, v_view, start_pos, q_pos):
    """The view-dependent part of one serving layer: put the block's
    new ``k``/``v`` rows (``[b, s, heads, head_dim]``) into this
    layer's view (``[b, capacity, heads, head_dim]``) at
    ``start_pos``, then :func:`cache_attention` over it."""

    def put(view_b, new_b, start_b):
        # Per-row scatter, NOT dynamic_update_slice: a slice window is
        # clamped as a whole, so a decode block [token, dummy] landing
        # at start == capacity-1 would shift back one position —
        # overwriting the previous token's entry and leaving the dummy
        # unmasked at capacity-1.  mode="drop" keeps every row at its
        # true index and discards rows past the capacity.  (hvd-serve's
        # scheduler evicts one step before that boundary; this keeps
        # forward_step's own contract exact for any caller stepping at
        # the final cached position.)
        idx = jnp.clip(start_b, 0, None) + jnp.arange(
            new_b.shape[0], dtype=jnp.int32)
        return view_b.at[idx].set(new_b, mode="drop",
                                  unique_indices=True)

    k_full = jax.vmap(put)(k_view, k, start_pos)
    v_full = jax.vmap(put)(v_view, v, start_pos)
    return cache_attention(q, k_full, v_full, q_pos)


def _step_layers(params, tokens, start_pos, cfg: TransformerConfig,
                 attend, roll=False):
    """The serving forward around its attention: ``attend(layer, q, k,
    v, pos)`` is the one part that sees the cached keys and values
    (:func:`forward_step` reads a dense view, :func:`forward_step_paged`
    the page store).  ``roll`` walks the layers with ``lax.scan``, so
    the program holds ONE layer (``layer`` is then traced): the same
    operations in the same order, a fraction of the code to compile
    and load."""
    if cfg.num_experts > 0:
        raise ValueError("the serving path currently supports dense FFN "
                         "layers only (num_experts == 0)")
    b, s = tokens.shape
    h_n, d = cfg.n_heads, cfg.d_model
    if d % h_n != 0:
        raise ValueError(f"d_model {d} not divisible by n_heads {h_n}")
    hd = d // h_n
    pos = start_pos[:, None] + jnp.arange(s, dtype=jnp.int32)[None, :]
    # Inactive slots carry start_pos < 0; clamp the embedding lookup
    # (their rows are masked/garbage anyway, but the gather index must
    # stay in range).
    x = (params["embed"][tokens]
         + jnp.take(params["pos_embed"], jnp.clip(pos, 0, None), axis=0))
    ax = ParallelAxes(data=None)

    def layer(x, i, lp):
        h = _layernorm(x, lp["ln1"]["scale"], lp["ln1"]["bias"])
        # Same fused [d, 3d] projection as the training forward.
        qkv = jnp.dot(
            h, jnp.concatenate([lp["wq"], lp["wk"], lp["wv"]], axis=-1),
            preferred_element_type=jnp.float32).astype(x.dtype)
        q, k, v = (y.reshape(b, s, h_n, hd)
                   for y in jnp.split(qkv, 3, axis=-1))
        attn = attend(i, q, k, v, pos)
        out = jnp.dot(attn.reshape(b, s, d), lp["wo"],
                      preferred_element_type=jnp.float32).astype(x.dtype)
        x, _ = _ffn_block(x + out, lp, cfg, ax, jnp.zeros((), jnp.float32))
        return x, (k, v)

    if roll:
        x, (k_new, v_new) = jax.lax.scan(
            lambda x, il: layer(x, *il), x,
            (jnp.arange(cfg.n_layers), params["layers"]))
    else:
        news = []
        for i in range(cfg.n_layers):
            x, new = layer(x, i, _index_layer(params["layers"], i))
            news.append(new)
        k_new, v_new = (jnp.stack(n) for n in zip(*news))
    x = _layernorm(x, params["ln_f"]["scale"], params["ln_f"]["bias"])
    logits = jnp.dot(x, params["unembed"],
                     preferred_element_type=jnp.float32)
    return logits, k_new, v_new


def forward_step(params, tokens, start_pos, k_view, v_view,
                 cfg: TransformerConfig):
    """Cache-aware forward over ``tokens`` given already-cached context.

    The ONE program both serving phases run: prefill calls it with the
    whole (padded) prompt, decode with a single token per sequence —
    same code path, so the two compose bitwise.

    ``tokens``: ``[b, s]`` int32.  ``start_pos``: ``[b]`` int32 — the
    global position of ``tokens[:, 0]``, which is also how many valid
    entries the KV view already holds for that sequence (ragged across
    the batch).  ``k_view``/``v_view``:
    ``[n_layers, b, capacity, heads, head_dim]`` with positions
    ``< start_pos`` populated.

    Returns ``(logits [b, s, vocab] float32, k_new, v_new)`` where
    ``k_new``/``v_new`` are ``[n_layers, b, s, heads, head_dim]`` — the
    new tokens' entries, for the caller to scatter back into its paged
    store (the view itself is a gather, not the storage).
    """
    cap = k_view.shape[2]
    if cap > cfg.max_seq_len:
        raise ValueError(f"KV capacity {cap} exceeds cfg.max_seq_len "
                         f"{cfg.max_seq_len}")

    def attend(i, q, k, v, pos):
        return view_attention(q, k, v, k_view[i], v_view[i], start_pos,
                              pos)

    return _step_layers(params, tokens, start_pos, cfg, attend)


def view_rungs(page_size: int, pages_per_slot: int) -> tuple:
    """The ladder of KV-view lengths, in TOKENS: powers of two from 8
    pages up, ``pages_per_slot`` always last; fewer than 16 pages per
    slot is the one full rung."""
    rungs = []
    n = 8
    while pages_per_slot >= 16 and n < pages_per_slot:
        rungs.append(n * page_size)
        n *= 2
    rungs.append(pages_per_slot * page_size)
    return tuple(rungs)


def view_rung(start_pos, rungs, width: int = 2):
    """Index of the smallest rung that holds ``max(start_pos) + width``
    tokens (the last one if none does: rows past a view drop).  Pure
    and the same for the traced ``start_pos`` of a serving program and
    for the host's numpy lengths; inactive slots (``< 0``) ask for
    nothing."""
    need = start_pos.max() + width
    return (need > np.asarray(rungs[:-1], np.int32)).sum()


def forward_step_paged(params, tokens, start_pos, k_pages, v_pages,
                       table, cfg: TransformerConfig):
    """:func:`forward_step` straight over the page store, attending the
    live tokens and not the capacity: each layer gathers only the first
    ``n`` pages of every sequence's ``table`` row into its view, ``n``
    the smallest rung of :func:`view_rungs` (a function of the shapes
    held here: the page size and the table's width) that covers the
    batch's longest sequence plus this block (:func:`view_rung`, from
    ``start_pos`` INSIDE the program, ``lax.switch`` around the layer's
    gather, put and attention only — the projections, the FFN and the
    unembedding exist once).  Masked positions contribute exact zeros
    (:func:`cache_attention`), so every rung is bitwise the full view.
    The layers are rolled into a ``lax.scan``: unrolled, the rungs'
    branches of every layer made the executable four times the dense
    one's and its load at start-up three seconds longer.

    ``k_pages``/``v_pages``: ``[n_layers, n_pages, page_size, heads *
    head_dim]``; ``table``: ``[b, pages_per_slot]`` int32.  Returns
    what :func:`forward_step` returns.
    """
    ps, pps = k_pages.shape[2], table.shape[1]
    if pps * ps > cfg.max_seq_len:
        raise ValueError(f"KV capacity {pps * ps} exceeds "
                         f"cfg.max_seq_len {cfg.max_seq_len}")
    b, s = tokens.shape
    rungs = view_rungs(ps, pps)
    rung = view_rung(start_pos, rungs, s)

    def over(n_tokens, layer, q, k, v, pos):
        rows = table[:, :n_tokens // ps]
        k_view = k_pages[layer, rows].reshape(b, n_tokens, *q.shape[2:])
        v_view = v_pages[layer, rows].reshape(b, n_tokens, *q.shape[2:])
        return view_attention(q, k, v, k_view, v_view, start_pos, pos)

    def attend(layer, q, k, v, pos):
        return jax.lax.switch(rung,
                              [partial(over, n, layer) for n in rungs],
                              q, k, v, pos)

    return _step_layers(params, tokens, start_pos, cfg, attend, roll=True)


def _put_view(view, new, pos):
    """Scatter one KV entry per sequence into a fixed-capacity view:
    ``view [n_layers, b, capacity, heads, head_dim]``, ``new
    [n_layers, b, heads, head_dim]`` written at per-sequence position
    ``pos [b]`` (rows past the capacity drop — the same mode="drop"
    discipline as :func:`forward_step`'s in-block put)."""
    def one(vb, nb, pb):
        return vb.at[pb].set(nb, mode="drop")
    return jax.vmap(jax.vmap(one, in_axes=(0, 0, 0)),
                    in_axes=(0, 0, None))(view, new, pos)


def speculative_propose(params, prev, pending, start_pos, k_view,
                        v_view, cfg: TransformerConfig, n_propose: int):
    """Greedy draft rollout for speculative decoding (hvd-spec): ONE
    program proposing ``n_propose`` tokens per sequence by unrolling
    that many cache-aware forward steps over the draft's KV view.

    ``prev``/``pending``: ``[b]`` int32 — the second-newest context
    token (at global position ``start_pos``) and the newest, not yet
    cached one (at ``start_pos + 1``).  The first step is a width-2
    block of BOTH real tokens: re-deriving ``prev``'s KV is either an
    exact overwrite (the values are a pure function of the token, its
    position and the accepted prefix — bitwise-identical on
    recomputation) or, after a fully accepted previous iteration, the
    catch-up write for the one draft token whose KV the draft never
    computed (it was the last PROPOSAL, not an input).  That single
    rule keeps the program shape identical for every slot in a mixed
    batch — no per-slot catch-up flag.

    Subsequent steps run ``[token, dummy]`` width-2 blocks (the same
    M>=2 gemm discipline as decode) feeding each argmax proposal back
    in, with the freshly derived KV scattered into the view between
    steps so step ``j+1`` attends to step ``j``'s entry.

    Returns ``(proposals [b, n_propose] int32, k_writes, v_writes)``
    where the writes are ``[n_layers, b, n_propose + 1, heads,
    head_dim]`` — the KV entries for global positions ``start_pos ..
    start_pos + n_propose``, for the caller to scatter into its paged
    store.
    """
    if n_propose < 1:
        raise ValueError(f"n_propose must be >= 1, got {n_propose}")
    kv, vv = k_view, v_view
    k_cols, v_cols = [], []
    blk = jnp.stack([prev, pending], axis=1)
    logits, kn, vn = forward_step(params, blk, start_pos, kv, vv, cfg)
    cur = jnp.argmax(logits[:, 1], axis=-1).astype(jnp.int32)
    proposals = [cur]
    k_cols += [kn[:, :, 0], kn[:, :, 1]]
    v_cols += [vn[:, :, 0], vn[:, :, 1]]
    # prev's entry must land in the view too: after a fully-accepted
    # iteration it is the catch-up fill, and steps >= 2 attend to it.
    kv = _put_view(kv, k_cols[0], start_pos)
    vv = _put_view(vv, v_cols[0], start_pos)
    for j in range(1, n_propose):
        kv = _put_view(kv, k_cols[-1], start_pos + j)
        vv = _put_view(vv, v_cols[-1], start_pos + j)
        blk = jnp.stack([cur, jnp.zeros_like(cur)], axis=1)
        logits, kn, vn = forward_step(params, blk, start_pos + 1 + j,
                                      kv, vv, cfg)
        cur = jnp.argmax(logits[:, 0], axis=-1).astype(jnp.int32)
        proposals.append(cur)
        k_cols.append(kn[:, :, 0])
        v_cols.append(vn[:, :, 0])
    return (jnp.stack(proposals, axis=1),
            jnp.stack(k_cols, axis=2), jnp.stack(v_cols, axis=2))


def serving_forward(params, tokens, cfg: TransformerConfig,
                    capacity: Optional[int] = None):
    """Non-incremental reference for the serving path: the full sequence
    through :func:`forward_step` from an empty KV view.  Returns
    ``logits [b, s, vocab]`` (float32).  The serving bitwise contract —
    asserted by tests/test_serving.py and the serving bench — is that a
    prefill of ``tokens[:, :p]`` followed by ``s - p`` single-token
    decode steps reproduces these logits exactly."""
    b, s = tokens.shape
    cap = capacity if capacity is not None else s
    hd = cfg.d_model // cfg.n_heads
    zeros = jnp.zeros((cfg.n_layers, b, cap, cfg.n_heads, hd),
                      cfg.dtype)
    logits, _, _ = forward_step(
        params, tokens, jnp.zeros((b,), jnp.int32), zeros, zeros, cfg)
    return logits
