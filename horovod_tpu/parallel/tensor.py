"""Tensor (model) parallelism: Megatron-style sharded matmuls.

Beyond-parity extension (the reference shards nothing — SURVEY.md §2.3
"Tensor parallelism: NO").  Weight matrices shard over the
:data:`..core.topology.MODEL_AXIS` mesh axis; activations stay replicated
within a model group.  The classic pairing keeps communication to one
``psum`` per block:

* :func:`column_parallel` — weight split on the *output* feature axis;
  each device computes a disjoint slice of the outputs.  No communication
  (outputs stay sharded), so it starts a block.
* :func:`row_parallel` — weight split on the *input* feature axis; each
  device contracts its input slice and the partial products are summed
  with ``lax.psum``.  It ends a block, consuming column-parallel outputs
  directly.

``tp_mlp`` composes them into the standard 2-layer block (one collective
per MLP); attention uses column-parallel QKV (heads sharded) + row-
parallel output projection the same way — see models/transformer.py.

**Fused closers/openers** (hvd-fuse, ops/fused.py): ``row_parallel``'s
GEMM+psum closer is chunked along the token axis so chunk *i*'s
partial-product reduction flies while chunk *i+1* multiplies, inside one
XLA program — bitwise-identical to the unfused program (rows are
reduction-free; psum is elementwise).  The sequence-parallel-style pair
:func:`row_parallel_scatter` (matmul + reduce_scatter: each device keeps
its feature shard of the sum) and :func:`gather_column_parallel`
(all_gather + matmul: re-gather the feature shards into the next
block's GEMM) hand activations off feature-sharded between blocks, and
both chunk the same way.  ``fuse``/``fuse_chunks`` default to the
``HVD_TPU_FUSE`` / ``HVD_TPU_FUSE_CHUNKS`` knobs.

All functions are for use inside ``shard_map`` over a mesh that has the
model axis.  Helpers to place full weights shard-wise live here too.
"""

from __future__ import annotations

from typing import Optional

import jax
import jax.numpy as jnp

from ..core.topology import MODEL_AXIS
from ..ops import fused as _fused


def column_parallel(x, w, b=None, *, axis_name: str = MODEL_AXIS,
                    gather_output: bool = False):
    """``y_local = x @ w_local (+ b_local)`` with ``w`` sharded on its
    last (output) axis.  Outputs are feature-sharded unless
    ``gather_output``.
    """
    y = jnp.dot(x, w, preferred_element_type=jnp.float32).astype(x.dtype)
    if b is not None:
        y = y + b
    if gather_output:
        y = jax.lax.all_gather(y, axis_name, axis=y.ndim - 1, tiled=True)
    return y


def row_parallel(x, w, b=None, *, axis_name: str = MODEL_AXIS,
                 input_is_parallel: bool = True,
                 fuse: Optional[bool] = None,
                 fuse_chunks: Optional[int] = None):
    """``y = psum_axis(x_local @ w_local) (+ b)`` with ``w`` sharded on its
    first (input) axis.

    ``input_is_parallel=True`` (the default) means ``x`` is already
    feature-sharded — i.e. it came from :func:`column_parallel`; otherwise
    the local input slice is taken here.

    When fusion is on (the default; ``HVD_TPU_FUSE``), the GEMM is
    chunked along the token axis and each chunk's psum is emitted inside
    the same program, so chunk *i*'s reduction overlaps chunk *i+1*'s
    multiply.  Bitwise-identical to the unfused program: the per-chunk
    leg repeats the exact unfused dot→cast→psum ordering and psum is
    elementwise in the chunked rows.
    """
    if not input_is_parallel:
        n = jax.lax.axis_size(axis_name)
        idx = jax.lax.axis_index(axis_name)
        shard = x.shape[-1] // n
        x = jax.lax.dynamic_slice_in_dim(x, idx * shard, shard, axis=-1)

    def closer(xc):
        yc = jnp.dot(xc, w,
                     preferred_element_type=jnp.float32).astype(x.dtype)
        return jax.lax.psum(yc, axis_name)

    y = _fused.chunked_map(closer, x, axis=0, chunks=fuse_chunks,
                           fuse=fuse)
    if b is not None:
        y = y + b
    return y


def row_parallel_scatter(x, w, b_local=None, *,
                         axis_name: str = MODEL_AXIS,
                         fuse: Optional[bool] = None,
                         fuse_chunks: Optional[int] = None):
    """Matmul + reduce_scatter closer: ``psum_scatter(x_local @ w_local)``
    — each device keeps only its shard of the summed output's LAST
    (feature) axis, 1/n the bytes of :func:`row_parallel`'s full psum.

    The feature-sharded output hands off directly to
    :func:`gather_column_parallel` in the next block (the fused
    sequence-parallel-style pair).  ``b_local`` is the caller's shard of
    the bias (e.g. via :func:`local_shard`).  Chunked along the token
    axis like :func:`row_parallel`; psum_scatter is elementwise in rows,
    so the fused program is bitwise-identical to the unfused one.
    """
    def closer(xc):
        yc = jnp.dot(xc, w,
                     preferred_element_type=jnp.float32).astype(x.dtype)
        return jax.lax.psum_scatter(yc, axis_name,
                                    scatter_dimension=yc.ndim - 1,
                                    tiled=True)

    y = _fused.chunked_map(closer, x, axis=0, chunks=fuse_chunks,
                           fuse=fuse)
    if b_local is not None:
        y = y + b_local
    return y


def gather_column_parallel(x, w, b=None, *, axis_name: str = MODEL_AXIS,
                           fuse: Optional[bool] = None,
                           fuse_chunks: Optional[int] = None):
    """All_gather + matmul opener: ``all_gather(x) @ w_local`` where ``x``
    arrives feature-sharded (from :func:`row_parallel_scatter`) and ``w``
    is sharded on its last (output) axis like :func:`column_parallel`.

    Chunked along the token axis: chunk *i+1*'s gather flies while chunk
    *i* multiplies.  Gathering the contraction axis per row-chunk never
    reorders any element's dot, so the fused program is
    bitwise-identical to the unfused one.
    """
    def opener(xc):
        xg = jax.lax.all_gather(xc, axis_name, axis=xc.ndim - 1,
                                tiled=True)
        return jnp.dot(xg, w,
                       preferred_element_type=jnp.float32).astype(x.dtype)

    y = _fused.chunked_map(opener, x, axis=0, chunks=fuse_chunks,
                           fuse=fuse)
    if b is not None:
        y = y + b
    return y


def tp_mlp(x, w_in, b_in, w_out, b_out, *, axis_name: str = MODEL_AXIS,
           activation=jax.nn.gelu, fuse: Optional[bool] = None,
           fuse_chunks: Optional[int] = None):
    """The Megatron MLP block: column-parallel up-projection, elementwise
    activation on the sharded features, row-parallel down-projection.
    Exactly one ``psum`` of communication (chunk-fused with the down-
    projection GEMM unless ``HVD_TPU_FUSE=off``)."""
    h = column_parallel(x, w_in, b_in, axis_name=axis_name)
    h = activation(h)
    return row_parallel(h, w_out, b_out, axis_name=axis_name, fuse=fuse,
                        fuse_chunks=fuse_chunks)


def local_shard(full, dim: int, *, axis_name: str = MODEL_AXIS):
    """``full``'s shard for the calling device along ``dim`` (inside
    shard_map)."""
    n = jax.lax.axis_size(axis_name)
    idx = jax.lax.axis_index(axis_name)
    size = full.shape[dim] // n
    return jax.lax.dynamic_slice_in_dim(full, idx * size, size, axis=dim)
