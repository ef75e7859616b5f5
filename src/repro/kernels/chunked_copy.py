"""Pipelined chunked copy kernel (Pallas, TPU target).

The paper's GPU implementation replaces ``cudaMemcpy`` with CUDA-kernel
copies so chunk k+1's HBM read overlaps chunk k's write (the pipelined CUDA
IPC path, Sec. IV-C). The TPU analogue: a grid-over-chunks ``pallas_call``
whose BlockSpec tiling makes the Mosaic pipeliner double-buffer
HBM -> VMEM -> HBM chunk traffic. This is the staging primitive the
host-staged broadcast path uses to move bucket chunks.

The ragged tail is handled by the grid's masked final block (Pallas pads
out-of-bounds reads and masks out-of-bounds writes), NOT by materializing a
zero pad with ``jnp.concatenate`` — that pad was a full extra HBM copy of
the buffer before the pipeline even started.

``interpret`` defaults to the backend: the Pallas interpreter off-TPU
(validated by the shape/dtype sweeps in tests), the real Mosaic DMA
pipeline on TPU.
"""
from __future__ import annotations

import functools

import jax
from jax.experimental import pallas as pl

from .interpret import resolve_interpret

__all__ = ["chunked_copy"]

# 8 * 128 lanes * 4 sublanes: a full VREG-aligned tile row count
_LANE = 128


def _copy_kernel(src_ref, dst_ref):
    dst_ref[...] = src_ref[...]


@functools.partial(jax.jit, static_argnames=("chunk_elems", "interpret"))
def chunked_copy(x: jax.Array, *, chunk_elems: int = 64 * 1024, interpret: bool | None = None) -> jax.Array:
    """Copy a 1-D buffer through VMEM in ``chunk_elems``-sized chunks.

    The grid walks chunks and the pipeliner overlaps the k-th write with the
    (k+1)-th read; a non-divisible tail rides in the final block under the
    grid's implicit bounds mask (no pad copy is ever materialized).
    """
    assert x.ndim == 1, "chunked_copy operates on flat comm buffers"
    interpret = resolve_interpret(interpret)
    n = x.size
    chunk_elems = max(_LANE, min(chunk_elems, max(n, _LANE)))
    num_chunks = pl.cdiv(n, chunk_elems)

    return pl.pallas_call(
        _copy_kernel,
        grid=(num_chunks,),
        in_specs=[pl.BlockSpec((chunk_elems,), lambda i: (i,))],
        out_specs=pl.BlockSpec((chunk_elems,), lambda i: (i,)),
        out_shape=jax.ShapeDtypeStruct((n,), x.dtype),
        interpret=interpret,
    )(x)
