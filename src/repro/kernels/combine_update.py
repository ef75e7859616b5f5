"""Fused combine-update kernel (Pallas) for the compiled schedule executor.

One replay round of a lane class must merge the received block into the
buffer window it lands on: ``out = cur + recv`` on the rows the schedule
actually addressed this round when the round combines, ``out = recv`` when
it overwrites, ``out = cur`` everywhere else. The jnp spelling of that is a
``dynamic_slice`` -> ``jnp.where`` mask -> ``dynamic_update_slice`` triple
that materializes the zero-filled mask operand and a second merged block in
HBM every round. This kernel does the merge in ONE VMEM pass — read the
current rows and the received rows, add-or-select-or-keep under the per-row
mode, write back — with the current block aliased to the output
(``input_output_aliases``) so no extra block is materialized. Same
grid-over-tiles contract as :func:`repro.kernels.chunked_copy`: the Mosaic
pipeliner double-buffers tile (k+1)'s HBM read under tile k's write.

The per-row mode (0 = keep, 1 = overwrite, 2 = accumulate) is data, not
kernel structure, so one kernel serves combining AND overwriting rounds —
which is what lets a lane class carry a per-round combine flag (e.g.
ring_allreduce's reduce-scatter and allgather phases on one class).

Validated with ``interpret=True`` off-TPU (the executor parity sweeps);
on TPU the same code emits the real DMA pipeline.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl

from .interpret import resolve_interpret

__all__ = ["fused_combine", "fused_combine_update"]

# Tiles follow the TPU block rule: each of the last two block dimensions is
# either the whole array dimension or a multiple of the (8, 128) tile (row
# blocks are kept to multiples of 32, which also covers bf16 and int8). A
# tile holds about _TILE_BYTES, so the four double-buffered operands stay
# far below the default scoped VMEM limit at any row count and dtype.
_ROW_BLOCK = 256
_TILE_BYTES = 512 << 10
_LANE = 128

# row modes
KEEP, OVERWRITE, ACCUMULATE = 0, 1, 2


def _merge_kernel(cur_ref, recv_ref, m_ref, out_ref):
    m = m_ref[...]                                    # (rows, 1)
    cur = cur_ref[...]
    rec = recv_ref[...]
    # where(mode, ..., cur) — NOT cur + where(mode, rec, 0): kept rows must
    # round-trip bit-identically (a -0.0 would flip under the add-zero
    # form), which is what makes compiled == unrolled exact
    out_ref[...] = jnp.where(m == ACCUMULATE, cur + rec,
                             jnp.where(m == OVERWRITE, rec, cur))


def fused_combine(cur: jax.Array, recv: jax.Array, row_mode: jax.Array, *,
                  interpret: bool | None = None) -> jax.Array:
    """Merge ``recv`` into ``cur`` row-wise under ``row_mode``.

    ``cur``/``recv``: (block, chunk_elems); ``row_mode``: (block, 1) int32
    of KEEP (0) / OVERWRITE (1) / ACCUMULATE (2). Must be called inside a
    trace (jit/shard_map) like the executors that own it.
    """
    interpret = resolve_interpret(interpret)
    B, C = cur.shape
    rowb = B if B <= _ROW_BLOCK else _ROW_BLOCK
    colb = max(_LANE, _TILE_BYTES // (rowb * cur.dtype.itemsize) // _LANE * _LANE)
    colb = C if C <= colb else colb
    tile = pl.BlockSpec((rowb, colb), lambda i, j: (i, j))
    return pl.pallas_call(
        _merge_kernel,
        grid=(pl.cdiv(B, rowb), pl.cdiv(C, colb)),
        in_specs=[tile, tile, pl.BlockSpec((rowb, 1), lambda i, j: (i, 0))],
        out_specs=tile,
        out_shape=jax.ShapeDtypeStruct((B, C), cur.dtype),
        input_output_aliases={0: 0},
        interpret=interpret,
    )(cur, recv, row_mode)


def fused_combine_update(buf: jax.Array, recv: jax.Array, start, lo, hi, *,
                         combine, interpret: bool | None = None) -> jax.Array:
    """Apply one lane-class round to ``buf`` (num_chunks, chunk_elems):
    rows ``[start + lo, start + hi)`` merge the matching rows of ``recv``
    (add when ``combine`` is truthy, else overwrite); every other row of
    the ``[start, start + block)`` window writes back unchanged. ``start``,
    ``lo``, ``hi``, and ``combine`` (bool or 0/1 int) may be traced scalars
    from the lowered round tables.
    """
    B, _C = recv.shape
    cur = lax.dynamic_slice(buf, (start, 0), recv.shape)
    rows = jnp.arange(B, dtype=jnp.int32)
    valid = ((rows >= lo) & (rows < hi)).astype(jnp.int32)
    mode = (valid * (1 + jnp.asarray(combine, jnp.int32))).reshape(B, 1)
    merged = fused_combine(cur, recv, mode, interpret=interpret)
    return lax.dynamic_update_slice(buf, merged, (start, 0))
