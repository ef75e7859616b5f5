"""Per-block quantize/dequantize kernels (Pallas) for compressed wire
formats.

A compressed collective hop ships each chunk as a low-precision payload
(int8 or float8_e4m3fn) plus one f32 scale per 256-element block instead of
the full-precision values: 4x fewer payload bytes at a ~1.6% scale
overhead. The quantize kernel computes a symmetric abs-max scale per block
(``scale = max(|x|) / qmax``), divides, clips to the representable range,
and casts; the dequantize kernel multiplies back. Both walk the (B, C)
buffer in its own layout, one (rows, 128 scale blocks) tile per grid step,
and split each tile into its 256-element blocks inside the kernel, so no
relayout copy of the buffer is ever made; the Mosaic pipeliner
double-buffers tile (k+1)'s HBM read under tile k's write.

The clip BEFORE the cast is load-bearing for fp8: ``float8_e4m3fn`` has no
inf, so an out-of-range cast produces NaN, not saturation. With the abs-max
scale the quotient is already in range; the clip pins the boundary case
(``|x| == amax`` maps exactly to ``qmax``) against rounding above qmax.

Zero blocks get ``scale = qmax_eps`` (a tiny positive floor) so dequantize
never divides-by-zero territory — a zero block round-trips to exact zeros
because the quantized payload is zero regardless of the scale.

Validated with ``interpret=True`` off-TPU (roundtrip property tests); on
TPU the same code emits the real tiled pipeline. Callers go through
:func:`repro.kernels.ops.quantize_blocks` / ``dequantize_blocks``, which
pad ragged tails to the block size and resolve interpret mode.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

__all__ = [
    "BLOCK_ELEMS",
    "QUANT_DTYPES",
    "quantize_blocks",
    "dequantize_blocks",
]

# elements per scale block; also the column tile (f32 min-tile friendly,
# and small enough that the int8/fp8 payload tile stays VREG-aligned)
BLOCK_ELEMS = 256

# wire dtype -> clipping range qmax (symmetric): int8 uses the symmetric
# [-127, 127] grid; float8_e4m3fn saturates at +-448 (no inf -> NaN past
# it, hence the pre-cast clip)
QUANT_DTYPES = {
    "int8": (jnp.int8, 127.0),
    "fp8": (jnp.float8_e4m3fn, 448.0),
}

# Tiles follow the TPU block rule (each of the last two block dimensions is
# the whole array dimension or a multiple of the (8, 128) tile). A column
# tile of 128 scale blocks puts a full 128-lane row in the scale tile; an
# (8, 32768) f32 tile is 1 MiB, so the double-buffered operands stay far
# below the default scoped VMEM limit.
_ROW_BLOCK = 8
_COL_BLOCK = 128 * BLOCK_ELEMS

# scale floor for all-zero blocks: keeps scale strictly positive without
# perturbing the roundtrip (payload is 0 -> dequant 0 * floor == 0)
_SCALE_FLOOR = 1e-30


def _blocked(x):
    """(rows, cols) -> (rows, cols // BLOCK_ELEMS, BLOCK_ELEMS) scale blocks."""
    rows, cols = x.shape
    return x.reshape(rows, cols // BLOCK_ELEMS, BLOCK_ELEMS)


def _quantize_kernel(x_ref, v_ref, s_ref, *, qmax, is_int):
    x = _blocked(x_ref[...].astype(jnp.float32))
    amax = jnp.max(jnp.abs(x), axis=-1)
    scale = jnp.maximum(amax, _SCALE_FLOOR) / qmax
    q = jnp.clip(x / scale[:, :, None], -qmax, qmax)
    if is_int:
        q = jnp.round(q)
    v_ref[...] = q.reshape(v_ref.shape).astype(v_ref.dtype)
    s_ref[...] = scale


def _dequantize_kernel(v_ref, s_ref, x_ref):
    v = _blocked(v_ref[...].astype(jnp.float32))
    x_ref[...] = (v * s_ref[...][:, :, None]).reshape(x_ref.shape)


def _tiles(B: int, C: int):
    """Grid plus the payload and scale block specs over a (B, C) buffer."""
    rowb = B if B <= _ROW_BLOCK else _ROW_BLOCK
    colb = C if C <= _COL_BLOCK else _COL_BLOCK
    grid = (pl.cdiv(B, rowb), pl.cdiv(C, colb))
    payload = pl.BlockSpec((rowb, colb), lambda i, j: (i, j))
    scale = pl.BlockSpec((rowb, colb // BLOCK_ELEMS), lambda i, j: (i, j))
    return grid, payload, scale


def quantize_blocks(x: jax.Array, fmt: str, *, interpret: bool) -> tuple[jax.Array, jax.Array]:
    """Quantize ``x`` (B, C) f32 with C a multiple of :data:`BLOCK_ELEMS`
    into ``(values (B, C) wire-dtype, scales (B, C // BLOCK_ELEMS) f32)``.
    Callers own padding; see :func:`repro.kernels.ops.quantize_blocks`.
    """
    dtype, qmax = QUANT_DTYPES[fmt]
    B, C = x.shape
    grid, payload, scale = _tiles(B, C)

    def kernel(x_ref, v_ref, s_ref):
        _quantize_kernel(x_ref, v_ref, s_ref, qmax=qmax, is_int=fmt == "int8")

    return pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=[payload],
        out_specs=[payload, scale],
        out_shape=[
            jax.ShapeDtypeStruct((B, C), dtype),
            jax.ShapeDtypeStruct((B, C // BLOCK_ELEMS), jnp.float32),
        ],
        interpret=interpret,
    )(x)


def dequantize_blocks(values: jax.Array, scales: jax.Array, *,
                      interpret: bool) -> jax.Array:
    """Inverse of :func:`quantize_blocks`: (B, C) wire-dtype + per-block f32
    scales back to (B, C) f32."""
    B, C = values.shape
    assert scales.shape == (B, C // BLOCK_ELEMS), (values.shape, scales.shape)
    grid, payload, scale = _tiles(B, C)
    return pl.pallas_call(
        _dequantize_kernel,
        grid=grid,
        in_specs=[payload, scale],
        out_specs=payload,
        out_shape=jax.ShapeDtypeStruct((B, C), jnp.float32),
        interpret=interpret,
    )(values, scales)
