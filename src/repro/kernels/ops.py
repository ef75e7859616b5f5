"""jit'd public wrappers for the Pallas kernels.

``interpret`` defaults to True off-TPU (the kernels execute through the
Pallas interpreter for correctness) and False on TPU (real Mosaic lowering).
"""
from __future__ import annotations

from typing import Optional

from .chunked_copy import chunked_copy as _chunked_copy
from .combine_update import fused_combine as _fused_combine
from .flash_attention import flash_attention as _flash
from .interpret import on_tpu, resolve_interpret
from .param_update import mix as _mix, scaled_add as _scaled_add
from .quantize import (
    BLOCK_ELEMS,
    QUANT_DTYPES,
    dequantize_blocks as _dequantize_blocks,
    quantize_blocks as _quantize_blocks,
)

__all__ = [
    "on_tpu",
    "resolve_interpret",
    "chunked_copy",
    "fused_combine",
    "mix",
    "scaled_add",
    "flash_attention",
    "quantize_blocks",
    "dequantize_blocks",
]


def chunked_copy(x, *, chunk_elems: int = 64 * 1024, interpret: Optional[bool] = None):
    return _chunked_copy(x, chunk_elems=chunk_elems, interpret=resolve_interpret(interpret))


def fused_combine(cur, recv, row_mode, *, interpret: Optional[bool] = None):
    return _fused_combine(cur, recv, row_mode, interpret=resolve_interpret(interpret))


def mix(w, u, a, *, interpret: Optional[bool] = None):
    return _mix(w, u, a, interpret=resolve_interpret(interpret))


def scaled_add(w, u, a, *, interpret: Optional[bool] = None):
    return _scaled_add(w, u, a, interpret=resolve_interpret(interpret))


def quantize_blocks(x, fmt: str, *, interpret: Optional[bool] = None):
    """Quantize (B, C) f32 ``x`` to ``(values, scales)`` under wire format
    ``fmt`` ('int8' | 'fp8'). Ragged column tails are zero-padded to the
    256-element scale block (the padding IS shipped on the wire, and
    :func:`repro.comm.compress.wire_chunk_bytes` counts it); a zero-sized
    input short-circuits to empty outputs without launching a kernel.
    Returns values of shape (B, Cp) and scales (B, Cp // 256) where Cp is C
    rounded up to a multiple of 256.
    """
    import jax.numpy as jnp

    if fmt not in QUANT_DTYPES:
        raise ValueError(f"unknown quantize format {fmt!r}; expected one of "
                         f"{sorted(QUANT_DTYPES)}")
    B, C = x.shape
    blocks = -(-max(C, 1) // BLOCK_ELEMS)
    Cp = blocks * BLOCK_ELEMS
    if B == 0:
        dtype, _ = QUANT_DTYPES[fmt]
        return (jnp.zeros((0, Cp), dtype), jnp.zeros((0, blocks), jnp.float32))
    x = x.astype(jnp.float32)
    if Cp != C:
        x = jnp.pad(x, ((0, 0), (0, Cp - C)))
    return _quantize_blocks(x, fmt, interpret=resolve_interpret(interpret))


def dequantize_blocks(values, scales, *, out_cols: Optional[int] = None,
                      interpret: Optional[bool] = None):
    """Inverse of :func:`quantize_blocks`; ``out_cols`` slices off the
    block padding to recover the original column count."""
    if values.shape[0] == 0:
        import jax.numpy as jnp

        cols = values.shape[1] if out_cols is None else out_cols
        return jnp.zeros((0, cols), jnp.float32)
    out = _dequantize_blocks(values, scales, interpret=resolve_interpret(interpret))
    if out_cols is not None and out_cols != out.shape[1]:
        out = out[:, :out_cols]
    return out


def flash_attention(
    q,
    k,
    v,
    *,
    causal: bool = True,
    window: Optional[int] = None,
    prefix: int = 0,
    bq: int = 128,
    bk: int = 128,
    interpret: Optional[bool] = None,
):
    interpret = resolve_interpret(interpret)
    return _flash(
        q, k, v, causal=causal, window=window, prefix=prefix, bq=bq, bk=bk, interpret=interpret
    )
