"""jit'd public wrappers for the Pallas kernels.

``interpret`` defaults to True off-TPU (the kernels execute through the
Pallas interpreter for correctness) and False on TPU (real Mosaic lowering).
"""
from __future__ import annotations

import functools
from typing import Optional

from .chunked_copy import chunked_copy as _chunked_copy
from .combine_update import fused_combine as _fused_combine
from .flash_attention import flash_attention as _flash
from .interpret import on_tpu, resolve_interpret
from .mamba_scan import mamba_scan as _mamba_scan
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
    "mamba_scan",
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


def mamba_scan(dt, x, Bm, Cm, A, h0, *, chunk: int, interpret: Optional[bool] = None):
    """Mamba's selective scan: dt, x (Bt, T, di); Bm, Cm (Bt, T, N); A (di, N);
    h0 (Bt, di, N), all f32. Returns (y (Bt, T, di), h_last (Bt, di, N)) with
    y_t = sum_n h_t C_t, the skip term left to the caller. Time blocks of
    about ``chunk`` steps; any T and di.

    XLA cannot partition a Mosaic kernel, so where the ambient mesh
    (``jax.sharding.get_abstract_mesh``) has axes of more than one device
    that are not already manual, the scan splits itself with
    ``jax.shard_map``: rows of the batch over those axes but the model axis,
    channels over the model axis, where they divide. Rows and channels are
    independent; the shard_map's transpose sums the cotangents of B, C and A
    over the axes that split their partners. Code that jits the scan on a
    multi-device mesh traces it under that mesh (``repro.dist.on_mesh``).
    """
    import math

    import jax
    from jax.sharding import PartitionSpec as P

    from ..dist.topology import TP_AXIS

    scan = functools.partial(_mamba_scan, chunk=chunk, interpret=resolve_interpret(interpret))
    mesh = jax.sharding.get_abstract_mesh()
    split = [a for a in mesh.axis_names if mesh.shape[a] > 1 and a not in mesh.manual_axes]
    if not split:
        return scan(dt, x, Bm, Cm, A, h0)
    Bt, _, di = dt.shape
    rows = tuple(a for a in split if a != TP_AXIS)
    rows = rows if rows and Bt % math.prod(mesh.shape[a] for a in rows) == 0 else None
    chans = TP_AXIS if TP_AXIS in split and di % mesh.shape[TP_AXIS] == 0 else None
    seq, bc, hs = P(rows, None, chans), P(rows, None, None), P(rows, chans, None)
    return jax.shard_map(scan, in_specs=(seq, seq, bc, bc, P(chans, None), hs),
                         out_specs=(seq, hs), axis_names=set(split),
                         check_vma=False)(dt, x, Bm, Cm, A, h0)
