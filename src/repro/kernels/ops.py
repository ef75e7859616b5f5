"""jit'd public wrappers for the Pallas kernels.

``interpret`` defaults to True off-TPU (the kernels execute through the
Pallas interpreter for correctness) and False on TPU (real Mosaic lowering).
"""
from __future__ import annotations

import functools
from typing import Optional

from .chunked_copy import chunked_copy as _chunked_copy
from .combine_update import fused_combine as _fused_combine
from . import interpret
from .flash_attention import flash_attention as _flash
from .interpret import on_tpu, resolve_interpret
from .mamba_scan import mamba_scan as _mamba_scan
from .mlstm_scan import mlstm_scan as _mlstm_scan
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
    "mlstm_scan",
    "grouped_matmul",
    "causal_attention",
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
    import jax
    from jax.sharding import PartitionSpec as P

    from ..dist.topology import TP_AXIS

    scan = functools.partial(_mamba_scan, chunk=chunk, interpret=resolve_interpret(interpret))
    mesh, split = _split_axes()
    if not split:
        return scan(dt, x, Bm, Cm, A, h0)
    Bt, _, di = dt.shape
    rows = _dividing(mesh, [a for a in split if a != TP_AXIS], Bt)
    chans = TP_AXIS if TP_AXIS in split and di % mesh.shape[TP_AXIS] == 0 else None
    seq, bc, hs = P(rows, None, chans), P(rows, None, None), P(rows, chans, None)
    return jax.shard_map(scan, in_specs=(seq, seq, bc, bc, P(chans, None), hs),
                         out_specs=(seq, hs), axis_names=set(split),
                         check_vma=False)(dt, x, Bm, Cm, A, h0)


def mlstm_scan(q, k, v, lf, li, C0, n0, *, chunk: int, interpret: Optional[bool] = None):
    """The mLSTM's chunkwise recurrence: q, k, v (B, T, H, hd); lf, li
    (B, T, H) log forget and input gates; C0 (B, H, hd, hd), n0 (B, H, hd)
    the state entering. Returns (h (B, T, H, hd) in q's dtype, (C, n) the
    final state, f32). Chunks of about ``chunk`` steps; any T and hd.

    Where the ambient mesh has axes of more than one device that are not
    already manual, the scan splits itself with ``jax.shard_map``, as
    :func:`mamba_scan` does: rows of the batch over those axes but the
    model axis, heads over the model axis, where they divide. Rows and
    heads are independent."""
    import jax
    from jax.sharding import PartitionSpec as P

    from ..dist.topology import TP_AXIS

    scan = functools.partial(_mlstm_scan, chunk=chunk, interpret=resolve_interpret(interpret))
    mesh, split = _split_axes()
    if not split:
        return scan(q, k, v, lf, li, C0, n0)
    rows = _dividing(mesh, [a for a in split if a != TP_AXIS], q.shape[0])
    heads = _dividing(mesh, [a for a in split if a == TP_AXIS], q.shape[2])
    seq, gate = P(rows, None, heads, None), P(rows, None, heads)
    mat, vec = P(rows, heads, None, None), P(rows, heads, None)
    return jax.shard_map(scan, in_specs=(seq, seq, seq, gate, gate, mat, vec),
                         out_specs=(seq, (mat, vec)), axis_names=set(split),
                         check_vma=False)(q, k, v, lf, li, C0, n0)


def _split_axes():
    """The ambient mesh (``jax.sharding.get_abstract_mesh``) and its axes of
    more than one device that are not already manual: the axes XLA would
    have to partition a kernel over, which it cannot do to a Mosaic kernel."""
    import jax

    mesh = jax.sharding.get_abstract_mesh()
    return mesh, [a for a in mesh.axis_names if mesh.shape[a] > 1 and a not in mesh.manual_axes]


def _dividing(mesh, axes, n):
    """``axes`` as one PartitionSpec entry where their devices divide ``n``,
    else None (replicated)."""
    import math

    axes = tuple(axes)
    return axes if axes and n % math.prod(mesh.shape[a] for a in axes) == 0 else None


# (rows, contraction, columns) tile of the grouped matmul on a TPU v5e: the
# fastest of four measured at Moonlight's expert shapes (PERF.md section 6)
GMM_TILING = (256, 512, 1408)


def _gmm(lhs, rhs, group_sizes):
    """megablox ``gmm`` over lhs's rows; the rows past the groups form one
    more group that it leaves out and zeroes."""
    import jax.numpy as jnp
    from jax.experimental.pallas.ops.tpu.megablox import ops as megablox

    m = lhs.shape[0]
    pad = -m % GMM_TILING[0]
    rows = jnp.pad(lhs, ((0, pad), (0, 0))) if pad else lhs
    sizes = jnp.concatenate([group_sizes, (m + pad - jnp.sum(group_sizes))[None]]).astype(jnp.int32)
    out = megablox.gmm(rows, rhs, sizes, lhs.dtype, GMM_TILING, jnp.zeros((), jnp.int32))
    return out[:m] if pad else out


def grouped_matmul(lhs, rhs, group_sizes):
    """lhs (m, k) rows sorted into groups, rhs (g, k, n), group_sizes (g,)
    int32 with a sum of at most m: row r of group i gives lhs[r] @ rhs[i];
    rows past the last group give zeros. Output in lhs's dtype.

    On a TPU, the megablox grouped matmul that ships with JAX (``gmm``, and
    ``tgmm`` in its backward), which visits only the row tiles of the g
    groups. Where the ambient mesh splits, it runs under ``jax.shard_map``:
    each device takes an equal slice of the rows (where the devices divide
    m, else all of them) with the groups cut to its slice, and the
    shard_map's transpose sums rhs's cotangent over the slices. Elsewhere
    ``jax.lax.ragged_dot``, the same result."""
    import jax
    import jax.numpy as jnp
    from jax.sharding import PartitionSpec as P

    if not interpret.on_tpu():
        return jax.lax.ragged_dot(lhs, rhs, group_sizes, preferred_element_type=lhs.dtype)
    mesh, split = _split_axes()
    if not split:
        return _gmm(lhs, rhs, group_sizes)
    rows = _dividing(mesh, split, lhs.shape[0])

    def local(lhs, rhs, sizes):
        if rows is not None:
            m, start = lhs.shape[0], jax.lax.axis_index(rows) * lhs.shape[0]
            ends = jnp.cumsum(sizes)
            sizes = jnp.clip(ends - start, 0, m) - jnp.clip(ends - sizes - start, 0, m)
        return _gmm(lhs, rhs, sizes.astype(jnp.int32))

    return jax.shard_map(local, in_specs=(P(rows, None), P(), P()), out_specs=P(rows, None),
                         axis_names=set(split), check_vma=False)(lhs, rhs, group_sizes)


# query and key block of the splash kernel: the fastest of those measured on
# a TPU v5e at Moonlight's widths (PERF.md section 6)
SPLASH_BLOCK = 1024


def _splash(q, k, v):
    import jax
    from jax.experimental.pallas.ops.tpu.splash_attention import splash_attention_kernel as sk
    from jax.experimental.pallas.ops.tpu.splash_attention import splash_attention_mask as sm

    _, T, H, _ = q.shape
    b = min(SPLASH_BLOCK, T)
    sizes = sk.BlockSizes(block_q=b, block_kv=b, block_kv_compute=b, block_q_dkv=b, block_kv_dkv=b,
                          block_kv_dkv_compute=b, block_q_dq=b, block_kv_dq=b)
    mask = sm.MultiHeadMask([sm.CausalMask((T, T)) for _ in range(H)])
    kernel = jax.vmap(sk.make_splash_mha(mask, block_sizes=sizes, head_shards=1, q_seq_shards=1))
    to_heads = lambda a: a.swapaxes(1, 2)  # noqa: E731
    out = kernel(to_heads(q * q.shape[-1] ** -0.5), to_heads(k), to_heads(v))
    return to_heads(out).astype(q.dtype)


def causal_attention(q, k, v):
    """Causal softmax attention through the splash attention kernel that
    ships with JAX (forward and backward kernels, fully masked blocks
    skipped), for a TPU: q, k (B, T, H, hd), v (B, T, H, hd_v), scale
    hd^-0.5. Returns (B, T, H, hd_v) in q's dtype. The kernel is built anew
    for each trace: it keeps its block tables as arrays, which a kernel
    built in one trace would carry into the next. Where the ambient mesh
    splits, it runs under ``jax.shard_map``: rows of the batch over the
    axes but the model axis, heads over the model axis, where they divide."""
    import jax
    from jax.sharding import PartitionSpec as P

    from ..dist.topology import TP_AXIS

    mesh, split = _split_axes()
    if not split:
        return _splash(q, k, v)
    rows = _dividing(mesh, [a for a in split if a != TP_AXIS], q.shape[0])
    heads = _dividing(mesh, [a for a in split if a == TP_AXIS], q.shape[2])
    spec = P(rows, None, heads, None)
    return jax.shard_map(_splash, in_specs=(spec,) * 3, out_specs=spec, axis_names=set(split),
                         check_vma=False)(q, k, v)
