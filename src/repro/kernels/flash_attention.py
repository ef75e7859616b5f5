"""Flash attention kernel (Pallas, TPU target).

The compute hot-spot of every assigned transformer. Blocked online-softmax
over (q-block, kv-block) grid tiles with VMEM scratch accumulators; causal /
sliding-window / prefix-LM masks are applied per tile, and tiles that are
fully masked are SKIPPED via ``pl.when`` (the block-level skipping our
XLA-portable fallback, models.layers._chunked_sdpa, cannot do — see
EXPERIMENTS.md §Perf).

Grid: (batch, q_heads, T/bq, S/bk); the innermost (kv) dim iterates
sequentially on TPU, so scratch (acc, m, l) carries across kv blocks.
GQA: kv-head index = q-head // (H // KV) via the k/v BlockSpec index maps.

Validated against ref.py with interpret=True (CPU); compiles to the real
Mosaic pipeline on TPU.
"""
from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from .interpret import resolve_interpret

__all__ = ["flash_attention"]

NEG_INF = -1e30


def _kernel(q_ref, k_ref, v_ref, o_ref, acc_ref, m_ref, l_ref, *, scale, causal, window, prefix, bq, bk):
    qi = pl.program_id(2)
    ki = pl.program_id(3)
    nk = pl.num_programs(3)

    @pl.when(ki == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)

    q0 = qi * bq
    k0 = ki * bk
    relevant = True
    if causal:
        relevant = k0 <= q0 + bq - 1
    if window is not None:
        in_win = k0 + bk - 1 > q0 - window
        if prefix:
            in_win = in_win | (k0 < prefix)
        relevant = relevant & in_win

    @pl.when(relevant)
    def _body():
        q = q_ref[0, :, 0, :].astype(jnp.float32)
        k = k_ref[0, :, 0, :].astype(jnp.float32)
        v = v_ref[0, :, 0, :].astype(jnp.float32)
        s = jax.lax.dot_general(
            q, k, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32
        ) * scale                                               # (bq, bk)
        i = q0 + jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 0)
        j = k0 + jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 1)
        if causal:
            mask = j <= i
            if prefix:
                mask = mask | (j < prefix)
        else:
            mask = jnp.ones((bq, bk), bool)
        if window is not None:
            w_ok = j > i - window
            if prefix:
                w_ok = w_ok | ((j < prefix) & (i < prefix))
            mask = mask & w_ok
        s = jnp.where(mask, s, NEG_INF)
        m_prev = m_ref[...]
        m_new = jnp.maximum(m_prev, jnp.max(s, axis=-1))
        p = jnp.exp(s - m_new[:, None])
        corr = jnp.exp(m_prev - m_new)
        l_ref[...] = l_ref[...] * corr + jnp.sum(p, axis=-1)
        acc_ref[...] = acc_ref[...] * corr[:, None] + jax.lax.dot_general(
            p, v, (((1,), (0,)), ((), ())), preferred_element_type=jnp.float32
        )
        m_ref[...] = m_new

    @pl.when(ki == nk - 1)
    def _finish():
        l = jnp.maximum(l_ref[...], 1e-30)
        o_ref[0, :, 0, :] = (acc_ref[...] / l[:, None]).astype(o_ref.dtype)


@functools.partial(
    jax.jit,
    static_argnames=("causal", "window", "prefix", "bq", "bk", "interpret"),
)
def flash_attention(
    q: jax.Array,
    k: jax.Array,
    v: jax.Array,
    *,
    causal: bool = True,
    window: Optional[int] = None,
    prefix: int = 0,
    bq: int = 128,
    bk: int = 128,
    interpret: bool | None = None,
) -> jax.Array:
    """q: (B, T, H, hd); k, v: (B, S, KV, hd) with H % KV == 0.

    Returns (B, T, H, hd). ``interpret=None`` follows the backend.
    """
    B, T, H, hd = q.shape
    S, KV = k.shape[1], k.shape[2]
    assert H % KV == 0
    G = H // KV
    bq = min(bq, T)
    bk = min(bk, S)
    assert T % bq == 0 and S % bk == 0, (T, bq, S, bk)
    grid = (B, H, T // bq, S // bk)

    kernel = functools.partial(
        _kernel,
        scale=hd**-0.5,
        causal=causal,
        window=window,
        prefix=prefix,
        bq=bq,
        bk=bk,
    )
    scratch = [
        pltpu.VMEM((bq, hd), jnp.float32),
        pltpu.VMEM((bq,), jnp.float32),
        pltpu.VMEM((bq,), jnp.float32),
    ]
    return pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, bq, 1, hd), lambda b, h, qi, ki: (b, qi, h, 0)),
            pl.BlockSpec((1, bk, 1, hd), lambda b, h, qi, ki: (b, ki, h // G, 0)),
            pl.BlockSpec((1, bk, 1, hd), lambda b, h, qi, ki: (b, ki, h // G, 0)),
        ],
        out_specs=pl.BlockSpec((1, bq, 1, hd), lambda b, h, qi, ki: (b, qi, h, 0)),
        out_shape=jax.ShapeDtypeStruct((B, T, H, hd), q.dtype),
        scratch_shapes=scratch,
        interpret=resolve_interpret(interpret),
    )(q, k, v)
