"""Fused parameter-update kernels (Pallas, TPU target).

The bcast-sync trainer's epilogue applies the synchronized update to every
parameter bucket; fusing the read-modify-write keeps each element's traffic
at one HBM read + one write:

  * ``mix``        — model averaging  out = (1-a)*w + a*u   (CNTK-style)
  * ``scaled_add`` — gradient step    out = w - a*u

Both tile flat buckets through VMEM on a 1-D grid.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from .interpret import resolve_interpret

__all__ = ["mix", "scaled_add"]

_TILE = 64 * 1024


def _mix_kernel(w_ref, u_ref, a_ref, o_ref):
    a = a_ref[0]
    w = w_ref[...].astype(jnp.float32)
    u = u_ref[...].astype(jnp.float32)
    o_ref[...] = ((1.0 - a) * w + a * u).astype(o_ref.dtype)


def _scaled_add_kernel(w_ref, u_ref, a_ref, o_ref):
    a = a_ref[0]
    w = w_ref[...].astype(jnp.float32)
    u = u_ref[...].astype(jnp.float32)
    o_ref[...] = (w - a * u).astype(o_ref.dtype)


def _run(kernel, w, u, a, tile: int, interpret: bool):
    assert w.shape == u.shape and w.ndim == 1
    n = w.size
    tile = max(128, min(tile, max(n, 128)))
    pad = (-n) % tile
    if pad:
        w = jnp.concatenate([w, jnp.zeros((pad,), w.dtype)])
        u = jnp.concatenate([u, jnp.zeros((pad,), u.dtype)])
    num = w.size // tile
    w2, u2 = w.reshape(num, tile), u.reshape(num, tile)
    a_arr = jnp.asarray([a], jnp.float32)
    out = pl.pallas_call(
        kernel,
        grid=(num,),
        in_specs=[
            pl.BlockSpec((1, tile), lambda i: (i, 0)),
            pl.BlockSpec((1, tile), lambda i: (i, 0)),
            pl.BlockSpec(memory_space=pl.ANY),
        ],
        out_specs=pl.BlockSpec((1, tile), lambda i: (i, 0)),
        out_shape=jax.ShapeDtypeStruct((num, tile), w.dtype),
        interpret=interpret,
    )(w2, u2, a_arr)
    out = out.reshape(-1)
    return out[:n] if pad else out


@functools.partial(jax.jit, static_argnames=("tile", "interpret"))
def mix(w: jax.Array, u: jax.Array, a, *, tile: int = _TILE, interpret: bool | None = None) -> jax.Array:
    """Model averaging: ``(1-a)*w + a*u`` over flat buffers."""
    return _run(_mix_kernel, w, u, a, tile, resolve_interpret(interpret))


@functools.partial(jax.jit, static_argnames=("tile", "interpret"))
def scaled_add(w: jax.Array, u: jax.Array, a, *, tile: int = _TILE, interpret: bool | None = None) -> jax.Array:
    """SGD-style step: ``w - a*u`` over flat buffers."""
    return _run(_scaled_add_kernel, w, u, a, tile, resolve_interpret(interpret))
