"""Pure-jnp oracles for every kernel (the tests' ground truth)."""
from __future__ import annotations

from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

__all__ = [
    "chunked_copy_ref",
    "fused_combine_ref",
    "inkernel_shared_ref",
    "mix_ref",
    "scaled_add_ref",
    "flash_attention_ref",
    "mamba_scan_ref",
]


def chunked_copy_ref(x: jax.Array) -> jax.Array:
    return jnp.array(x, copy=True)


def fused_combine_ref(cur, recv, row_mode):
    """Row-mode merge: per row, mode 2 accumulates recv, mode 1 selects it,
    mode 0 passes cur through bit-identically."""
    return jnp.where(row_mode == 2, cur + recv, jnp.where(row_mode == 1, recv, cur))


def inkernel_shared_ref(tables, shared):
    """Numpy oracle for the in-kernel schedule replay over the SHARED
    ``(n, num_chunks, chunk)`` buffer (row r = rank r's local buffer).

    Identical control flow to ``core.simulator.simulate_lowered``: per round,
    classes apply sequentially; within a class every source block is
    snapshotted BEFORE any destination writes (a rank may be src of one pair
    and dst of another in the same class); a destination whose window is
    empty (``hi <= lo``) keeps its rows bit-identically. ``tables`` is a
    :class:`repro.core.schedules.KernelTables`.
    """
    out = np.array(shared, copy=True)
    for s in range(tables.num_rounds):
        for c in range(tables.num_classes):
            perm, block = tables.perms[c], tables.blocks[c]
            if block == 0 or not perm:
                continue
            snap = {
                dst: out[src, tables.send_start[c, s, src]:
                         tables.send_start[c, s, src] + block].copy()
                for src, dst in perm
            }
            for _src, dst in perm:
                lo, hi = tables.lo[c, s, dst], tables.hi[c, s, dst]
                if hi <= lo:
                    continue
                r0 = tables.recv_start[c, s, dst]
                if tables.combine[c, s]:
                    out[dst, r0 + lo:r0 + hi] += snap[dst][lo:hi]
                else:
                    out[dst, r0 + lo:r0 + hi] = snap[dst][lo:hi]
    return out


def mix_ref(w, u, a):
    wf = w.astype(jnp.float32)
    uf = u.astype(jnp.float32)
    return ((1.0 - a) * wf + a * uf).astype(w.dtype)


def scaled_add_ref(w, u, a):
    return (w.astype(jnp.float32) - a * u.astype(jnp.float32)).astype(w.dtype)


def flash_attention_ref(
    q, k, v, *, causal: bool = True, window: Optional[int] = None, prefix: int = 0
):
    """Unblocked softmax attention with the same mask semantics."""
    B, T, H, hd = q.shape
    S, KV = k.shape[1], k.shape[2]
    G = H // KV
    qg = q.reshape(B, T, KV, G, hd).astype(jnp.float32)
    s = jnp.einsum("btkgh,bskh->bkgts", qg, k.astype(jnp.float32)) * hd**-0.5
    i = jnp.arange(T)[:, None]
    j = jnp.arange(S)[None, :]
    if causal:
        mask = j <= i
        if prefix:
            mask = mask | (j < prefix)
    else:
        mask = jnp.ones((T, S), bool)
    if window is not None:
        w_ok = j > i - window
        if prefix:
            w_ok = w_ok | ((j < prefix) & (i < prefix))
        mask = mask & w_ok
    s = jnp.where(mask[None, None, None], s, -1e30)
    p = jax.nn.softmax(s, axis=-1)
    out = jnp.einsum("bkgts,bskh->btkgh", p, v.astype(jnp.float32))
    return out.reshape(B, T, H, hd).astype(q.dtype)


def mamba_scan_ref(dt, x, Bm, Cm, A, h0, *, chunk: int):
    """The selective scan as chunks of ``chunk`` steps: a ``lax.scan`` over
    the chunks carries the state, and each chunk runs an associative scan
    over its (Bt, chunk, di, N) f32 state sequence, fused with the C
    projection, as the model ran it before the kernels. Any T that
    ``chunk`` divides; any di. Same arguments and results as
    :func:`repro.kernels.ops.mamba_scan`; the tests' oracle."""
    Bt, T, di = dt.shape
    nc = T // chunk

    def rs(a):  # (Bt,T,...) -> (nc,Bt,chunk,...)
        return jnp.moveaxis(a.reshape(Bt, nc, chunk, *a.shape[2:]), 1, 0)

    def op(u, w):
        la1, h1 = u
        la2, h2 = w
        return (la1 + la2, jnp.exp(la2) * h1 + h2)

    def step(h_in, xs):
        dt_c, x_c, b_c, c_c = xs             # (Bt,L,di) / (Bt,L,N)
        log_a = dt_c[..., None] * A          # (Bt,L,di,N)
        bu = (dt_c * x_c)[..., None] * b_c[..., None, :]
        la_cum, h_intra = lax.associative_scan(op, (log_a, bu), axis=1)
        h = h_intra + jnp.exp(la_cum) * h_in[:, None]
        y_c = jnp.einsum("bldn,bln->bld", h, c_c)
        return h[:, -1], y_c

    h_last, y_chunks = lax.scan(step, h0, (rs(dt), rs(x), rs(Bm), rs(Cm)))
    return jnp.moveaxis(y_chunks, 0, 1).reshape(Bt, T, di), h_last
