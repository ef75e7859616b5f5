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
    "mlstm_scan_ref",
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


def mlstm_scan_ref(q, k, v, lf, li, C0, n0, *, chunk: int):
    """The chunkwise mLSTM recurrence in plain jnp: a ``lax.scan`` over
    chunks of ``chunk`` steps carries the matrix memory C (B, H, hd, hd)
    and the normaliser n (B, H, hd) in f32, and each chunk is computed in
    parallel with einsums. Any T that ``chunk`` divides. Same arguments as
    :func:`repro.kernels.ops.mlstm_scan`; returns (h (B, T, H, hd) f32,
    (C, n)). The tests' oracle."""
    B, T, H, hd = q.shape
    L = chunk
    nc = T // L

    def rs(a):  # (B,T,H,...) -> (nc,B,H,L,...)
        a = jnp.moveaxis(a.reshape(B, nc, L, *a.shape[2:]), 1, 0)
        return jnp.moveaxis(a, 3, 2) if a.ndim >= 5 else jnp.swapaxes(a, 2, 3)

    idx = jnp.arange(L)
    causal = idx[:, None] >= idx[None, :]

    def step(carry, xs):
        C, n = carry
        qq, kk, vv, lff, lii = xs           # (B,H,L,hd), (B,H,L)
        qf, kf, vf = (a.astype(jnp.float32) for a in (qq, kk, vv))
        F = jnp.cumsum(lff, axis=-1)        # (B,H,L) inclusive decay sums
        # intra-chunk: scores_ts = (q_t.k_s) exp(F_t - F_s + li_s), s <= t
        dec = F[..., :, None] - F[..., None, :] + lii[..., None, :]
        dec = jnp.where(causal, dec, -jnp.inf)
        scores = jnp.einsum("bhtd,bhsd->bhts", qf, kf) * jnp.exp(dec)
        num = jnp.einsum("bhts,bhsd->bhtd", scores, vf)
        # inter-chunk: exp(F_t) * (C q_t, n q_t)
        ef = jnp.exp(F)[..., None]
        num = num + jnp.einsum("bhtd,bhde->bhte", qf * ef, C)
        nq = jnp.einsum("bhtd,bhd->bht", qf * ef, n)
        # intra normalizer: sum_s exp(F_t - F_s + li_s) (k_s . q_t)
        nq = nq + jnp.einsum("bhts,bhsd,bhtd->bht", jnp.exp(dec), kf, qf)
        h = num / (jnp.abs(nq)[..., None] + 1.0)
        # carry updates
        eL = jnp.exp(F[..., -1])[..., None]                 # (B,H,1)
        w_s = jnp.exp(F[..., -1:] - F + lii)                # (B,H,L)
        C_new = C * eL[..., None] + jnp.einsum("bhs,bhsd,bhse->bhde", w_s, kf, vf)
        n_new = n * eL + jnp.einsum("bhs,bhsd->bhd", w_s, kf)
        return (C_new, n_new), h

    (C_f, n_f), hs = lax.scan(step, (C0, n0), (rs(q), rs(k), rs(v), rs(lf), rs(li)))
    # hs: (nc, B, H, L, hd) -> (B, T, H, hd)
    h = jnp.moveaxis(jnp.moveaxis(hs, 0, 1), 2, 3)
    return h.reshape(B, T, H, hd), (C_f, n_f)
