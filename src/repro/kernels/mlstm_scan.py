"""mLSTM chunkwise recurrence (Pallas, TPU target), forward and backward.

The recurrence, per batch row and head, with f_t = exp(lf_t), i_t = exp(li_t):

    C_t = f_t C_{t-1} + i_t k_t v_tᵀ,   n_t = f_t n_{t-1} + i_t k_t,
    h_t = C_tᵀ q_t / (|n_t · q_t| + 1)

It runs chunkwise. Within a chunk of L steps, F is the inclusive sum of lf
from the chunk's start, a_s = li_s - F_s, and (C, n) is the state entering
the chunk:

    D[t, s] = exp(F_t + a_s) for s <= t, else 0;   P = (q kᵀ) ∘ D
    h = (P v + (q ∘ e^F) C) / (|rowsum(P) + (q ∘ e^F) · n| + 1)
    C <- e^{F_L} C + (k ∘ w)ᵀ v,   n <- e^{F_L} n + Σ_s w_s k_s,   w_s = exp(F_L + a_s)

The matrix memory never leaves VMEM while a (row, head) walks its chunks:
C (hd x hd f32) and n live in VMEM scratch, initialised from (C0, n0) at
the first chunk, and written out as the final state after the last. Only
q, k, v, the gates, h, the final state and, where asked for, the state
entering each chunk touch HBM. Grid: (batch row, head, chunk), the chunk
innermost; TPU grids run in order, so the scratch carries across chunks.

The primal (:func:`mlstm_scan` outside differentiation, and the forward
pass of a remat'd layer) writes no chunk states. The custom VJP's forward
rule, which remat runs again inside the backward pass, writes the state
entering each chunk: one per chunk, as a ``lax.scan`` over chunks keeps.
The backward kernel walks the chunks in reverse with dC and dn in VMEM,
recomputes each chunk's forward quantities from its saved state, and emits
dq, dk, dv, the cotangents of F and a per position, dC0 and dn0. F and a
are computed outside the kernels from lf and li (a cumulative sum inside
each chunk), so autodiff finishes d(lf) and d(li) there.

Numerics follow the model's float32 chunkwise scan on a TPU: C, n, their
cotangents, the decay sums and every accumulation are f32. A dot's operands
go to the MXU in q's dtype (bf16 in the model, where XLA's DEFAULT
precision rounds a float32 einsum's operands to bf16 too; f32 where q is).

Any shape runs: :func:`mlstm_scan` pads the head dim to a multiple of 128
with zero columns (zero columns of q and k change no product and no n), and
the sequence to whole chunks with steps of lf = 0 and li = -inf, which
leave the state as it was. The padding is sliced off.

Validated against ref.py with interpret=True (CPU); compiles to the real
Mosaic pipeline on TPU.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from .interpret import resolve_interpret
from .mamba_scan import time_blocks

__all__ = ["mlstm_scan"]

LANES = 128      # head dims are padded to a multiple of this
GATE_ROWS = 8    # F and a ride on the first two rows of one f32 sublane tile
# the backward kernel holds a chunk's (hd x hd) state, its cotangent and
# their products in f32 (several MB at hd = 512): past the default scoped limit
VMEM_LIMIT = 64 * 1024 * 1024
NT = (((1,), (1,)), ((), ()))  # a @ bᵀ
TN = (((0,), (0,)), ((), ()))  # aᵀ @ b
F32 = jnp.float32


def _round_up(n: int, m: int) -> int:
    return -(-n // m) * m


def _dot(a, b, dtype):
    return jnp.dot(a.astype(dtype), b.astype(dtype), preferred_element_type=F32)


def _dot_nt(a, b, dtype):
    return lax.dot_general(a.astype(dtype), b.astype(dtype), NT, preferred_element_type=F32)


def _dot_tn(a, b, dtype):
    return lax.dot_general(a.astype(dtype), b.astype(dtype), TN, preferred_element_type=F32)


def _sum(x):
    """The sum of a 2-D value, as (1, 1)."""
    return jnp.sum(jnp.sum(x, axis=1, keepdims=True), axis=0, keepdims=True)


def _chunk(q, k, v, g, C, n):
    """The forward quantities of one chunk. q, k, v: (L, hd); g: (8, L), F
    and a on rows 0 and 1; C: (hd, hd) and n: (1, hd), the state entering
    the chunk."""
    L = q.shape[0]
    gt = g.T                                      # (L, 8): F and a as columns
    F, a = gt[:, 0:1], gt[:, 1:2]
    # F_L as (1, 1) through a reduction: Mosaic broadcasts a reduced value
    # over sublanes and lanes, not a sliced one
    row, col = (lax.broadcasted_iota(jnp.int32, g.shape, d) for d in (0, 1))
    FL = _sum(jnp.where((row == 0) & (col == L - 1), g, 0.0))
    t = lax.broadcasted_iota(jnp.int32, (L, L), 0)
    s = lax.broadcasted_iota(jnp.int32, (L, L), 1)
    D = jnp.exp(jnp.where(t >= s, F + g[1:2, :], -jnp.inf))
    P = _dot_nt(q, k, q.dtype) * D
    E = jnp.exp(F)
    qe = q.astype(F32) * E
    num = _dot(P, v, q.dtype) + _dot(qe, C, q.dtype)
    nq = jnp.sum(P, axis=1, keepdims=True) + jnp.sum(qe * n, axis=1, keepdims=True)
    W = jnp.exp(FL + a)
    return dict(F=F, FL=FL, D=D, P=P, E=E, qe=qe, num=num, nq=nq, W=W, eL=jnp.exp(FL),
                kw=k.astype(F32) * W)


def _fwd_kernel(q_ref, k_ref, v_ref, g_ref, c0_ref, n0_ref, h_ref, cf_ref, nf_ref, *rest,
                nc):
    *states, c_scr, n_scr = rest
    c = pl.program_id(2)

    @pl.when(c == 0)
    def _():
        c_scr[...] = c0_ref[0, 0]
        n_scr[...] = n0_ref[0, 0]

    C, n = c_scr[...], n_scr[...]
    if states:
        cs_ref, ns_ref = states
        cs_ref[0, 0, 0] = C
        ns_ref[0, 0, 0] = n
    q, v = q_ref[0], v_ref[0]
    x = _chunk(q, k_ref[0], v, g_ref[0, 0], C, n)
    h_ref[0] = (x["num"] / (jnp.abs(x["nq"]) + 1.0)).astype(h_ref.dtype)
    c_scr[...] = x["eL"] * C + _dot_tn(x["kw"], v, q.dtype)
    n_scr[...] = x["eL"] * n + jnp.sum(x["kw"], axis=0, keepdims=True)

    @pl.when(c == nc - 1)
    def _():
        cf_ref[0, 0] = c_scr[...]
        nf_ref[0, 0] = n_scr[...]


def _bwd_kernel(q_ref, k_ref, v_ref, g_ref, cs_ref, ns_ref, dh_ref, dcf_ref, dnf_ref,
                dq_ref, dk_ref, dv_ref, dg_ref, dc0_ref, dn0_ref, dc_scr, dn_scr, *, nc):
    c = pl.program_id(2)

    @pl.when(c == 0)
    def _():
        dc_scr[...] = dcf_ref[0, 0]
        dn_scr[...] = dnf_ref[0, 0]

    q, k, v = q_ref[0], k_ref[0], v_ref[0]
    C, n = cs_ref[0, 0, 0], ns_ref[0, 0, 0]
    dCo, dno = dc_scr[...], dn_scr[...]           # cotangents of the outgoing state
    x = _chunk(q, k, v, g_ref[0, 0], C, n)
    L, mx = q.shape[0], q.dtype
    E, W, P, qe, kw, eL = x["E"], x["W"], x["P"], x["qe"], x["kw"], x["eL"]

    # h = num / den, den = |nq| + 1
    dh = dh_ref[0].astype(F32)
    den = jnp.abs(x["nq"]) + 1.0
    dnum = dh / den
    dnq = -jnp.sum(dh * x["num"], axis=1, keepdims=True) / (den * den) * jnp.sign(x["nq"])
    # num = P v + qe C;  nq = rowsum(P) + qe n;  C' = eL C + kwᵀ v;  n' = eL n + colsum(kw)
    dP = _dot_nt(dnum, v, mx) + dnq
    dqe = _dot_nt(dnum, C, mx) + dnq * n
    dkw = _dot_nt(v, dCo, mx) + dno
    dv = _dot_tn(P, dnum, mx) + _dot(kw, dCo, mx)
    # P = (q kᵀ) ∘ D;  qe = q ∘ e^F;  kw = k ∘ w
    dS = dP * x["D"]
    dq = _dot(dS, k, mx) + dqe * E
    dk = _dot_tn(dS, q, mx) + dkw * W
    # the gates, through D = exp(F_t + a_s), e^F, w = exp(F_L + a) and e^{F_L}
    dlogD = dP * P
    dw = jnp.sum(dkw * k.astype(F32), axis=1, keepdims=True) * W
    dFL = (_sum(C * dCo) + _sum(n * dno)) * eL + _sum(dw)
    row = lax.broadcasted_iota(jnp.int32, (L, 1), 0)
    dF = (jnp.sum(dlogD, axis=1, keepdims=True) + jnp.sum(dqe * q.astype(F32), axis=1,
                                                          keepdims=True) * E
          + jnp.where(row == L - 1, dFL, 0.0))
    lane = lax.broadcasted_iota(jnp.int32, (L, GATE_ROWS), 1)
    dg = jnp.where(lane == 0, dF, jnp.where(lane == 1, dw, 0.0)).T
    grow = lax.broadcasted_iota(jnp.int32, (GATE_ROWS, L), 0)
    dg_ref[0, 0] = dg + jnp.where(grow == 1, jnp.sum(dlogD, axis=0, keepdims=True), 0.0)
    dq_ref[0] = dq.astype(dq_ref.dtype)
    dk_ref[0] = dk.astype(dk_ref.dtype)
    dv_ref[0] = dv.astype(dv_ref.dtype)
    dc_scr[...] = eL * dCo + _dot_tn(qe, dnum, mx)
    dn_scr[...] = eL * dno + jnp.sum(qe * dnq, axis=0, keepdims=True)

    @pl.when(c == nc - 1)
    def _():
        dc0_ref[0, 0] = dc_scr[...]
        dn0_ref[0, 0] = dn_scr[...]


def _params():
    return pltpu.CompilerParams(
        dimension_semantics=("parallel", "parallel", "arbitrary"),
        vmem_limit_bytes=VMEM_LIMIT,
    )


def _specs(L, hd, nc, reverse):
    """Block specs over the grid (row, head, chunk): a chunk of q, k, v, h
    ((B, T, H * hd) arrays), of the gates ((B, H, 8, T)), a chunk state
    ((B, H, nc, hd, hd) and (B, H, nc, 1, hd)), and a row's whole state
    ((B, H, hd, hd) and (B, H, 1, hd))."""
    at = (lambda c: nc - 1 - c) if reverse else (lambda c: c)
    seq = pl.BlockSpec((1, L, hd), lambda b, h, c: (b, at(c), h))
    gate = pl.BlockSpec((1, 1, GATE_ROWS, L), lambda b, h, c: (b, h, 0, at(c)))
    cs = pl.BlockSpec((1, 1, 1, hd, hd), lambda b, h, c: (b, h, at(c), 0, 0))
    ns = pl.BlockSpec((1, 1, 1, 1, hd), lambda b, h, c: (b, h, at(c), 0, 0))
    mat = pl.BlockSpec((1, 1, hd, hd), lambda b, h, c: (b, h, 0, 0))
    vec = pl.BlockSpec((1, 1, 1, hd), lambda b, h, c: (b, h, 0, 0))
    return seq, gate, cs, ns, mat, vec


def _fwd_call(q, k, v, g, c0, n0, *, L, states, interpret):
    B, T, _ = q.shape
    H, hd, nc = g.shape[1], c0.shape[-1], T // L
    seq, gate, cs, ns, mat, vec = _specs(L, hd, nc, reverse=False)
    out_specs = [seq, mat, vec]
    out_shape = [jax.ShapeDtypeStruct(q.shape, q.dtype), jax.ShapeDtypeStruct(c0.shape, F32),
                 jax.ShapeDtypeStruct(n0.shape, F32)]
    if states:
        out_specs += [cs, ns]
        out_shape += [jax.ShapeDtypeStruct((B, H, nc, hd, hd), F32),
                      jax.ShapeDtypeStruct((B, H, nc, 1, hd), F32)]
    return pl.pallas_call(
        functools.partial(_fwd_kernel, nc=nc),
        grid=(B, H, nc),
        in_specs=[seq, seq, seq, gate, mat, vec],
        out_specs=out_specs,
        out_shape=out_shape,
        scratch_shapes=[pltpu.VMEM((hd, hd), F32), pltpu.VMEM((1, hd), F32)],
        compiler_params=_params(),
        interpret=interpret,
        name="mlstm_scan_fwd",
    )(q, k, v, g, c0, n0)


def _bwd_call(q, k, v, g, cs, ns, dh, dcf, dnf, *, L, interpret):
    B, T, _ = q.shape
    H, hd, nc = g.shape[1], cs.shape[-1], T // L
    seq, gate, cs_spec, ns_spec, mat, vec = _specs(L, hd, nc, reverse=True)
    return pl.pallas_call(
        functools.partial(_bwd_kernel, nc=nc),
        grid=(B, H, nc),
        in_specs=[seq, seq, seq, gate, cs_spec, ns_spec, seq, mat, vec],
        out_specs=[seq, seq, seq, gate, mat, vec],
        out_shape=[
            jax.ShapeDtypeStruct(q.shape, q.dtype),
            jax.ShapeDtypeStruct(k.shape, k.dtype),
            jax.ShapeDtypeStruct(v.shape, v.dtype),
            jax.ShapeDtypeStruct(g.shape, F32),
            jax.ShapeDtypeStruct(dcf.shape, F32),
            jax.ShapeDtypeStruct(dnf.shape, F32),
        ],
        scratch_shapes=[pltpu.VMEM((hd, hd), F32), pltpu.VMEM((1, hd), F32)],
        compiler_params=_params(),
        interpret=interpret,
        name="mlstm_scan_bwd",
    )(q, k, v, g, cs, ns, dh, dcf, dnf)


@functools.partial(jax.custom_vjp, nondiff_argnums=(6, 7))
def _scan(q, k, v, g, c0, n0, L, interpret):
    return tuple(_fwd_call(q, k, v, g, c0, n0, L=L, states=False, interpret=interpret))


def _scan_fwd(q, k, v, g, c0, n0, L, interpret):
    h, cf, nf, cs, ns = _fwd_call(q, k, v, g, c0, n0, L=L, states=True, interpret=interpret)
    return (h, cf, nf), (q, k, v, g, cs, ns)


def _scan_bwd(L, interpret, res, cts):
    return tuple(_bwd_call(*res, *cts, L=L, interpret=interpret))


# under remat the primal runs the kernel without chunk states, and only the
# recompute inside the backward pass runs the forward rule
_scan.defvjp(_scan_fwd, _scan_bwd, optimize_remat=True)


def mlstm_scan(q, k, v, lf, li, C0, n0, *, chunk: int, interpret: bool | None = None):
    """q, k, v: (B, T, H, hd); lf, li: (B, T, H) log forget and input gates;
    C0: (B, H, hd, hd); n0: (B, H, hd). Returns (h (B, T, H, hd) in q's
    dtype, (C (B, H, hd, hd), n (B, H, hd)) f32, the final state). Chunks
    of :func:`~repro.kernels.mamba_scan.time_blocks` (T, chunk) steps; any T
    and hd. ``interpret=None`` follows the backend. Differentiable (custom
    VJP, both passes kernels)."""
    B, T, H, hd = q.shape
    L, Tp = time_blocks(T, chunk)
    dp = _round_up(hd, LANES)
    steps = lambda x, value=0.0: jnp.pad(x.astype(F32), ((0, 0), (0, Tp - T), (0, 0)),
                                         constant_values=value)
    F = jnp.cumsum(steps(lf).reshape(B, Tp // L, L, H), axis=2).reshape(B, Tp, H)
    a = steps(li, -jnp.inf) - F
    g = jnp.pad(jnp.stack([F, a], axis=2).transpose(0, 3, 2, 1),
                ((0, 0), (0, 0), (0, GATE_ROWS - 2), (0, 0)))
    heads = lambda x: jnp.pad(x, ((0, 0), (0, Tp - T), (0, 0), (0, dp - hd))).reshape(
        B, Tp, H * dp)
    c0 = jnp.pad(C0.astype(F32), ((0, 0), (0, 0), (0, dp - hd), (0, dp - hd)))
    n0 = jnp.pad(n0.astype(F32), ((0, 0), (0, 0), (0, dp - hd)))[:, :, None]
    h, cf, nf = _scan(heads(q), heads(k), heads(v), g, c0, n0, L, resolve_interpret(interpret))
    return h.reshape(B, Tp, H, dp)[:, :T, :, :hd], (cf[..., :hd, :hd], nf[:, :, 0, :hd])
