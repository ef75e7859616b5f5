"""Recurrent / state-space mixers: mLSTM & sLSTM (xLSTM) and Mamba (S6).

TPU adaptation notes (DESIGN.md Sec. 2): the GPU reference implementations
use fused CUDA scans. Here the mLSTM and Mamba recurrences run as Pallas
kernel pairs (forward and backward under a custom VJP) that walk time in
order with the state held in VMEM; the sLSTM is a ``lax.scan`` over time.
mLSTM (``kernels/mlstm_scan.py``) processes the
sequence *chunkwise*: each chunk is computed with matmuls on the MXU while
its (hd x hd) matrix memory and normaliser stay in VMEM across the chunks,
so only q, k, v, the gates, h, the final state and (for the backward) the
state entering each chunk touch HBM. Mamba's diagonal recurrence has no
matmul to give the MXU; its kernel pair (``kernels/mamba_scan.py``) steps
through time, so only dt, x, B, C, y and the state at each chunk boundary
touch HBM. Every shape takes them: the kernel wrappers pad the channels to
whole lane tiles and odd prompt lengths to whole chunks with steps that
leave the state as it was.

Simplification recorded in DESIGN.md: xLSTM's stabilized exponential gating
is replaced by log-sigmoid gating (decay factors <= 1, unconditionally
stable). The matrix-memory structure, state shapes, and compute/collective
footprint — what the systems reproduction measures — are unchanged.

All mixers expose:
    init_*(key, cfg)        -> params
    *_seq(p, x, cfg)        -> (y, final_state)   # train / prefill
    *_step(p, x1, state, cfg) -> (y1, new_state)  # single-token decode
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
from jax import lax

from ..kernels import ops
from .layers import _norm_init, down_proj

__all__ = [
    "init_mlstm",
    "mlstm_seq",
    "mlstm_step",
    "init_slstm",
    "slstm_seq",
    "slstm_step",
    "init_mamba",
    "mamba_seq",
    "mamba_step",
]


# ---------------------------------------------------------------------------
# mLSTM (matrix-memory LSTM, chunkwise linear attention with decay)
# ---------------------------------------------------------------------------


def init_mlstm(key, cfg, dtype=jnp.bfloat16):
    d = cfg.d_model
    di = cfg.ssm_expand * d
    ks = jax.random.split(key, 7)
    s = d**-0.5
    return {
        "wq": _norm_init(ks[0], (d, di), s, dtype),
        "wk": _norm_init(ks[1], (d, di), s, dtype),
        "wv": _norm_init(ks[2], (d, di), s, dtype),
        "wg": _norm_init(ks[3], (d, di), s, dtype),
        "wi": _norm_init(ks[4], (d, cfg.num_heads), s, jnp.float32),
        "wf": _norm_init(ks[5], (d, cfg.num_heads), s, jnp.float32),
        "bf": jnp.full((cfg.num_heads,), 2.0, jnp.float32),  # open forget gates
        "wo": _norm_init(ks[6], (di, d), di**-0.5, dtype),
    }


def _mlstm_qkvg(p, x, cfg):
    B, T, d = x.shape
    H = cfg.num_heads
    di = cfg.ssm_expand * d
    hd = di // H
    q = (x @ p["wq"]).reshape(B, T, H, hd) * hd**-0.5
    k = (x @ p["wk"]).reshape(B, T, H, hd) * hd**-0.5
    v = (x @ p["wv"]).reshape(B, T, H, hd)
    g = jax.nn.sigmoid(x @ p["wg"])
    lf = jax.nn.log_sigmoid((x.astype(jnp.float32) @ p["wf"]) + p["bf"])  # (B,T,H)
    li = jax.nn.log_sigmoid(x.astype(jnp.float32) @ p["wi"])
    return q, k, v, g, lf, li


def mlstm_seq(p, x, cfg, state=None):
    """Chunkwise mLSTM. Returns (y, (C, n)) with C (B,H,hd,hd), n (B,H,hd)."""
    B, T, d = x.shape
    H = cfg.num_heads
    di = cfg.ssm_expand * d
    hd = di // H
    q, k, v, g, lf, li = _mlstm_qkvg(p, x, cfg)
    if state is None:
        C0 = jnp.zeros((B, H, hd, hd), jnp.float32)
        n0 = jnp.zeros((B, H, hd), jnp.float32)
    else:
        C0, n0 = state
    h, (C_f, n_f) = ops.mlstm_scan(q, k, v, lf, li, C0, n0, chunk=cfg.ssm_chunk)
    y = down_proj(g * h.reshape(B, T, di).astype(x.dtype), p["wo"])
    return y, (C_f, n_f)


def mlstm_step(p, x, state, cfg):
    """Single-token decode. x: (B, 1, d); state (C, n)."""
    B = x.shape[0]
    H = cfg.num_heads
    di = cfg.ssm_expand * cfg.d_model
    hd = di // H
    q, k, v, g, lf, li = _mlstm_qkvg(p, x, cfg)
    qf = q[:, 0].reshape(B, H, hd).astype(jnp.float32)
    kf = k[:, 0].reshape(B, H, hd).astype(jnp.float32)
    vf = v[:, 0].reshape(B, H, hd).astype(jnp.float32)
    f = jnp.exp(lf[:, 0])[..., None]        # (B,H,1)
    i = jnp.exp(li[:, 0])[..., None]
    C, n = state
    C = C * f[..., None] + i[..., None] * kf[..., :, None] * vf[..., None, :]
    n = n * f + i * kf
    num = jnp.einsum("bhd,bhde->bhe", qf, C)
    nq = jnp.einsum("bhd,bhd->bh", qf, n)
    h = (num / (jnp.abs(nq)[..., None] + 1.0)).reshape(B, 1, di).astype(x.dtype)
    y = down_proj(g * h, p["wo"])
    return y, (C, n)


# ---------------------------------------------------------------------------
# sLSTM (scalar-memory LSTM with head-wise recurrent mixing) — sequential
# ---------------------------------------------------------------------------


def init_slstm(key, cfg, dtype=jnp.bfloat16):
    d = cfg.d_model
    H = cfg.num_heads
    hd = d // H
    ks = jax.random.split(key, 3)
    return {
        "w": _norm_init(ks[0], (d, 4 * d), d**-0.5, jnp.float32),
        "r": _norm_init(ks[1], (H, hd, 4 * hd), hd**-0.5, jnp.float32),
        "b": jnp.concatenate(
            [jnp.zeros((2 * d,)), jnp.full((d,), 2.0), jnp.zeros((d,))]
        ).astype(jnp.float32),
        "wo_r": _norm_init(ks[2], (d, d), d**-0.5, dtype),
    }


def _slstm_cell(p, xt, carry, cfg):
    """xt: (B, 4d) pre-projected input; carry: (c, n, h) each (B, d)."""
    d = cfg.d_model
    H = cfg.num_heads
    hd = d // H
    c, n, h = carry
    hr = h.reshape(-1, H, hd)
    rec = jnp.einsum("bhk,hkm->bhm", hr, p["r"]).reshape(-1, 4 * d)
    z, i, f, o = jnp.split(xt + rec + p["b"], 4, axis=-1)
    z = jnp.tanh(z)
    i = jax.nn.sigmoid(i)
    f = jax.nn.sigmoid(f)
    o = jax.nn.sigmoid(o)
    c = f * c + i * z
    n = f * n + i
    h = o * c / (jnp.abs(n) + 1.0)
    return (c, n, h)


def slstm_seq(p, x, cfg, state=None):
    B, T, d = x.shape
    xp = (x.astype(jnp.float32) @ p["w"])   # (B,T,4d)
    if state is None:
        z = jnp.zeros((B, d), jnp.float32)
        state = (z, z, z)

    def step(carry, xt):
        carry = _slstm_cell(p, xt, carry, cfg)
        return carry, carry[2]

    state, hs = lax.scan(step, state, jnp.moveaxis(xp, 0, 1))
    y = jnp.moveaxis(hs, 0, 1).astype(x.dtype) @ p["wo_r"]
    return y, state


def slstm_step(p, x, state, cfg):
    xt = (x[:, 0].astype(jnp.float32) @ p["w"])
    state = _slstm_cell(p, xt, state, cfg)
    y = state[2][:, None].astype(x.dtype) @ p["wo_r"]
    return y, state


# ---------------------------------------------------------------------------
# Mamba (S6 selective scan, diagonal state) — chunked associative scan
# ---------------------------------------------------------------------------


def init_mamba(key, cfg, dtype=jnp.bfloat16):
    d = cfg.d_model
    di = cfg.ssm_expand * d
    N = cfg.ssm_state
    ks = jax.random.split(key, 6)
    return {
        "w_in": _norm_init(ks[0], (d, 2 * di), d**-0.5, dtype),
        "conv": _norm_init(ks[1], (cfg.ssm_conv, di), 0.5, jnp.float32),
        "w_bc": _norm_init(ks[2], (di, 2 * N), di**-0.5, jnp.float32),
        "w_dt": _norm_init(ks[3], (di, di), di**-0.5, jnp.float32),
        "b_dt": jnp.full((di,), -4.0, jnp.float32),  # softplus ~= 0.018
        "a_log": jnp.log(jnp.tile(jnp.arange(1, N + 1, dtype=jnp.float32), (di, 1))),
        "d_skip": jnp.ones((di,), jnp.float32),
        "w_out": _norm_init(ks[4], (di, d), di**-0.5, dtype),
    }


def _mamba_conv(p, xb, conv_state=None):
    """Depthwise causal conv, width W. xb: (B,T,di) f32.
    conv_state: (B, W-1, di) previous inputs (or None -> zeros)."""
    W = p["conv"].shape[0]
    B, T, di = xb.shape
    if conv_state is None:
        conv_state = jnp.zeros((B, W - 1, di), xb.dtype)
    xp = jnp.concatenate([conv_state, xb], axis=1)       # (B, T+W-1, di)
    out = sum(xp[:, i : i + T] * p["conv"][i] for i in range(W))
    new_state = xp[:, -(W - 1) :]
    return jax.nn.silu(out), new_state


def mamba_seq(p, x, cfg, state=None):
    """Returns (y, (ssm_state (B,di,N), conv_state (B,W-1,di)))."""
    B, _, d = x.shape
    di = cfg.ssm_expand * d
    N = cfg.ssm_state
    xz = x @ p["w_in"]
    xb, z = jnp.split(xz, 2, axis=-1)
    xb = xb.astype(jnp.float32)
    conv_in = None if state is None else state[1]
    xc, conv_state = _mamba_conv(p, xb, conv_in)
    dt = jax.nn.softplus(xc @ p["w_dt"] + p["b_dt"])     # (B,T,di)
    BC = xc @ p["w_bc"]
    Bm, Cm = jnp.split(BC, 2, axis=-1)                   # (B,T,N)
    A = -jnp.exp(p["a_log"])                             # (di,N)
    h0 = jnp.zeros((B, di, N), jnp.float32) if state is None else state[0]

    y, h_last = ops.mamba_scan(dt, xc, Bm, Cm, A, h0, chunk=cfg.ssm_chunk)
    y = y + p["d_skip"] * xc
    y = down_proj(y.astype(x.dtype) * jax.nn.silu(z), p["w_out"])
    return y, (h_last, conv_state)


def mamba_step(p, x, state, cfg):
    """x: (B,1,d); state: (ssm_state, conv_state)."""
    B = x.shape[0]
    di = cfg.ssm_expand * cfg.d_model
    xz = x @ p["w_in"]
    xb, z = jnp.split(xz, 2, axis=-1)
    xb = xb.astype(jnp.float32)
    h0, conv_state = state
    xc, conv_state = _mamba_conv(p, xb, conv_state)
    dt = jax.nn.softplus(xc @ p["w_dt"] + p["b_dt"])
    Bm, Cm = jnp.split(xc @ p["w_bc"], 2, axis=-1)
    A = -jnp.exp(p["a_log"])
    a = jnp.exp(dt[:, 0, :, None] * A)                   # (B,di,N)
    h = h0 * a + (dt[:, 0] * xc[:, 0])[..., None] * Bm[:, 0, None, :]
    y = jnp.einsum("bdn,bn->bd", h, Cm[:, 0]) + p["d_skip"] * xc[:, 0]
    y = down_proj(y[:, None].astype(x.dtype) * jax.nn.silu(z), p["w_out"])
    return y, (h, conv_state)
