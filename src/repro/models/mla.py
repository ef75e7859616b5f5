"""Multi-head latent attention (DeepSeek-V2/V3; Moonlight runs it without
query compression), on the training path.

    q                 = x W_q                      (B, T, H, nope + rope)
    [c_kv, k_rope]    = x W_kv_a                   (B, T, r + rope)
    [k_nope, v]       = rms_norm(c_kv) W_kv_b      (B, T, H, nope + v)
    k                 = [k_nope, k_rope]           k_rope shared by every head
    out               = softmax(q k^T / sqrt(nope + rope)) v, heads W_o -> d

RoPE runs on ``q_rope`` and the shared ``k_rope`` only. Pair convention:
the published modelling code (``apply_rotary_pos_emb`` of DeepSeek-V3's
``modeling_deepseek.py``) first de-interleaves the rope part,
``view(d/2, 2).transpose``, so the rotated pairs are adjacent elements
(2i, 2i+1) at frequency theta^(-2i/rope); ``rope_pairs`` does the same.
The latent's RMSNorm takes that code's default eps, 1e-6; the config's
``rms_norm_eps`` is for the layer norms.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from .layers import (
    CHUNKED_ATTN_MIN_S,
    AttnSpec,
    _chunked_sdpa,
    _norm_init,
    _sdpa,
    down_proj,
    full_mask,
    rms_norm,
    rope,
)

__all__ = ["init_mla", "mla_attention", "rope_pairs", "LATENT_NORM_EPS"]

LATENT_NORM_EPS = 1e-6


def init_mla(key, cfg, dtype=jnp.bfloat16):
    d, H, r = cfg.d_model, cfg.num_heads, cfg.kv_lora_rank
    nope, rp, vd = cfg.qk_nope_head_dim, cfg.qk_rope_head_dim, cfg.v_head_dim
    ks = jax.random.split(key, 4)
    return {
        "wq": _norm_init(ks[0], (d, H, nope + rp), d**-0.5, dtype),
        "wkv_a": _norm_init(ks[1], (d, r + rp), d**-0.5, dtype),
        "kv_norm": {"scale": jnp.ones((r,), jnp.float32)},
        "wkv_b": _norm_init(ks[2], (r, H, nope + vd), r**-0.5, dtype),
        "wo": _norm_init(ks[3], (H, vd, d), (H * vd) ** -0.5, dtype),
    }


def rope_pairs(x, positions, theta: float):
    """RoPE over adjacent pairs, in the de-interleaved order the published
    code leaves them in. x: (..., T, H, rope)."""
    *lead, n = x.shape
    x = x.reshape(*lead, n // 2, 2).swapaxes(-1, -2).reshape(*lead, n)
    return rope(x, positions, theta)


def mla_attention(p, x, cfg, positions=None):
    """Causal MLA over x (B, T, d) -> (B, T, d)."""
    B, T, _ = x.shape
    H, r, nope = cfg.num_heads, cfg.kv_lora_rank, cfg.qk_nope_head_dim
    if positions is None:
        positions = jnp.arange(T)[None, :]
    q = jnp.einsum("btd,dhk->bthk", x, p["wq"])
    q_nope, q_rope = q[..., :nope], q[..., nope:]
    kv_a = x @ p["wkv_a"]
    c_kv, k_rope = kv_a[..., :r], kv_a[..., None, r:]
    kv = jnp.einsum("btr,rhk->bthk", rms_norm(p["kv_norm"], c_kv, LATENT_NORM_EPS), p["wkv_b"])
    k_nope, v = kv[..., :nope], kv[..., nope:]
    q_rope = rope_pairs(q_rope, positions, cfg.rope_theta)
    k_rope = rope_pairs(k_rope, positions, cfg.rope_theta)
    q = jnp.concatenate([q_nope, q_rope], axis=-1)
    k = jnp.concatenate([k_nope, jnp.broadcast_to(k_rope, k_nope.shape[:-1] + k_rope.shape[-1:])], axis=-1)
    out = _attend(q, k, v)
    return down_proj(out.reshape(B, T, -1), p["wo"].reshape(-1, p["wo"].shape[-1]))


def _attend(q, k, v):
    """Causal softmax attention, scale (q's head dim)^-0.5; v may be
    narrower than q and k. The splash attention kernel on a TPU, else the
    XLA paths that every attention layer shares."""
    from ..kernels import interpret, ops

    _, T, H, hd = q.shape
    if interpret.on_tpu():
        return ops.causal_attention(q, k, v)
    spec = AttnSpec(num_heads=H, num_kv_heads=H, head_dim=hd)
    if T >= CHUNKED_ATTN_MIN_S:
        return _chunked_sdpa(q, k, v, spec, 0)
    return _sdpa(q, k, v, full_mask(T, spec), spec)
