"""Shared model layers: norms, RoPE, GQA attention (windowed / prefix-LM /
cross), SwiGLU MLP, embeddings. Pure functions over param dicts.

Conventions:
  * activations ``(B, T, D)``; attention heads ``(B, T, H, hd)``.
  * params are plain dict pytrees; every init_* takes a PRNGKey.
  * caches: dict with 'k','v' of shape (B, S_cache, KV, hd) plus 'pos'
    (stored absolute positions (S_cache,) int32, -1 = empty slot). Windowed
    layers use S_cache == window (ring buffer), global layers S_cache == max.
"""
from __future__ import annotations

import dataclasses
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np

__all__ = [
    "rms_norm",
    "init_rms_norm",
    "rope",
    "init_dense",
    "dense",
    "init_attention",
    "attention",
    "init_attn_cache",
    "init_mlp",
    "mlp",
    "init_embedding",
    "embed_tokens",
    "unembed",
    "cross_entropy_loss",
]


def _norm_init(key, shape, scale=1.0, dtype=jnp.float32):
    return (jax.random.normal(key, shape, dtype=jnp.float32) * scale).astype(dtype)


# ---------------------------------------------------------------------------
# norm / rope
# ---------------------------------------------------------------------------


def init_rms_norm(d: int):
    return {"scale": jnp.ones((d,), jnp.float32)}


def rms_norm(p, x, eps: float = 1e-6):
    dt = x.dtype
    xf = x.astype(jnp.float32)
    var = jnp.mean(xf * xf, axis=-1, keepdims=True)
    # normalize in f32, but apply the scale in the COMPUTE dtype: an f32
    # scale promotes every backward cotangent of the residual stream to f32,
    # doubling the bytes of each TP activation collective (measured on
    # gemma3 train_4k: 459 GiB/device of f32 all-gathers — §Perf pair 2).
    out = (xf * jax.lax.rsqrt(var + eps)).astype(dt)
    return out * p["scale"].astype(dt)


def rope(x: jax.Array, positions: jax.Array, theta: float = 10000.0) -> jax.Array:
    """Rotary embedding. x: (..., T, H, hd); positions: broadcastable to (..., T)."""
    hd = x.shape[-1]
    half = hd // 2
    freqs = jnp.exp(-np.log(theta) * jnp.arange(half, dtype=jnp.float32) / half)
    ang = positions[..., None].astype(jnp.float32) * freqs  # (..., T, half)
    cos = jnp.cos(ang)[..., None, :]  # (..., T, 1, half)
    sin = jnp.sin(ang)[..., None, :]
    x1, x2 = x[..., :half].astype(jnp.float32), x[..., half:].astype(jnp.float32)
    out = jnp.concatenate([x1 * cos - x2 * sin, x1 * sin + x2 * cos], axis=-1)
    return out.astype(x.dtype)


# ---------------------------------------------------------------------------
# dense
# ---------------------------------------------------------------------------


def init_dense(key, d_in: int, d_out: int, bias: bool = False, dtype=jnp.bfloat16):
    p = {"w": _norm_init(key, (d_in, d_out), scale=d_in**-0.5, dtype=dtype)}
    if bias:
        p["b"] = jnp.zeros((d_out,), dtype)
    return p


def dense(p, x):
    y = x @ p["w"]
    if "b" in p:
        y = y + p["b"]
    return y


# ---------------------------------------------------------------------------
# attention
# ---------------------------------------------------------------------------


@dataclasses.dataclass(frozen=True)
class AttnSpec:
    num_heads: int
    num_kv_heads: int
    head_dim: int
    qkv_bias: bool = False
    rope_theta: float = 10000.0
    window: Optional[int] = None     # sliding window (None = global)
    causal: bool = True              # False for encoder / cross attention
    use_rope: bool = True


def init_attention(key, d: int, spec: AttnSpec, dtype=jnp.bfloat16):
    ks = jax.random.split(key, 4)
    H, KV, hd = spec.num_heads, spec.num_kv_heads, spec.head_dim
    s = d**-0.5
    p = {
        "wq": _norm_init(ks[0], (d, H, hd), s, dtype),
        "wk": _norm_init(ks[1], (d, KV, hd), s, dtype),
        "wv": _norm_init(ks[2], (d, KV, hd), s, dtype),
        "wo": _norm_init(ks[3], (H, hd, d), (H * hd) ** -0.5, dtype),
    }
    if spec.qkv_bias:
        p["bq"] = jnp.zeros((H, hd), dtype)
        p["bk"] = jnp.zeros((KV, hd), dtype)
        p["bv"] = jnp.zeros((KV, hd), dtype)
    return p


def init_attn_cache(batch: int, max_len: int, spec: AttnSpec, dtype=jnp.bfloat16):
    """Cache for one attention layer. Windowed layers keep a ring buffer."""
    S = min(max_len, spec.window) if spec.window else max_len
    KV, hd = spec.num_kv_heads, spec.head_dim
    return {
        "k": jnp.zeros((batch, S, KV, hd), dtype),
        "v": jnp.zeros((batch, S, KV, hd), dtype),
        "pos": jnp.full((S,), -1, jnp.int32),  # absolute position per slot
    }


def _qkv(p, spec: AttnSpec, x, kv_input=None):
    kv_input = x if kv_input is None else kv_input
    q = jnp.einsum("btd,dhk->bthk", x, p["wq"])
    k = jnp.einsum("bsd,dhk->bshk", kv_input, p["wk"])
    v = jnp.einsum("bsd,dhk->bshk", kv_input, p["wv"])
    if spec.qkv_bias:
        q, k, v = q + p["bq"], k + p["bk"], v + p["bv"]
    return q, k, v


def _out_proj(out, wo):
    """(B,T,H,hd) x (H,hd,d) -> (B,T,d) with compute-dtype accumulation
    declaration (see down_proj)."""
    B, T, H, hd = out.shape
    return down_proj(out.reshape(B, T, H * hd), wo.reshape(H * hd, -1))


def _sdpa(q, k, v, mask, spec: AttnSpec):
    """q: (B,T,H,hd); k: (B,S,KV,hd); v: (B,S,KV,hd_v); mask broadcastable
    to (B,T,S)."""
    B, T, H, hd = q.shape
    KV = k.shape[2]
    G = H // KV
    qg = q.reshape(B, T, KV, G, hd)
    k = k.astype(q.dtype)
    v = v.astype(q.dtype)
    scores = jnp.einsum("btkgh,bskh->bkgts", qg, k).astype(jnp.float32)
    scores = scores * (hd**-0.5)
    scores = jnp.where(mask[:, None, None, :, :], scores, -1e30)
    w = jax.nn.softmax(scores, axis=-1)
    out = jnp.einsum("bkgts,bskh->btkgh", w.astype(v.dtype), v)
    return out.reshape(B, T, H, v.shape[-1])


# S at which train/prefill attention switches to the memory-efficient
# KV-block-scanned softmax (full T x S scores never materialize). The TPU
# production path is the Pallas flash kernel (repro.kernels.flash_attention);
# this is the XLA-portable equivalent with identical math.
CHUNKED_ATTN_MIN_S = 4096
_CHUNK_BLOCK = 1024


def _mask_block(spec: AttnSpec, prefix_len: int, i, j):
    """Boolean mask for query positions i (T,) x key positions j (block,)."""
    ii, jj = i[:, None], j[None, :]
    if spec.causal:
        m = jj <= ii
        if prefix_len:
            m = m | (jj < prefix_len)
    else:
        m = jnp.ones((ii.shape[0], jj.shape[1]), bool)
    if spec.window is not None:
        m = m & (jj > ii - spec.window)
        if prefix_len:
            m = m | ((jj < prefix_len) & (ii < prefix_len))
    return m


def _chunked_sdpa(q, k, v, spec: AttnSpec, prefix_len: int, block: int = _CHUNK_BLOCK):
    """Flash-style attention: scan over KV blocks with running (max, sum).

    Peak memory is O(B*T*H*block) instead of O(B*T*H*S).
    """
    B, T, H, hd = q.shape
    S, KV = k.shape[1], k.shape[2]
    G = H // KV
    block = min(block, S)
    assert S % block == 0, (S, block)
    nb = S // block
    qg = (q.reshape(B, T, KV, G, hd).astype(jnp.float32)) * (hd**-0.5)
    kb = jnp.moveaxis(k.reshape(B, nb, block, KV, hd), 1, 0)
    vb = jnp.moveaxis(v.reshape(B, nb, block, KV, hd), 1, 0)
    i = jnp.arange(T)

    def body(carry, xs):
        o, m, l = carry
        kblk, vblk, j0 = xs
        j = j0 + jnp.arange(block)
        mask = _mask_block(spec, prefix_len, i, j)  # (T, block)
        s = jnp.einsum("btkgh,bskh->bkgts", qg, kblk.astype(jnp.float32))
        s = jnp.where(mask[None, None, None], s, -1e30)
        m_new = jnp.maximum(m, jnp.max(s, axis=-1))
        p = jnp.exp(s - m_new[..., None])
        corr = jnp.exp(m - m_new)
        l = l * corr + jnp.sum(p, axis=-1)
        o = o * corr[..., None] + jnp.einsum(
            "bkgts,bskh->bkgth", p, vblk.astype(jnp.float32)
        )
        return (o, m_new, l), None

    o0 = jnp.zeros((B, KV, G, T, v.shape[-1]), jnp.float32)
    m0 = jnp.full((B, KV, G, T), -jnp.inf, jnp.float32)
    l0 = jnp.zeros((B, KV, G, T), jnp.float32)
    (o, m, l), _ = jax.lax.scan(
        body, (o0, m0, l0), (kb, vb, jnp.arange(nb) * block)
    )
    out = o / jnp.maximum(l[..., None], 1e-30)
    out = jnp.moveaxis(out, 3, 1).reshape(B, T, H, v.shape[-1])
    return out.astype(q.dtype)


def full_mask(T: int, spec: AttnSpec, prefix_len: int = 0):
    """(1, T, S=T) mask for train/prefill."""
    i = jnp.arange(T)[:, None]
    j = jnp.arange(T)[None, :]
    if spec.causal:
        m = j <= i
        if prefix_len:
            m = m | (j < prefix_len)
    else:
        m = jnp.ones((T, T), bool)
    if spec.window is not None:
        m = m & (j > i - spec.window)
        if prefix_len:
            m = m | ((j < prefix_len) & (i < prefix_len))
    return m[None]


def attention(
    p,
    x,
    spec: AttnSpec,
    *,
    mode: str = "train",           # train | prefill | decode
    positions: jax.Array | None = None,
    prefix_len: int = 0,
    cache: dict | None = None,
    cur_pos: jax.Array | None = None,   # scalar int32: index of the new token
    cross_kv: tuple | None = None,      # (k, v, valid_len) for cross attention
):
    """Returns (out, new_cache). new_cache is None unless prefill/decode."""
    B, T, D = x.shape

    # ---- cross attention (whisper decoder): kv precomputed, no cache update
    if cross_kv is not None:
        k, v = cross_kv
        q = jnp.einsum("btd,dhk->bthk", x, p["wq"])
        if spec.qkv_bias:
            q = q + p["bq"]
        mask = jnp.ones((1, T, k.shape[1]), bool)
        out = _sdpa(q, k, v, mask, spec)
        return _out_proj(out, p["wo"]), None

    if mode in ("train", "prefill"):
        if positions is None:
            positions = jnp.arange(T)[None, :]
        q, k, v = _qkv(p, spec, x)
        if spec.use_rope:
            q = rope(q, positions, spec.rope_theta)
            k = rope(k, positions, spec.rope_theta)
        if k.shape[1] >= CHUNKED_ATTN_MIN_S:
            out = _chunked_sdpa(q, k, v, spec, prefix_len)
        else:
            mask = full_mask(T, spec, prefix_len)
            out = _sdpa(q, k, v, mask, spec)
        y = _out_proj(out, p["wo"])
        new_cache = None
        if mode == "prefill":
            new_cache = _fill_cache(k, v, spec, T)
        return y, new_cache

    # ---- decode: T == 1, append to cache ----
    assert mode == "decode" and cache is not None and cur_pos is not None
    q, k_new, v_new = _qkv(p, spec, x)
    pos_b = jnp.broadcast_to(cur_pos, (B, 1))
    if spec.use_rope:
        q = rope(q, pos_b, spec.rope_theta)
        k_new = rope(k_new, pos_b, spec.rope_theta)
    S = cache["k"].shape[1]
    slot = (cur_pos % S).astype(jnp.int32)
    k = jax.lax.dynamic_update_slice(cache["k"], k_new.astype(cache["k"].dtype), (0, slot, 0, 0))
    v = jax.lax.dynamic_update_slice(cache["v"], v_new.astype(cache["v"].dtype), (0, slot, 0, 0))
    pos = jax.lax.dynamic_update_slice(cache["pos"], cur_pos[None].astype(jnp.int32), (slot,))
    valid = pos >= 0
    if spec.window is not None:
        valid = valid & (pos > cur_pos - spec.window)
    mask = valid[None, None, :]  # (1, 1, S)
    if k.dtype != q.dtype and S >= 8192:
        # Quantized (f8) cache: _sdpa's cast would materialize a full
        # compute-dtype shadow of the cache (qwen1.5-32b: +20 GiB/device).
        # Heads are independent, so process KV-head blocks in sequence —
        # the KV dim is unsharded for these archs (the cache seq dim holds
        # the 'model' axis), so slicing it inserts NO collectives. (A seq-dim
        # blocked scan all-gathered the sharded cache: 0.85 ms -> 1.3 s on
        # minitron — see EXPERIMENTS.md §Perf.)
        out = _decode_sdpa_headblocked(q, k, v, mask, spec)
    else:
        out = _sdpa(q, k, v, mask, spec)
    y = _out_proj(out, p["wo"])
    return y, {"k": k, "v": v, "pos": pos}


def _decode_sdpa_headblocked(q, k, v, mask, spec: AttnSpec, heads_per_block: int = 8):
    """q: (B,1,H,hd); k/v: (B,S,KV,hd) in a narrower cache dtype.

    Static loop over KV-head blocks: heads are independent under softmax,
    so each block runs a full (small) _sdpa; only one block of the cache is
    ever cast to the compute dtype."""
    B, T, H, hd = q.shape
    S, KV = k.shape[1], k.shape[2]
    G = H // KV
    hb = min(heads_per_block, KV)
    while KV % hb:
        hb -= 1
    qg = q.reshape(B, T, KV, G, hd)
    outs = []
    for k0 in range(0, KV, hb):
        qb = qg[:, :, k0 : k0 + hb].reshape(B, T, hb * G, hd)
        kb = k[:, :, k0 : k0 + hb].astype(q.dtype)
        vb = v[:, :, k0 : k0 + hb].astype(q.dtype)
        outs.append(_sdpa(qb, kb, vb, mask, spec).reshape(B, T, hb, G, hd))
    return jnp.concatenate(outs, axis=2).reshape(B, T, H, hd)


def _fill_cache(k, v, spec: AttnSpec, T: int):
    """Build a decode cache from prefill K/V (keep last `window` for SWA)."""
    if spec.window is not None and T > spec.window:
        W = spec.window
        start = T - W
        k = k[:, start:]
        v = v[:, start:]
        # ring-buffer layout: slot = pos % W
        pos_abs = jnp.arange(start, T)
        slots = pos_abs % W
        order = jnp.argsort(slots)
        k, v = k[:, order], v[:, order]
        pos = jnp.zeros((W,), jnp.int32).at[slots[order]].set(pos_abs[order])
        return {"k": k, "v": v, "pos": pos}
    pos = jnp.arange(k.shape[1], dtype=jnp.int32)
    return {"k": k, "v": v, "pos": pos}


# ---------------------------------------------------------------------------
# MLP
# ---------------------------------------------------------------------------


def init_mlp(key, d: int, f: int, act: str = "silu", dtype=jnp.bfloat16):
    ks = jax.random.split(key, 3)
    p = {
        "w_up": _norm_init(ks[0], (d, f), d**-0.5, dtype),
        "w_down": _norm_init(ks[1], (f, d), f**-0.5, dtype),
    }
    if act in ("silu", "geglu"):
        p["w_gate"] = _norm_init(ks[2], (d, f), d**-0.5, dtype)
    return p


def down_proj(h, w):
    """Contraction-sharded (TP) projection with COMPUTE-dtype output: jax
    emits f32-accumulating dots by default and GSPMD all-reduces the f32
    partials BEFORE the downcast — 2x the wire bytes of every TP psum
    (measured on gemma3 train_4k; EXPERIMENTS.md §Perf pair 2). Declaring
    the output dtype moves the rounding before the collective; the MXU
    still accumulates in f32 on TPU."""
    return jax.lax.dot_general(
        h, w, (((h.ndim - 1,), (0,)), ((), ())), preferred_element_type=h.dtype
    )


def mlp(p, x, act: str = "silu"):
    up = x @ p["w_up"]
    if act == "silu":
        h = jax.nn.silu(x @ p["w_gate"]) * up
    elif act == "geglu":
        h = jax.nn.gelu(x @ p["w_gate"]) * up
    else:
        h = jax.nn.gelu(up)
    return down_proj(h, p["w_down"])


# ---------------------------------------------------------------------------
# embedding / unembedding / loss
# ---------------------------------------------------------------------------


def init_embedding(key, vocab: int, d: int, tie: bool = True, dtype=jnp.bfloat16):
    ks = jax.random.split(key, 2)
    p = {"tokens": _norm_init(ks[0], (vocab, d), d**-0.5, dtype)}
    if not tie:
        p["unembed"] = _norm_init(ks[1], (vocab, d), d**-0.5, dtype)
    return p


def embed_tokens(p, tokens):
    return jnp.take(p["tokens"], tokens, axis=0)


def unembed(p, x):
    table = p.get("unembed", p["tokens"])
    return jnp.einsum("btd,vd->btv", x, table).astype(jnp.float32)


def cross_entropy_loss(logits, labels, mask=None):
    """logits (B,T,V) f32, labels (B,T) int32. Returns mean nll."""
    logz = jax.nn.logsumexp(logits, axis=-1)
    gold = jnp.take_along_axis(logits, labels[..., None], axis=-1)[..., 0]
    nll = logz - gold
    if mask is None:
        return jnp.mean(nll)
    mask = mask.astype(nll.dtype)
    return jnp.sum(nll * mask) / jnp.maximum(jnp.sum(mask), 1.0)
