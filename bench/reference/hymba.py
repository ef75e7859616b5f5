"""Plain reference of the Hymba language model as this repo states it
(arXiv:2411.13676, with the repo's departures listed in the configuration
file): its weights from a seed, its loss, and its model FLOPs.

Each layer runs grouped-query attention (rotary, sliding window) and a
Mamba (S6) mixer side by side on the same normed input, mixes them with two
learned scalars, and adds a SwiGLU MLP. Departures from the paper that the
program makes and this reference follows: every layer is windowed (no
global layers), no meta tokens, no shared KV cache across layers, and the
Mamba dt projection is full rank.

Attention is a dense masked softmax and the Mamba recurrence a plain scan
over time: neither is the program's chunked algorithm.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from .common import F32, Math, cross_entropy, normal, padded_vocab, rms_norm, rope

BF16 = jnp.bfloat16


def _heads(cfg):
    H, KV = cfg["num_heads"], cfg["num_kv_heads"]
    hd = cfg.get("head_dim") or cfg["d_model"] // H
    return H, KV, hd


def init(cfg, key):
    d, f, V, n = cfg["d_model"], cfg["d_ff"], padded_vocab(cfg), cfg["num_layers"]
    H, KV, hd = _heads(cfg)
    di, N, W = cfg["ssm_expand"] * d, cfg["ssm_state"], cfg["ssm_conv"]
    if tuple(cfg["block_pattern"]) != ("hybrid",) or len(cfg["attn_pattern"]) != 1:
        raise ValueError("the hymba reference runs one hybrid layer kind with one window")
    keys = iter(jax.random.split(key, 16))

    def w(shape, scale, dtype=BF16):
        return normal(next(keys), (n,) + shape, scale, dtype)

    a_log = jnp.log(jnp.tile(jnp.arange(1, N + 1, dtype=F32), (di, 1)))
    block = {
        "norm1": {"scale": jnp.ones((n, d), F32)},
        "attn": {
            "wq": w((d, H, hd), d ** -0.5), "wk": w((d, KV, hd), d ** -0.5),
            "wv": w((d, KV, hd), d ** -0.5), "wo": w((H, hd, d), (H * hd) ** -0.5),
        },
        "ssm": {
            "w_in": w((d, 2 * di), d ** -0.5),
            "conv": w((W, di), 0.5, F32),
            "w_bc": w((di, 2 * N), di ** -0.5, F32),
            "w_dt": w((di, di), di ** -0.5, F32),
            "b_dt": jnp.full((n, di), -4.0, F32),
            "a_log": jnp.broadcast_to(a_log, (n, di, N)),
            "d_skip": jnp.ones((n, di), F32),
            "w_out": w((di, d), di ** -0.5),
        },
        "mix_a": jnp.ones((n,), F32),
        "mix_m": jnp.ones((n,), F32),
        "norm2": {"scale": jnp.ones((n, d), F32)},
        "mlp": {"w_up": w((d, f), d ** -0.5), "w_down": w((f, d), f ** -0.5),
                "w_gate": w((d, f), d ** -0.5)},
    }
    return {
        "embed": {"tokens": normal(next(keys), (V, d), d ** -0.5, BF16)},
        "decoder": {"blocks": [block], "tail": []},
        "final_norm": {"scale": jnp.ones((d,), F32)},
    }


def attention(p, x, cfg, mm: Math):
    B, T, _ = x.shape
    H, KV, hd = _heads(cfg)
    window = cfg["attn_pattern"][0]
    q = rope(mm.einsum("btd,dhk->bthk", x, p["wq"]), cfg["rope_theta"])
    k = rope(mm.einsum("btd,dhk->bthk", x, p["wk"]), cfg["rope_theta"])
    v = mm.einsum("btd,dhk->bthk", x, p["wv"])
    q = q.reshape(B, T, KV, H // KV, hd)
    s = mm.einsum("btkgh,bskh->bkgts", q, k) * hd ** -0.5
    i, j = jnp.arange(T)[:, None], jnp.arange(T)[None, :]
    allowed = j <= i
    if window is not None:
        allowed = allowed & (j > i - window)
    s = jnp.where(allowed, s, -jnp.inf)
    out = mm.einsum("bkgts,bskh->btkgh", jax.nn.softmax(s, axis=-1), v)
    return mm.einsum("bte,ed->btd", out.reshape(B, T, H * hd), p["wo"].reshape(H * hd, -1))


def mamba(p, x, cfg, mm: Math):
    B, T, _ = x.shape
    W = cfg["ssm_conv"]
    xb, z = jnp.split(mm.einsum("btd,de->bte", x, p["w_in"]), 2, axis=-1)
    padded = jnp.pad(xb, ((0, 0), (W - 1, 0), (0, 0)))
    xc = jax.nn.silu(sum(padded[:, i:i + T] * p["conv"][i] for i in range(W)))
    dt = jax.nn.softplus(mm.einsum("bte,ef->btf", xc, p["w_dt"]) + p["b_dt"])
    b_in, c_out = jnp.split(mm.einsum("bte,en->btn", xc, p["w_bc"]), 2, axis=-1)
    A = -jnp.exp(p["a_log"])                                            # (di, N)

    def step(h, xs):
        dt_t, x_t, b_t, c_t = xs                                        # (B,di),(B,di),(B,N),(B,N)
        h = jnp.exp(dt_t[..., None] * A) * h + (dt_t * x_t)[..., None] * b_t[:, None, :]
        return h, mm.einsum("ben,bn->be", h, c_t)

    h0 = jnp.zeros((B,) + A.shape, F32)
    xs = tuple(jnp.moveaxis(a, 1, 0) for a in (dt, xc, b_in, c_out))
    _, y = jax.lax.scan(step, h0, xs, unroll=8)
    y = jnp.moveaxis(y, 0, 1) + p["d_skip"] * xc
    return mm.einsum("bte,ed->btd", y * jax.nn.silu(z), p["w_out"])


def mlp(p, x, mm: Math):
    h = jax.nn.silu(mm.einsum("btd,df->btf", x, p["w_gate"])) * mm.einsum("btd,df->btf", x, p["w_up"])
    return mm.einsum("btf,fd->btd", h, p["w_down"])


def loss(params, tokens, labels, cfg, mm: Math):
    d, eps = cfg["d_model"], cfg["norm_eps"]
    emb = params["embed"]["tokens"]
    x = jnp.take(emb, tokens, axis=0).astype(F32) * d ** 0.5

    @jax.checkpoint
    def layer(x, p):
        h = rms_norm(x, p["norm1"]["scale"], eps)
        x = x + p["mix_a"] * attention(p["attn"], h, cfg, mm) + p["mix_m"] * mamba(p["ssm"], h, cfg, mm)
        return x + mlp(p["mlp"], rms_norm(x, p["norm2"]["scale"], eps), mm), None

    x, _ = jax.lax.scan(layer, x, params["decoder"]["blocks"][0])
    x = rms_norm(x, params["final_norm"]["scale"], eps)
    return cross_entropy(mm.einsum("btd,vd->btv", x, emb), labels)


def attended_keys(seq: int, window) -> float:
    """Mean number of keys a query attends to under a causal window."""
    w = seq if window is None else min(window, seq)
    full = seq - w
    return (w * (w + 1) / 2 + full * w) / seq


def train_flops_per_token(cfg, seq: int, n_params: int) -> float:
    """Model FLOPs of one training token (3x the forward): 2 per parameter,
    plus attention's q.k and weights.v over the keys each query attends to
    under the causal window (4 H hd keys), plus the selective scan's state
    update and read-out (4 di N). Recomputation is not counted."""
    H, _, hd = _heads(cfg)
    di, N = cfg["ssm_expand"] * cfg["d_model"], cfg["ssm_state"]
    per_layer = 4 * H * hd * attended_keys(seq, cfg["attn_pattern"][0]) + 4 * di * N
    return 3.0 * (2.0 * n_params + cfg["num_layers"] * per_layer)
