"""Plain reference of the xLSTM language model as this repo states it
(arXiv:2405.04517, with the repo's departures listed in the configuration
file): its weights from a seed, its loss, and its model FLOPs.

Departures from the paper that the program makes and this reference
follows: log-sigmoid input and forget gates (no exponential gating and no
stabilizer state), the normalizer ``|n . q| + 1``, sLSTM blocks without the
gated MLP, and no causal convolution in front of either cell.

The mLSTM is computed in its parallel (quadratic) form over the whole
sequence and the sLSTM as a plain scan over time: neither is the chunkwise
algorithm the program uses.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from .common import F32, Math, cross_entropy, normal, padded_vocab, rms_norm

BF16 = jnp.bfloat16


def _layout(cfg):
    pattern = cfg["block_pattern"]
    if cfg["num_layers"] % len(pattern):
        raise ValueError("the xlstm reference runs whole periods of the block pattern only")
    return pattern, cfg["num_layers"] // len(pattern)


def init(cfg, key):
    """Weights of the dtypes the program stores: bf16 projections, float32
    gates, recurrences and norms."""
    d, H, V = cfg["d_model"], cfg["num_heads"], padded_vocab(cfg)
    di = cfg["ssm_expand"] * d
    hd_s = d // H
    pattern, n = _layout(cfg)
    keys = iter(jax.random.split(key, 16 * len(pattern) + 2))

    def w(shape, fan_in, dtype=BF16):
        return normal(next(keys), (n,) + shape, fan_in ** -0.5, dtype)

    blocks = []
    for kind in pattern:
        p = {"norm1": {"scale": jnp.ones((n, d), F32)}}
        if kind == "mlstm":
            p["ssm"] = {
                "wq": w((d, di), d), "wk": w((d, di), d), "wv": w((d, di), d),
                "wg": w((d, di), d),
                "wi": w((d, H), d, F32), "wf": w((d, H), d, F32),
                "bf": jnp.full((n, H), 2.0, F32),
                "wo": w((di, d), di),
            }
        elif kind == "slstm":
            gate_bias = jnp.concatenate([jnp.zeros((2 * d,)), jnp.full((d,), 2.0), jnp.zeros((d,))])
            p["ssm"] = {
                "w": w((d, 4 * d), d, F32),
                "r": w((H, hd_s, 4 * hd_s), hd_s, F32),
                "b": jnp.broadcast_to(gate_bias, (n, 4 * d)).astype(F32),
                "wo_r": w((d, d), d),
            }
        else:
            raise ValueError(f"xlstm reference has no block {kind!r}")
        blocks.append(p)
    return {
        "embed": {"tokens": normal(next(keys), (V, d), d ** -0.5, BF16)},
        "decoder": {"blocks": blocks, "tail": []},
        "final_norm": {"scale": jnp.ones((d,), F32)},
    }


def mlstm(p, x, cfg, mm: Math):
    B, T, d = x.shape
    H = cfg["num_heads"]
    hd = cfg["ssm_expand"] * d // H
    q = mm.einsum("btd,de->bte", x, p["wq"]).reshape(B, T, H, hd) * hd ** -0.5
    k = mm.einsum("btd,de->bte", x, p["wk"]).reshape(B, T, H, hd) * hd ** -0.5
    v = mm.einsum("btd,de->bte", x, p["wv"]).reshape(B, T, H, hd)
    g = jax.nn.sigmoid(mm.einsum("btd,de->bte", x, p["wg"]))
    lf = jax.nn.log_sigmoid(mm.einsum("btd,dh->bth", x, p["wf"]) + p["bf"])
    li = jax.nn.log_sigmoid(mm.einsum("btd,dh->bth", x, p["wi"]))
    # D[t, s] = exp(F_t - F_s + li_s) for s <= t, F the running sum of lf
    F = jnp.cumsum(lf, axis=1)
    log_d = F[:, :, None, :] - F[:, None, :, :] + li[:, None, :, :]          # (B,T,S,H)
    causal = jnp.tril(jnp.ones((T, T), bool))[None, :, :, None]
    dmat = jnp.where(causal, jnp.exp(jnp.where(causal, log_d, 0.0)), 0.0)
    scores = mm.einsum("bthd,bshd->btsh", q, k) * dmat
    num = mm.einsum("btsh,bshd->bthd", scores, v)
    den = jnp.abs(jnp.sum(scores, axis=2)) + 1.0                              # (B,T,H)
    h = (num / den[..., None]).reshape(B, T, H * hd)
    return mm.einsum("bte,ed->btd", g * h, p["wo"])


def slstm(p, x, cfg, mm: Math):
    B, T, d = x.shape
    H = cfg["num_heads"]
    hd = d // H
    xp = mm.einsum("btd,de->bte", x, p["w"])                                  # (B,T,4d)

    def cell(carry, xt):
        c, n, h = carry
        rec = mm.einsum("bhk,hkm->bhm", h.reshape(B, H, hd), p["r"]).reshape(B, 4 * d)
        z, i, f, o = jnp.split(xt + rec + p["b"], 4, axis=-1)
        i, f, o = jax.nn.sigmoid(i), jax.nn.sigmoid(f), jax.nn.sigmoid(o)
        c = f * c + i * jnp.tanh(z)
        n = f * n + i
        h = o * c / (jnp.abs(n) + 1.0)
        return (c, n, h), h

    zero = jnp.zeros((B, d), F32)
    _, hs = jax.lax.scan(cell, (zero, zero, zero), jnp.moveaxis(xp, 1, 0))
    return mm.einsum("btd,de->bte", jnp.moveaxis(hs, 0, 1), p["wo_r"])


MIXERS = {"mlstm": mlstm, "slstm": slstm}


def loss(params, tokens, labels, cfg, mm: Math):
    d, eps = cfg["d_model"], cfg["norm_eps"]
    pattern, _ = _layout(cfg)
    emb = params["embed"]["tokens"]
    x = jnp.take(emb, tokens, axis=0).astype(F32) * d ** 0.5

    @jax.checkpoint
    def superblock(x, blocks):
        for kind, p in zip(pattern, blocks):
            x = x + MIXERS[kind](p["ssm"], rms_norm(x, p["norm1"]["scale"], eps), cfg, mm)
        return x, None

    x, _ = jax.lax.scan(superblock, x, params["decoder"]["blocks"])
    x = rms_norm(x, params["final_norm"]["scale"], eps)
    logits = mm.einsum("btd,vd->btv", x, emb)
    return cross_entropy(logits, labels)


def train_flops_per_token(cfg, seq: int, n_params: int) -> float:
    """Model FLOPs of one training token (forward and backward, 3x the
    forward): 2 per parameter (each weight multiplies once per token, the
    sLSTM recurrence and the tied unembedding included), plus the mLSTM's
    chunkwise mixing at the configured chunk length: per head, q.k and
    scores.v over the chunk (4 L hd) and the read and update of the
    hd x hd state (4 hd^2). Recomputation is not counted."""
    pattern, n = _layout(cfg)
    H = cfg["num_heads"]
    hd = cfg["ssm_expand"] * cfg["d_model"] // H
    L = min(cfg["ssm_chunk"], seq)
    mix = H * (4 * L * hd + 4 * hd * hd) * n * sum(k == "mlstm" for k in pattern)
    return 3.0 * (2.0 * n_params + mix)
