"""Plain reference of Moonlight-16B-A3B (DeepSeek-V3's architecture), as the
configuration file states it by the published config.json's keys: its
weights from a seed, its loss, and its model FLOPs.

Layer 0 is dense, the rest are mixture-of-experts layers; every layer
attends with multi-head latent attention:

    q = x W_q, split per head into nope and rope parts
    [c_kv, k_rope] = x W_kv_a;  [k_nope, v] = rms_norm(c_kv) W_kv_b per head
    k = [k_nope, k_rope] (k_rope shared by the heads); RoPE on q_rope, k_rope
    softmax(q k^T / sqrt(nope + rope)) v, heads through W_o

RoPE pairs adjacent elements (2i, 2i+1), de-interleaved as the published
modelling code does before rotating halves; the latent's RMSNorm takes that
code's default eps, 1e-6. K and V are materialised per head and the
softmax is dense over the causal keys, in blocks of queries so that it fits.

The router scores all ``router_experts`` experts with a sigmoid and picks
the top ``num_experts_per_tok`` of the scores plus a correction bias; the
weights are the chosen scores, normalised and scaled by
``routed_scaling_factor``. This layer holds ``n_routed_experts`` of them
(the first ones: one chip's share): each held expert runs on every token
and is weighted by its routing weight, zero where it was not chosen, so
choices of experts held elsewhere add nothing. Two shared experts (one MLP
of twice the width) run for every token. The loss adds DeepSeek-V3's
sequence-wise balance loss (``router_aux_coef`` is its alpha). The bias
takes no gradient: ``bias_update`` is the rule the program applies after
each step. The reference step that the benchmark builds
(``common.make_train_step``) carries weights and AdamW state only, so
``loss`` routes with the bias leaf of the weights, which stays at zero.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from .common import F32, Math, cross_entropy, normal, padded_vocab, rms_norm, rope

LATENT_NORM_EPS = 1e-6
QUERY_BLOCK = 256
BIAS_UPDATE_SPEED = 1e-3   # gamma, from the DeepSeek-V3 paper (the config does not give it)


def _dims(cfg):
    return (cfg["hidden_size"], cfg["num_attention_heads"], cfg["kv_lora_rank"],
            cfg["qk_nope_head_dim"], cfg["qk_rope_head_dim"], cfg["v_head_dim"])


def init(cfg, key):
    d, H, r, nope, rp, vd = _dims(cfg)
    V, f, F = padded_vocab(cfg), cfg["moe_intermediate_size"], cfg["intermediate_size"]
    E, n_held, fs = cfg["router_experts"], cfg["n_routed_experts"], f * cfg["n_shared_experts"]
    lead = cfg["first_k_dense_replace"]
    n = cfg["num_hidden_layers"] - lead
    keys = iter(jax.random.split(key, 64))
    dt = jnp.dtype(cfg["dtype"])    # the program's storage dtype

    def w(lead_shape, shape, scale, dtype=dt):
        return normal(next(keys), lead_shape + shape, scale, dtype)

    def ones(lead_shape, width):
        return {"scale": jnp.ones(lead_shape + (width,), F32)}

    def attn(s):
        return {"wq": w(s, (d, H, nope + rp), d ** -0.5), "wkv_a": w(s, (d, r + rp), d ** -0.5),
                "kv_norm": ones(s, r), "wkv_b": w(s, (r, H, nope + vd), r ** -0.5),
                "wo": w(s, (H, vd, d), (H * vd) ** -0.5)}

    def mlp(s, width):
        return {"w_up": w(s, (d, width), d ** -0.5), "w_down": w(s, (width, d), width ** -0.5),
                "w_gate": w(s, (d, width), d ** -0.5)}

    dense = [{"norm1": ones((), d), "attn": attn(()), "norm2": ones((), d), "mlp": mlp((), F)}
             for _ in range(lead)]
    s = (n,)
    moe_block = {
        "norm1": ones(s, d), "attn": attn(s), "norm2": ones(s, d),
        "moe": {"router": w(s, (d, E), d ** -0.5, F32), "router_bias": jnp.zeros(s + (E,), F32),
                "w_gate": w(s, (n_held, d, f), d ** -0.5), "w_up": w(s, (n_held, d, f), d ** -0.5),
                "w_down": w(s, (n_held, f, d), f ** -0.5), "shared": mlp(s, fs)},
    }
    return {
        "embed": {"tokens": normal(next(keys), (V, d), d ** -0.5, dt),
                  "unembed": normal(next(keys), (V, d), d ** -0.5, dt)},
        "decoder": {"blocks": [moe_block], "tail": [], "lead": dense},
        "final_norm": {"scale": jnp.ones((d,), F32)},
    }


def rope_pairs(x, theta):
    """RoPE over adjacent pairs (2i, 2i+1) of x (B, T, H, n), left in the
    de-interleaved order of the published code."""
    *lead, n = x.shape
    return rope(x.reshape(*lead, n // 2, 2).swapaxes(-1, -2).reshape(*lead, n), theta)


def mla(p, x, cfg, mm: Math):
    B, T, _ = x.shape
    d, H, r, nope, rp, vd = _dims(cfg)
    q = mm.einsum("btd,dhk->bthk", x, p["wq"])
    kv_a = mm.einsum("btd,de->bte", x, p["wkv_a"])
    c_kv = rms_norm(kv_a[..., :r], p["kv_norm"]["scale"], LATENT_NORM_EPS)
    kv = mm.einsum("btr,rhk->bthk", c_kv, p["wkv_b"])
    k_rope = rope_pairs(kv_a[..., None, r:], cfg["rope_theta"])
    q = jnp.concatenate([q[..., :nope], rope_pairs(q[..., nope:], cfg["rope_theta"])], axis=-1)
    k = jnp.concatenate([kv[..., :nope], jnp.broadcast_to(k_rope, (B, T, H, rp))], axis=-1)
    v = kv[..., nope:]
    bq = min(QUERY_BLOCK, T)

    @jax.checkpoint
    def queries(args):
        qb, i0 = args
        s = mm.einsum("bqhk,bshk->bhqs", qb, k) * (nope + rp) ** -0.5
        allowed = jnp.arange(T)[None, :] <= i0 + jnp.arange(bq)[:, None]
        s = jnp.where(allowed, s, -jnp.inf)
        return mm.einsum("bhqs,bshk->bqhk", jax.nn.softmax(s, axis=-1), v)

    blocks = jnp.moveaxis(q.reshape(B, T // bq, bq, H, nope + rp), 1, 0)
    out = jax.lax.map(queries, (blocks, jnp.arange(T // bq) * bq))
    out = jnp.moveaxis(out, 0, 1).reshape(B, T, H * vd)
    return mm.einsum("bte,ed->btd", out, p["wo"].reshape(H * vd, d))


def mlp(p, x, mm: Math):
    h = jax.nn.silu(mm.einsum("btd,df->btf", x, p["w_gate"])) * mm.einsum("btd,df->btf", x, p["w_up"])
    return mm.einsum("btf,fd->btd", h, p["w_down"])


def route(p, x, cfg, mm: Math):
    """(weights over all router experts (B, T, E), zero where not chosen;
    per-expert load (E,); sequence-wise balance loss)."""
    E, k = cfg["router_experts"], cfg["num_experts_per_tok"]
    scores = jax.nn.sigmoid(mm.einsum("btd,de->bte", x, p["router"]))
    _, idx = jax.lax.top_k(scores + jax.lax.stop_gradient(p["router_bias"]), k)
    w = jnp.take_along_axis(scores, idx, axis=-1)
    if cfg["norm_topk_prob"]:
        w = w / (jnp.sum(w, axis=-1, keepdims=True) + 1e-20)
    w = w * cfg["routed_scaling_factor"]
    onehot = jax.nn.one_hot(idx, E, dtype=F32)                       # (B, T, k, E)
    weights = jnp.einsum("btk,btke->bte", w, onehot)
    chosen = jnp.sum(onehot, axis=2)
    f = jnp.sum(chosen, axis=1) * (E / (k * x.shape[1]))
    P = jnp.mean(scores / jnp.sum(scores, axis=-1, keepdims=True), axis=1)
    aux = cfg["router_aux_coef"] * jnp.mean(jnp.sum(f * P, axis=-1)) if cfg["seq_aux"] else 0.0
    return weights, jnp.sum(chosen, axis=(0, 1)), aux


def moe(p, x, cfg, mm: Math):
    """The held experts' part of the routed result, plus the shared
    experts; the balance loss and the per-expert load."""
    weights, load, aux = route(p, x, cfg, mm)
    held = weights[..., : cfg["n_routed_experts"]]
    h = jax.nn.silu(mm.einsum("btd,edf->btef", x, p["w_gate"])) * mm.einsum("btd,edf->btef", x, p["w_up"])
    y = mm.einsum("btef,efd->btd", h * held[..., None], p["w_down"])   # sum_e w_e (h_e W_down,e)
    return y + mlp(p["shared"], x, mm), aux, load


def bias_update(bias, load):
    """DeepSeek-V3's auxiliary-loss-free balancing, after each step:
    b_i += gamma * sign(mean load - load_i)."""
    return bias + BIAS_UPDATE_SPEED * jnp.sign(jnp.mean(load) - load)


def loss(params, tokens, labels, cfg, mm: Math):
    eps = cfg["rms_norm_eps"]
    x = jnp.take(params["embed"]["tokens"], tokens, axis=0).astype(F32)

    def attend(x, p):
        return x + mla(p["attn"], rms_norm(x, p["norm1"]["scale"], eps), cfg, mm)

    @jax.checkpoint
    def dense_layer(x, p):
        x = attend(x, p)
        return x + mlp(p["mlp"], rms_norm(x, p["norm2"]["scale"], eps), mm)

    @jax.checkpoint
    def moe_layer(x, p):
        x = attend(x, p)
        y, aux, _ = moe(p["moe"], rms_norm(x, p["norm2"]["scale"], eps), cfg, mm)
        return x + y, aux

    for p in params["decoder"]["lead"]:
        x = dense_layer(x, p)
    x, aux = jax.lax.scan(moe_layer, x, params["decoder"]["blocks"][0])
    x = rms_norm(x, params["final_norm"]["scale"], eps)
    return cross_entropy(mm.einsum("btd,vd->btv", x, params["embed"]["unembed"]), labels) + jnp.sum(aux)


def train_flops_per_token(cfg, seq: int, n_params: int) -> float:
    """Model FLOPs of one training token (3x the forward), 2 per multiply-add
    of the weights a token uses: the unembedding, each layer's MLA
    projections, the dense layer's MLP, and in each MoE layer the router,
    the shared experts and the routed experts held here at uniform load,
    num_experts_per_tok * n_routed_experts / router_experts expert
    evaluations a token (6 x 8/64 = 0.75 in the cell); plus attention's
    q.k over nope + rope dimensions and weights.v over v_head_dim for the
    (seq + 1) / 2 causal keys of a query. The embedding lookup and
    recomputation are not counted (``n_params`` is not used: most held
    experts sit idle for a given token)."""
    d, H, r, nope, rp, vd = _dims(cfg)
    f, lead = cfg["moe_intermediate_size"], cfg["first_k_dense_replace"]
    n_moe = cfg["num_hidden_layers"] - lead
    E, k = cfg["router_experts"], cfg["num_experts_per_tok"]
    attn = d * H * (nope + rp) + d * (r + rp) + r * H * (nope + vd) + H * vd * d
    dense = 3 * d * cfg["intermediate_size"]
    routed = k * cfg["n_routed_experts"] / E * 3 * d * f
    moe_layer = d * E + 3 * d * f * cfg["n_shared_experts"] + routed
    macs = padded_vocab(cfg) * d + cfg["num_hidden_layers"] * attn + lead * dense + n_moe * moe_layer
    keys = (seq + 1) / 2
    scores = cfg["num_hidden_layers"] * 2 * H * (nope + rp + vd) * keys
    return 3.0 * (2.0 * macs + scores)
