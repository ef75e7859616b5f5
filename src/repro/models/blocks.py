"""Block assembly: one residual block per `kind`, with a uniform
(init, apply) interface so the transformer stack can scan over
heterogeneous layer patterns (see transformer.py).

Kinds:
  attn    pre-norm GQA attention + MLP            (dense archs)
  moe     pre-norm GQA attention + MoE FFN        (mixtral / qwen3: GShard
          capacity routing; moonshot: dropless DeepSeek-V3 routing over one
          share of the experts, plus shared experts)
  mlstm   matrix-LSTM mixer                       (xLSTM)
  slstm   scalar-LSTM mixer                       (xLSTM)
  hybrid  parallel attention + mamba heads + MLP  (hymba)

Where ``cfg.kv_lora_rank`` is set, ``attn`` and ``moe`` blocks attend with
multi-head latent attention (``mla.py``) instead of GQA.

Caches (prefill/decode) are dict pytrees whose structure depends on kind.
"""
from __future__ import annotations

from typing import Optional

import jax
import jax.numpy as jnp

from . import mla
from . import moe as moe_lib
from . import ssm
from .layers import (
    AttnSpec,
    attention,
    init_attention,
    init_mlp,
    init_rms_norm,
    mlp,
    rms_norm,
)

__all__ = ["attn_spec_for", "init_block", "apply_block", "init_block_cache"]


def attn_spec_for(cfg, window: Optional[int], causal: bool = True) -> AttnSpec:
    return AttnSpec(
        num_heads=cfg.num_heads,
        num_kv_heads=cfg.num_kv_heads,
        head_dim=cfg.head_dim,
        qkv_bias=cfg.qkv_bias,
        rope_theta=cfg.rope_theta,
        window=window,
        causal=causal,
    )


def init_block(key, cfg, kind: str, window: Optional[int], *, cross: bool = False, causal: bool = True,
               dtype=jnp.bfloat16, d_ff: Optional[int] = None):
    """``d_ff`` overrides the MLP width (the leading dense layers')."""
    d = cfg.d_model
    ks = jax.random.split(key, 8)
    spec = attn_spec_for(cfg, window, causal)
    p = {"norm1": init_rms_norm(d)}
    if kind in ("attn", "moe") and cfg.mla:
        p["attn"] = mla.init_mla(ks[0], cfg, dtype)
    elif kind in ("attn", "moe", "hybrid"):
        p["attn"] = init_attention(ks[0], d, spec, dtype)
    if kind == "hybrid":
        p["ssm"] = ssm.init_mamba(ks[1], cfg, dtype)
        p["mix_a"] = jnp.ones((), jnp.float32)
        p["mix_m"] = jnp.ones((), jnp.float32)
    if kind == "mlstm":
        p["ssm"] = ssm.init_mlstm(ks[1], cfg, dtype)
    if kind == "slstm":
        p["ssm"] = ssm.init_slstm(ks[1], cfg, dtype)
    if kind in ("attn", "moe", "hybrid") and cfg.d_ff:
        p["norm2"] = init_rms_norm(d)
        if kind == "moe":
            p["moe"] = moe_lib.init_moe(ks[2], cfg, dtype)
        else:
            p["mlp"] = init_mlp(ks[2], d, d_ff or cfg.d_ff, cfg.act, dtype)
    if kind == "moe" and not cfg.d_ff:
        raise ValueError("moe blocks need d_ff (expert width)")
    if cross:
        p["norm_x"] = init_rms_norm(d)
        p["cross"] = init_attention(ks[3], d, spec, dtype)
    return p


def init_block_cache(cfg, kind: str, window: Optional[int], batch: int, max_len: int, dtype=jnp.bfloat16):
    """Zero cache for one block (used by serving and by decode input_specs)."""
    from .layers import init_attn_cache

    d = cfg.d_model
    di = cfg.ssm_expand * d
    H = cfg.num_heads
    cache = {}
    if kind in ("attn", "moe", "hybrid"):
        import jax.numpy as _jnp

        kv_dt = _jnp.dtype(cfg.kv_cache_dtype)
        cache["attn"] = init_attn_cache(batch, max_len, attn_spec_for(cfg, window), kv_dt)
    if kind == "hybrid":
        N = cfg.ssm_state
        cache["ssm"] = {
            "h": jnp.zeros((batch, di, N), jnp.float32),
            "conv": jnp.zeros((batch, cfg.ssm_conv - 1, di), jnp.float32),
        }
    if kind == "mlstm":
        hd = di // H
        cache["ssm"] = {
            "C": jnp.zeros((batch, H, hd, hd), jnp.float32),
            "n": jnp.zeros((batch, H, hd), jnp.float32),
        }
    if kind == "slstm":
        z = jnp.zeros((batch, d), jnp.float32)
        cache["ssm"] = {"c": z, "n": z, "h": z}
    return cache


def apply_block(
    p,
    x,
    cfg,
    kind: str,
    window: Optional[int],
    *,
    mode: str = "train",
    cache: dict | None = None,
    cur_pos=None,
    max_len: int = 0,
    prefix_len: int = 0,
    positions=None,
    causal: bool = True,
    cross_inputs=None,
    axis_name=None,
):
    """Returns (x, new_cache, aux_loss, expert_load). ``axis_name`` names
    the mesh axis for explicit MoE expert dispatch
    (``cfg.moe_dispatch='alltoallv'``); None keeps the dense einsum
    formulation. ``expert_load`` is the per-expert token count of a
    dropless MoE layer (over the router's experts), else None."""
    spec = attn_spec_for(cfg, window, causal)
    aux = jnp.zeros((), jnp.float32)
    load = None
    new_cache = {}
    h = rms_norm(p["norm1"], x, cfg.norm_eps)

    if kind in ("attn", "moe") and cfg.mla:
        if mode != "train":
            raise NotImplementedError("latent attention has no latent KV cache yet: training only")
        with jax.named_scope("mixer/mla"):
            x = x + mla.mla_attention(p["attn"], h, cfg, positions)
    elif kind in ("attn", "moe", "hybrid"):
        attn_cache = cache.get("attn") if cache else None
        with jax.named_scope("mixer/attn"):
            y, ac = attention(
                p["attn"],
                h,
                spec,
                mode=mode,
                positions=positions,
                prefix_len=prefix_len,
                cache=attn_cache,
                cur_pos=cur_pos,
            )
        if mode == "prefill" and max_len:
            ac = _grow_cache(ac, max_len, spec)
        if ac is not None:
            kv_dt = jnp.dtype(cfg.kv_cache_dtype)
            ac = {**ac, "k": ac["k"].astype(kv_dt), "v": ac["v"].astype(kv_dt)}
            new_cache["attn"] = ac
        if kind == "hybrid":
            with jax.named_scope("mixer/mamba"):
                if mode in ("train", "prefill"):
                    m, ms = ssm.mamba_seq(p["ssm"], h, cfg, state=None)
                else:
                    st = (cache["ssm"]["h"], cache["ssm"]["conv"])
                    m, ms = ssm.mamba_step(p["ssm"], h, st, cfg)
            if mode in ("prefill", "decode"):
                new_cache["ssm"] = {"h": ms[0], "conv": ms[1]}
            y = p["mix_a"].astype(x.dtype) * y + p["mix_m"].astype(x.dtype) * m
        x = x + y
    elif kind in ("mlstm", "slstm"):
        fn_seq = ssm.mlstm_seq if kind == "mlstm" else ssm.slstm_seq
        fn_step = ssm.mlstm_step if kind == "mlstm" else ssm.slstm_step
        with jax.named_scope(f"mixer/{kind}"):
            if mode in ("train", "prefill"):
                y, st = fn_seq(p["ssm"], h, cfg)
            else:
                c = cache["ssm"]
                st_in = (c["C"], c["n"]) if kind == "mlstm" else (c["c"], c["n"], c["h"])
                y, st = fn_step(p["ssm"], h, st_in, cfg)
        if mode in ("prefill", "decode"):
            if kind == "mlstm":
                new_cache["ssm"] = {"C": st[0], "n": st[1]}
            else:
                new_cache["ssm"] = {"c": st[0], "n": st[1], "h": st[2]}
        x = x + y
    else:
        raise ValueError(f"unknown block kind {kind}")

    if "cross" in p:
        hx = rms_norm(p["norm_x"], x, cfg.norm_eps)
        if mode == "decode":
            ck, cv = cache["cross"]["k"], cache["cross"]["v"]
            new_cache["cross"] = cache["cross"]  # carry through
        else:
            cp = p["cross"]
            ck = jnp.einsum("bsd,dhk->bshk", cross_inputs, cp["wk"])
            cv = jnp.einsum("bsd,dhk->bshk", cross_inputs, cp["wv"])
            if spec.qkv_bias:
                ck, cv = ck + cp["bk"], cv + cp["bv"]
            if mode == "prefill":
                new_cache["cross"] = {"k": ck, "v": cv}
        y, _ = attention(p["cross"], hx, spec, cross_kv=(ck, cv))
        x = x + y

    with jax.named_scope("mlp"):
        if "mlp" in p:
            x = x + mlp(p["mlp"], rms_norm(p["norm2"], x, cfg.norm_eps), cfg.act)
        elif "moe" in p and cfg.dropless:
            y, a, load = moe_lib.dropless_ffn(p["moe"], rms_norm(p["norm2"], x, cfg.norm_eps), cfg)
            x = x + y
            aux = aux + a
        elif "moe" in p:
            y, a = moe_lib.moe_ffn(p["moe"], rms_norm(p["norm2"], x, cfg.norm_eps), cfg,
                                   axis_name=axis_name)
            x = x + y
            aux = aux + a

    return x, (new_cache if new_cache else None), aux, load


def _grow_cache(cache: dict, max_len: int, spec: AttnSpec) -> dict:
    """Extend a prefill-built cache to decode capacity ``max_len``."""
    S_tgt = min(max_len, spec.window) if spec.window else max_len
    S = cache["k"].shape[1]
    if S >= S_tgt:
        return cache
    pad = S_tgt - S
    k = jnp.pad(cache["k"], ((0, 0), (0, pad), (0, 0), (0, 0)))
    v = jnp.pad(cache["v"], ((0, 0), (0, pad), (0, 0), (0, 0)))
    pos = jnp.pad(cache["pos"], (0, pad), constant_values=-1)
    return {"k": k, "v": v, "pos": pos}
