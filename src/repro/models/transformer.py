"""Stack assembly: scan-over-superblocks decoder (+ optional encoder).

Layers are grouped into *superblocks* of length P = lcm(|block_pattern|,
|attn_pattern|): a single traced scan body contains one block per pattern
slot, and ``lax.scan`` iterates over ``num_layers // P`` superblocks with
stacked parameters. Heterogeneous stacks (xLSTM's 7:1 mLSTM:sLSTM, gemma3's
5:1 local:global) therefore compile to ONE body — HLO size and compile time
are depth-independent. ``num_layers % P`` leftover layers run unscanned, as
do leading dense layers (``cfg.first_k_dense_replace``, kept under
``lead`` with their own MLP width) ahead of the scan.
"""
from __future__ import annotations

import math
from functools import partial
from typing import Optional

import jax
import jax.numpy as jnp
from jax import lax

from ..dist.hints import hint
from .blocks import apply_block, init_block, init_block_cache
from .layers import embed_tokens, init_embedding, init_rms_norm, rms_norm, unembed

__all__ = ["StackLayout", "init_lm", "apply_lm", "init_decode_cache", "update_router_bias"]


class StackLayout:
    """Derived layer layout for a config."""

    def __init__(self, cfg, *, encoder: bool = False):
        self.cfg = cfg
        self.lead = 0
        if encoder:
            self.kinds = ["attn"] * cfg.encoder_layers
            self.windows = [None] * cfg.encoder_layers
            self.period = 1
            self.num_layers = cfg.encoder_layers
        else:
            bp, ap = cfg.block_pattern, cfg.attn_pattern
            self.period = math.lcm(len(bp), len(ap))
            self.lead = cfg.first_k_dense_replace
            self.num_layers = cfg.num_layers - self.lead   # scanned and tail layers
            self.kinds = cfg.layer_kinds()[self.lead:]
            self.windows = cfg.layer_windows()[self.lead:]
        self.num_super = self.num_layers // self.period
        self.tail = self.num_layers % self.period

    def slot_kind(self, i: int) -> str:
        return self.kinds[i]

    def slot_window(self, i: int):
        return self.windows[i]


def _dtype(cfg):
    return jnp.dtype(cfg.dtype)


def _init_stack(key, cfg, layout: StackLayout, *, cross: bool, causal: bool):
    dt = _dtype(cfg)
    blocks = []
    for i in range(layout.period):
        kind, win = layout.kinds[i], layout.windows[i]
        keys = jax.random.split(jax.random.fold_in(key, i), max(layout.num_super, 1))
        init_one = partial(init_block, cfg=cfg, kind=kind, window=win, cross=cross, causal=causal, dtype=dt)
        if layout.num_super:
            blocks.append(jax.vmap(lambda k: init_one(k))(keys))
        else:
            blocks.append(None)
    tail = []
    for j in range(layout.tail):
        i = layout.num_super * layout.period + j
        tail.append(
            init_block(
                jax.random.fold_in(key, 10_000 + j),
                cfg,
                layout.kinds[i % layout.period],
                layout.windows[i % layout.period],
                cross=cross,
                causal=causal,
                dtype=dt,
            )
        )
    out = {"blocks": blocks, "tail": tail}
    if layout.lead:
        out["lead"] = [
            init_block(jax.random.fold_in(key, 20_000 + j), cfg, "attn", cfg.layer_windows()[j],
                       cross=cross, causal=causal, dtype=dt, d_ff=cfg.intermediate_size)
            for j in range(layout.lead)
        ]
    return out


def init_lm(key, cfg):
    """Full parameter tree for a config."""
    dt = _dtype(cfg)
    ks = jax.random.split(key, 4)
    layout = StackLayout(cfg)
    params = {
        "embed": init_embedding(ks[0], cfg.padded_vocab, cfg.d_model, cfg.tie_embeddings, dt),
        "decoder": _init_stack(ks[1], cfg, layout, cross=(cfg.arch_type == "encdec"), causal=True),
        "final_norm": init_rms_norm(cfg.d_model),
    }
    if cfg.arch_type == "encdec":
        enc_layout = StackLayout(cfg, encoder=True)
        params["encoder"] = _init_stack(ks[2], cfg, enc_layout, cross=False, causal=False)
        params["enc_norm"] = init_rms_norm(cfg.d_model)
    return params


def _apply_stack(
    stack_params,
    x,
    cfg,
    layout: StackLayout,
    *,
    mode: str,
    caches=None,
    cur_pos=None,
    max_len: int = 0,
    prefix_len: int = 0,
    causal: bool = True,
    cross_inputs=None,
    remat: bool = False,
    axis_name=None,
):
    """Returns (x, new_caches, aux, expert_load). Caches: {'blocks': [...],
    'tail': [...]}. ``axis_name`` routes MoE expert dispatch over that mesh
    axis (see ``apply_block``). ``expert_load`` stacks the per-expert loads
    of the dropless MoE layers in layer order (None without them)."""
    P = layout.period
    kinds, wins = layout.kinds, layout.windows
    run_block = partial(
        apply_block,
        cfg=cfg,
        mode=mode,
        cur_pos=cur_pos,
        max_len=max_len,
        prefix_len=prefix_len,
        causal=causal,
        cross_inputs=cross_inputs,
        axis_name=axis_name,
    )

    def body(x, xs):
        bs, cs = xs
        aux = jnp.zeros((), jnp.float32)
        new_cs, loads = [], []
        for i in range(P):
            x, nc, a, ld = run_block(bs[i], x, kind=kinds[i], window=wins[i], cache=None if cs is None else cs[i])
            x = hint(x, "btd_res")  # optional sequence-parallel residual
            aux = aux + a
            new_cs.append(nc)
            if ld is not None:
                loads.append(ld)
        if mode == "train":
            return x, (aux, loads)
        return x, (new_cs, aux)

    if remat and mode == "train":
        body = jax.checkpoint(body, prevent_cse=False)

    aux_total = jnp.zeros((), jnp.float32)
    loads = []
    for j, lp in enumerate(stack_params.get("lead", [])):
        lead_block = partial(run_block, kind="attn", window=cfg.layer_windows()[j], cache=None)
        if remat and mode == "train":
            lead_block = jax.checkpoint(lead_block, prevent_cse=False)
        x, _, a, _ = lead_block(lp, x)
        aux_total = aux_total + a
    new_caches = {"blocks": None, "tail": []}
    if layout.num_super:
        xs = (stack_params["blocks"], caches["blocks"] if caches else None)
        if mode == "train":
            x, (auxs, slot_loads) = lax.scan(body, x, xs)
            if slot_loads:  # (num_super, E) per slot -> layer order
                loads.append(jnp.stack(slot_loads, axis=1).reshape(-1, slot_loads[0].shape[-1]))
        else:
            x, (blk_caches, auxs) = lax.scan(body, x, xs)
            new_caches["blocks"] = blk_caches
        aux_total = aux_total + jnp.sum(auxs)
    for j, tp in enumerate(stack_params["tail"]):
        i = layout.num_super * P + j
        tc = caches["tail"][j] if caches else None
        x, nc, a, ld = run_block(tp, x, kind=kinds[i % P], window=wins[i % P], cache=tc)
        aux_total = aux_total + a
        new_caches["tail"].append(nc)
        if ld is not None:
            loads.append(ld[None])
    load = jnp.concatenate(loads) if loads else None
    return x, (new_caches if mode != "train" else None), aux_total, load


def apply_lm(
    params,
    cfg,
    *,
    tokens=None,
    embeds=None,
    mode: str = "train",
    caches=None,
    cur_pos=None,
    max_len: int = 0,
    remat: bool = False,
):
    """Unified forward.

    train/prefill: ``tokens`` (B, T_text); VLM prepends ``embeds``
    (B, prefix, D); audio encdec consumes ``embeds`` (B, frames, D) through
    the encoder. decode: ``tokens`` (B, 1) + ``caches`` + scalar ``cur_pos``.

    Returns (logits_f32, new_caches, aux, expert_load); ``expert_load``
    (dropless MoE layers x router experts) is None for other models.
    """
    layout = StackLayout(cfg)
    dt = _dtype(cfg)
    prefix_len = 0
    cross_inputs = None
    enc_caches_out = None

    if cfg.arch_type == "encdec":
        if mode == "decode":
            cross_inputs = None  # cross K/V live in the per-layer cache
        else:
            assert embeds is not None, "encdec needs frontend embeddings"
            enc_layout = StackLayout(cfg, encoder=True)
            h = embeds.astype(dt)
            h, _, _, _ = _apply_stack(
                params["encoder"], h, cfg, enc_layout, mode="train", causal=False, remat=remat
            )
            cross_inputs = rms_norm(params["enc_norm"], h, cfg.norm_eps)
        x = embed_tokens(params["embed"], tokens) * jnp.asarray(cfg.d_model**0.5, dt)
    elif cfg.frontend == "vision":
        x = embed_tokens(params["embed"], tokens) * jnp.asarray(cfg.d_model**0.5, dt)
        if mode in ("train", "prefill"):
            assert embeds is not None, "vlm needs patch embeddings"
            x = jnp.concatenate([embeds.astype(dt), x], axis=1)
            prefix_len = embeds.shape[1]
        else:
            prefix_len = cfg.prefix_len
    elif cfg.embed_scale:
        x = embed_tokens(params["embed"], tokens) * jnp.asarray(cfg.d_model**0.5, dt)
    else:
        x = embed_tokens(params["embed"], tokens)

    x = hint(x, "btd")
    x, new_caches, aux, load = _apply_stack(
        params["decoder"],
        x,
        cfg,
        layout,
        mode=mode,
        caches=caches,
        cur_pos=cur_pos,
        max_len=max_len,
        prefix_len=prefix_len,
        causal=True,
        cross_inputs=cross_inputs,
        remat=remat,
    )
    with jax.named_scope("head"):
        x = rms_norm(params["final_norm"], x, cfg.norm_eps)
        if mode in ("train", "prefill") and prefix_len and cfg.frontend == "vision":
            x = x[:, prefix_len:]
        logits = hint(unembed(params["embed"], x), "btv")
    return logits, new_caches, aux, load


# gamma of DeepSeek-V3's bias update (arXiv:2412.19437; published configs
# do not give it)
BIAS_UPDATE_SPEED = 1e-3


def update_router_bias(new_params, params, load, cfg):
    """DeepSeek-V3's auxiliary-loss-free balancing, after an optimizer step:
    each dropless MoE layer's correction bias becomes its value before the
    step plus ``BIAS_UPDATE_SPEED * sign(mean load - load)``, whatever the
    optimizer made of the leaf (its gradient is zero). ``load`` is
    ``apply_lm``'s, in layer order: the scanned superblocks, then the tail."""
    layout = StackLayout(cfg)
    P, n = layout.period, layout.num_super
    step = BIAS_UPDATE_SPEED * jnp.sign(jnp.mean(load, axis=-1, keepdims=True) - load)
    dec, old = dict(new_params["decoder"]), params["decoder"]
    slots = [i for i in range(P) if "router_bias" in old["blocks"][i].get("moe", {})] if n else []
    if slots:
        per_slot = step[: n * len(slots)].reshape(n, len(slots), -1)
        blocks = list(dec["blocks"])
        for j, i in enumerate(slots):
            moe = dict(blocks[i]["moe"], router_bias=old["blocks"][i]["moe"]["router_bias"] + per_slot[:, j])
            blocks[i] = dict(blocks[i], moe=moe)
        dec["blocks"] = blocks
    rest = step[n * len(slots):]
    tail = list(dec["tail"])
    for j, tp in enumerate(old["tail"]):
        if "router_bias" in tp.get("moe", {}):
            tail[j] = dict(tail[j], moe=dict(tail[j]["moe"], router_bias=tp["moe"]["router_bias"] + rest[0]))
            rest = rest[1:]
    dec["tail"] = tail
    return dict(new_params, decoder=dec)


def init_decode_cache(cfg, batch: int, max_len: int):
    """Zero decode cache matching apply_lm's cache structure (also used to
    build ShapeDtypeStruct specs for the decode dry-run)."""
    layout = StackLayout(cfg)
    dt = _dtype(cfg)
    P = layout.period
    blocks = None
    if layout.num_super:
        blocks = []
        for i in range(P):
            one = init_block_cache(cfg, layout.kinds[i], layout.windows[i], batch, max_len, dt)
            if cfg.arch_type == "encdec":
                one["cross"] = _zero_cross(cfg, batch, dt)
            stacked = jax.tree.map(
                lambda l: jnp.broadcast_to(l, (layout.num_super,) + l.shape), one
            )
            blocks.append(stacked)
    tail = []
    for j in range(layout.tail):
        i = layout.num_super * P + j
        one = init_block_cache(cfg, layout.kinds[i % P], layout.windows[i % P], batch, max_len, dt)
        if cfg.arch_type == "encdec":
            one["cross"] = _zero_cross(cfg, batch, dt)
        tail.append(one)
    return {"blocks": blocks, "tail": tail}


def _zero_cross(cfg, batch: int, dt):
    return {
        "k": jnp.zeros((batch, cfg.frontend_len, cfg.num_kv_heads, cfg.head_dim), dt),
        "v": jnp.zeros((batch, cfg.frontend_len, cfg.num_kv_heads, cfg.head_dim), dt),
    }
