"""Pieces every plain reference shares: the matmul that the control runs in
fp8, norms, the loss, the optimizer and learning-rate schedule the traffic
file states, and one training step.

Nothing here imports the system under test. Parameters are the same nested
dicts the program trains (its layout is the interface), but every value is
made here from the seed, and all arithmetic is float32 at the `highest`
matmul precision, except in the control, where each matmul's operands are
first rounded to fp8 (e4m3, one scale per tensor).
"""
from __future__ import annotations

import math

import jax
import jax.numpy as jnp
import numpy as np

F32 = jnp.float32
E4M3_MAX = 448.0


@jax.custom_vjp
def _fp8(x):
    scale = jnp.maximum(jnp.max(jnp.abs(x)), 1e-30) / E4M3_MAX
    return (x / scale).astype(jnp.float8_e4m3fn).astype(F32) * scale


def _fp8_fwd(x):
    return _fp8(x), None


def _fp8_bwd(_, g):
    return (g,)


_fp8.defvjp(_fp8_fwd, _fp8_bwd)


class Math:
    """Matmuls of one precision: ``fp8=False`` is the reference, float32
    at `highest`; ``fp8=True`` is the control, whose forward matmuls see
    operands rounded to e4m3 (gradients pass straight through)."""

    def __init__(self, fp8: bool = False):
        self.fp8 = fp8

    def einsum(self, eq, a, b):
        a, b = a.astype(F32), b.astype(F32)
        if self.fp8:
            a, b = _fp8(a), _fp8(b)
        return jnp.einsum(eq, a, b, precision=jax.lax.Precision.HIGHEST)


def rms_norm(x, scale, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps) * scale.astype(F32)


def normal(key, shape, scale, dtype):
    return (jax.random.normal(key, shape, F32) * scale).astype(dtype)


def padded_vocab(cfg) -> int:
    p = cfg["vocab_pad_to"]
    return -(-cfg["vocab_size"] // p) * p


def cross_entropy(logits, labels):
    logz = jax.nn.logsumexp(logits, axis=-1)
    gold = jnp.take_along_axis(logits, labels[..., None], axis=-1)[..., 0]
    return jnp.mean(logz - gold)


def lr_at(step, opt):
    """Linear warm-up to ``learning_rate``, then cosine to ``min_ratio`` of
    it by ``total_steps``."""
    base, warm, total = opt["learning_rate"], opt["warmup_steps"], opt["total_steps"]
    step = jnp.asarray(step, F32)
    warm_lr = base * step / max(warm, 1)
    frac = jnp.clip((step - warm) / max(total - warm, 1), 0.0, 1.0)
    cos_lr = base * (opt["min_ratio"] + (1 - opt["min_ratio"]) * 0.5 * (1 + jnp.cos(jnp.pi * frac)))
    return jnp.where(step < warm, warm_lr, cos_lr)


def adamw_init(params):
    zeros = lambda p: jnp.zeros(p.shape, F32)  # noqa: E731
    return {"m": jax.tree.map(zeros, params), "v": jax.tree.map(zeros, params),
            "step": jnp.zeros((), jnp.int32)}


def clip(grads, max_norm):
    norm = jnp.sqrt(sum(jnp.sum(g * g) for g in jax.tree.leaves(grads)))
    scale = jnp.minimum(1.0, max_norm / (norm + 1e-9))
    return jax.tree.map(lambda g: g * scale, grads)


def adamw_update(grads, state, params, lr, opt):
    """AdamW with bias correction and decoupled weight decay. Parameters are
    kept in the dtype the configuration stores them in."""
    b1, b2, eps, wd = opt["b1"], opt["b2"], opt["eps"], opt["weight_decay"]
    step = state["step"] + 1
    t = step.astype(F32)
    c1, c2 = 1.0 - b1 ** t, 1.0 - b2 ** t
    m = jax.tree.map(lambda m, g: b1 * m + (1 - b1) * g, state["m"], grads)
    v = jax.tree.map(lambda v, g: b2 * v + (1 - b2) * g * g, state["v"], grads)

    def upd(p, m, v):
        pf = p.astype(F32)
        delta = (m / c1) / (jnp.sqrt(v / c2) + eps) + wd * pf
        return (pf - lr * delta).astype(p.dtype)

    return jax.tree.map(upd, params, m, v), {"m": m, "v": v, "step": step}


def make_train_step(loss_fn, opt, rows_per_block: int, row_sharding=None):
    """One optimizer step over a global batch. The mean loss and its
    gradient are accumulated over blocks of ``rows_per_block`` sequences, so
    the reference fits beside nothing else on the chip; the result is the
    full batch's mean. ``row_sharding`` spreads each block's rows over the
    chips. Returns the new weights and state, the loss, and the norm of
    every leaf of the clipped gradient."""

    def grad_of_batch(params, tokens, labels):
        B = tokens.shape[0]
        nb = B // rows_per_block
        pf = jax.tree.map(lambda p: p.astype(F32), params)
        vg = jax.value_and_grad(loss_fn)

        def body(acc, blk):
            tok, lab = blk
            loss, g = vg(pf, tok, lab)
            return jax.tree.map(lambda a, b: a + b / nb, acc, (loss, g)), None

        zero = (jnp.zeros((), F32), jax.tree.map(jnp.zeros_like, pf))
        blocks = (tokens.reshape(nb, rows_per_block, -1), labels.reshape(nb, rows_per_block, -1))
        if row_sharding is not None:
            blocks = jax.lax.with_sharding_constraint(blocks, row_sharding)
        (loss, grads), _ = jax.lax.scan(body, zero, blocks)
        return loss, grads

    def step(params, state, tokens, labels):
        loss, grads = grad_of_batch(params, tokens, labels)
        grads = clip(grads, opt["clip_norm"])
        lr = lr_at(state["step"], opt)
        params, state = adamw_update(grads, state, params, lr, opt)
        return params, state, loss, leaf_norms(grads)

    return step


def leaf_norms(tree):
    """float32 norm of every leaf, in flattening order."""
    return jnp.stack([jnp.sqrt(jnp.sum(jnp.square(l.astype(F32)))) for l in jax.tree.leaves(tree)])


def rope(x, theta):
    """Rotary embedding over the last axis, halves rotated; x (B,T,H,hd)."""
    T, hd = x.shape[1], x.shape[-1]
    half = hd // 2
    freqs = jnp.exp(-math.log(theta) * jnp.arange(half, dtype=F32) / half)
    ang = jnp.arange(T, dtype=F32)[:, None] * freqs
    cos, sin = jnp.cos(ang)[None, :, None, :], jnp.sin(ang)[None, :, None, :]
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate([x1 * cos - x2 * sin, x1 * sin + x2 * cos], axis=-1)


def n_params(shapes) -> int:
    return int(sum(np.prod(s.shape) for s in jax.tree.leaves(shapes)))
