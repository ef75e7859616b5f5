"""Per-architecture smoke tests: REDUCED variant of each assigned arch runs
one forward/train step on CPU; output shapes and finiteness asserted."""
from __future__ import annotations

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import ARCHS, get_config
from repro.configs.base import ShapeSpec
from repro.models import Model

SHAPE = ShapeSpec("tiny_train", 64, 2, "train")


@pytest.mark.parametrize("name", sorted(ARCHS))
def test_forward_and_loss(name):
    cfg = get_config(name + "-smoke")
    assert cfg.d_model <= 512 and cfg.num_layers <= 2 * cfg.pattern_period
    assert cfg.num_experts <= 4
    m = Model(cfg)
    params = m.init(jax.random.PRNGKey(0))
    batch = m.sample_batch(SHAPE)
    logits, aux = jax.jit(lambda p, b: m.forward(p, b))(params, batch)
    t_text = SHAPE.seq_len - (cfg.prefix_len if cfg.frontend == "vision" else 0)
    assert logits.shape == (SHAPE.global_batch, t_text, cfg.padded_vocab)
    assert bool(jnp.isfinite(logits).all()), name
    loss, metrics = jax.jit(lambda p, b: m.loss(p, b))(params, batch)
    assert np.isfinite(float(loss)), name
    assert float(metrics["nll"]) < 2.5 * np.log(cfg.padded_vocab), name


@pytest.mark.parametrize("name", ["minitron-8b", "qwen3-moe-30b-a3b", "xlstm-350m", "hymba-1.5b"])
def test_one_grad_step_reduces_loss(name):
    from repro.optim.optimizers import get_optimizer

    cfg = get_config(name + "-smoke")
    m = Model(cfg)
    params = m.init(jax.random.PRNGKey(0))
    opt = get_optimizer("adamw")
    state = opt.init(params)
    batch = m.sample_batch(SHAPE)

    @jax.jit
    def step(params, state, batch):
        (loss, _), grads = jax.value_and_grad(lambda p: m.loss(p, batch), has_aux=True)(params)
        params, state = opt.update(grads, state, params, jnp.asarray(1e-3))
        return params, state, loss

    l0 = None
    for _ in range(4):
        params, state, loss = step(params, state, batch)
        l0 = float(loss) if l0 is None else l0
    assert float(loss) < l0, (name, l0, float(loss))
    for leaf in jax.tree.leaves(params):
        assert bool(jnp.isfinite(leaf.astype(jnp.float32)).all())


@pytest.mark.parametrize(
    "name", ["gemma3-27b", "mixtral-8x7b", "whisper-large-v3", "paligemma-3b", "xlstm-350m"]
)
def test_prefill_decode_consistency(name):
    """Incremental decode reproduces the full forward (bf16 tolerance)."""
    T, B = 24, 2
    cfg = get_config(name + "-smoke")
    if cfg.num_experts:
        cfg = dataclasses.replace(cfg, capacity_factor=float(cfg.num_experts))
    m = Model(cfg)
    params = m.init(jax.random.PRNGKey(1))
    rng = np.random.RandomState(0)
    batch = {"tokens": jnp.asarray(rng.randint(0, cfg.vocab_size - 1, (B, T)))}
    if cfg.frontend == "vision":
        batch["embeds"] = jnp.asarray(
            rng.randn(B, cfg.prefix_len, cfg.d_model).astype(np.float32), jnp.bfloat16
        )
    if cfg.arch_type == "encdec":
        batch["embeds"] = jnp.asarray(
            rng.randn(B, cfg.frontend_len, cfg.d_model).astype(np.float32), jnp.bfloat16
        )
    full, _ = m.forward(params, batch)
    Tp = T // 2
    pre = dict(batch)
    pre["tokens"] = batch["tokens"][:, :Tp]
    logits_p, caches = jax.jit(lambda p, b: m.prefill(p, b, max_len=T))(params, pre)
    np.testing.assert_allclose(
        np.asarray(logits_p, np.float32), np.asarray(full[:, :Tp], np.float32),
        atol=0.12, rtol=0.12,
    )
    step = jax.jit(m.decode_step)
    offset = cfg.prefix_len if cfg.frontend == "vision" else 0
    for t in range(Tp, T):
        logits_d, caches = step(
            params, batch["tokens"][:, t : t + 1], caches, jnp.asarray(t + offset, jnp.int32)
        )
        np.testing.assert_allclose(
            np.asarray(logits_d[:, 0], np.float32), np.asarray(full[:, t], np.float32),
            atol=0.3, rtol=0.3,
        )


def test_param_counts_reasonable():
    """Full configs' param counts are in the advertised ballpark."""
    expect = {
        "minitron-8b": (6e9, 11e9),
        "mixtral-8x7b": (40e9, 52e9),
        "qwen3-moe-30b-a3b": (25e9, 34e9),
        "gemma3-27b": (22e9, 32e9),
        "qwen1.5-32b": (28e9, 38e9),
        # Moonlight-16B-A3B as published: 27 layers of MLA, 1 dense + 26 with
        # 64 experts of 1408 and 2 shared: 15.96e9
        "moonshot-v1-16b-a3b": (15.5e9, 16.5e9),
        "xlstm-350m": (0.25e9, 0.5e9),
        "hymba-1.5b": (1.0e9, 2.2e9),
        "whisper-large-v3": (1.2e9, 2.2e9),
        "paligemma-3b": (2.0e9, 3.5e9),
    }
    for name, (lo, hi) in expect.items():
        n = get_config(name).param_count()
        assert lo <= n <= hi, f"{name}: {n/1e9:.2f}B not in [{lo/1e9}, {hi/1e9}]"


def test_long_500k_eligibility():
    sub_q = {n for n in ARCHS if get_config(n).sub_quadratic}
    assert sub_q == {"xlstm-350m", "hymba-1.5b", "gemma3-27b", "mixtral-8x7b"}
