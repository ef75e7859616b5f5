"""Moonlight-16B-A3B (DeepSeek-V3's architecture) against its plain
reference (``bench/reference/moonlight.py``), at the benchmark
configuration's rehearsal widths on seeded random weights, in float32:
the loss and gradients, latent attention against a naive per-head
attention, the router's choice and weights under a nonzero bias, dropless
routing under forced imbalance, one chip's share of the experts against
the uncut layer, and the routing bias's update."""
from __future__ import annotations

import dataclasses
import json
import os
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from bench import train as bench_train  # noqa: E402
from bench.reference import common, moonlight as ref  # noqa: E402
from repro.configs import get_config  # noqa: E402
from repro.configs.base import RunConfig  # noqa: E402
from repro.models import Model, mla, moe  # noqa: E402
from repro.train.trainer import Trainer  # noqa: E402

CONFIG = os.path.join(ROOT, "bench", "configs", "moonlight-16b-a3b.json")
HIGHEST = jax.default_matmul_precision("highest")


def small_config(**over):
    """The benchmark file at its rehearsal widths, float32; the reference's
    dict and the program's ModelConfig."""
    with open(CONFIG) as f:
        raw = json.load(f)
    d = {**raw, **raw["rehearsal"], "dtype": "float32", **over}
    return d, bench_train.model_config(d)


def batch(cfg_dict, B=2, T=32, seed=1):
    tok = jax.random.randint(jax.random.PRNGKey(seed), (B, T + 1), 0, cfg_dict["vocab_size"])
    return {"tokens": tok[:, :-1], "labels": tok[:, 1:]}


def close(a, b, tol, what=""):
    """Largest gap within ``tol`` of the reference's largest magnitude."""
    a, b = np.asarray(a, np.float64), np.asarray(b, np.float64)
    scale = max(np.max(np.abs(b)), 1e-30)
    assert np.max(np.abs(a - b)) <= tol * scale, (what, np.max(np.abs(a - b)), scale)


def test_published_keys_configure_the_program():
    with open(CONFIG) as f:
        raw = json.load(f)
    cfg = bench_train.model_config(raw)
    assert (cfg.num_layers, cfg.d_model, cfg.num_heads, cfg.d_ff) == (6, 2048, 16, 1408)
    assert (cfg.num_experts, cfg.router_width, cfg.experts_per_token, cfg.num_shared_experts) == (8, 64, 6, 2)
    assert cfg.mla and cfg.dropless and not cfg.tie_embeddings and cfg.norm_eps == 1e-5
    assert cfg.layer_kinds() == ["attn"] + ["moe"] * 5
    # the cut the configuration file describes: 668.9M parameters
    shapes = Model(cfg).param_shapes()
    assert common.n_params(shapes) == 668_890_432
    assert cfg.param_count() + cfg.d_model == 668_890_432
    with pytest.raises(ValueError, match="disagrees"):
        bench_train.model_config({**raw, "d_model": 1024})


def test_loss_and_gradients_match_reference():
    d, cfg = small_config()
    params = ref.init(d, jax.random.PRNGKey(0))
    model = Model(cfg)
    want = jax.tree.map(lambda a: (a.shape, a.dtype), model.param_shapes())
    assert jax.tree.map(lambda a: (a.shape, a.dtype), params) == want
    b = batch(d)
    with HIGHEST:
        (loss, metrics), grads = jax.value_and_grad(
            lambda p: model.loss(p, b, remat=True), has_aux=True)(params)
        rloss, rgrads = jax.value_and_grad(
            lambda p: ref.loss(p, b["tokens"], b["labels"], d, common.Math()))(params)
    assert metrics["aux"] > 0
    close(loss, rloss, 1e-5)
    for (path, g), r in zip(jax.tree_util.tree_flatten_with_path(grads)[0], jax.tree.leaves(rgrads)):
        close(g, r, 1e-4, jax.tree_util.keystr(path))


def test_mla_matches_naive_attention():
    d, cfg = small_config()
    p = ref.init(d, jax.random.PRNGKey(3))["decoder"]["lead"][0]["attn"]
    B, T, H = 2, 16, cfg.num_heads
    nope, rp, vd, r = cfg.qk_nope_head_dim, cfg.qk_rope_head_dim, cfg.v_head_dim, cfg.kv_lora_rank
    x = jax.random.normal(jax.random.PRNGKey(4), (B, T, cfg.d_model))
    with HIGHEST:
        got = mla.mla_attention(p, x, cfg)
    xn = np.asarray(x, np.float64)
    ang = np.arange(T)[:, None] * cfg.rope_theta ** (-np.arange(0, rp, 2) / rp)

    def rot(v):  # adjacent pairs (2i, 2i+1), de-interleaved
        a, b = v[..., 0::2], v[..., 1::2]
        return np.concatenate([a * np.cos(ang) - b * np.sin(ang), a * np.sin(ang) + b * np.cos(ang)], -1)

    kv_a = xn @ np.asarray(p["wkv_a"], np.float64)
    c = kv_a[..., :r] / np.sqrt(np.mean(kv_a[..., :r] ** 2, -1, keepdims=True) + mla.LATENT_NORM_EPS)
    k_rope = rot(kv_a[..., r:])
    out = np.zeros((B, T, cfg.d_model))
    for h in range(H):
        q = xn @ np.asarray(p["wq"][:, h], np.float64)
        kv = c @ np.asarray(p["wkv_b"][:, h], np.float64)
        qh = np.concatenate([q[..., :nope], rot(q[..., nope:])], -1)
        kh = np.concatenate([kv[..., :nope], k_rope], -1)
        s = np.einsum("btk,bsk->bts", qh, kh) / np.sqrt(nope + rp)
        s = np.where(np.tril(np.ones((T, T), bool)), s, -np.inf)
        w = np.exp(s - s.max(-1, keepdims=True))
        w /= w.sum(-1, keepdims=True)
        out += (w @ kv[..., nope:]) @ np.asarray(p["wo"][h], np.float64)
    close(got, out, 1e-5)


def _moe_params(d, seed=5):
    return jax.tree.map(lambda a: a[0], ref.init(d, jax.random.PRNGKey(seed))["decoder"]["blocks"][0]["moe"])


def test_router_choice_and_weights_match_reference_under_bias():
    d, cfg = small_config()
    p = _moe_params(d)
    x = jax.random.normal(jax.random.PRNGKey(6), (2, 16, cfg.d_model))
    with HIGHEST:
        idx0, _, _, _ = moe.route_sigmoid(p, x, cfg)
        p = dict(p, router_bias=jnp.linspace(-0.5, 0.5, cfg.router_width))
        idx, w, load, aux = moe.route_sigmoid(p, x, cfg)
        rw, rload, raux = ref.route(p, x, d, common.Math())
    assert not np.array_equal(np.sort(idx0, -1), np.sort(idx, -1))   # the bias moved choices
    got = np.zeros(rw.shape)
    np.put_along_axis(got, np.asarray(idx), np.asarray(w), axis=-1)
    close(got, rw, 1e-6)
    np.testing.assert_array_equal(load, rload)
    close(aux, raux, 1e-6)
    # the weights are the unbiased chosen scores, normalised, then scaled
    np.testing.assert_allclose(np.sum(w, -1), cfg.routed_scaling_factor, rtol=1e-6)


def test_no_token_dropped_under_forced_imbalance():
    """A bias that sends every token to the same experts, one of them held
    here: the held expert takes all 2 x 64 tokens, and the layer still
    equals the reference's dense sum over its held experts."""
    d, cfg = small_config()
    p = _moe_params(d)
    bias = jnp.zeros(cfg.router_width).at[jnp.array([0, cfg.router_width - 1])].set(100.0)
    p = dict(p, router_bias=bias)
    x = jax.random.normal(jax.random.PRNGKey(7), (2, 64, cfg.d_model))
    with HIGHEST:
        y, aux, load = moe.dropless_ffn(p, x, cfg)
        ry, raux, rload = ref.moe(p, x, d, common.Math())
    assert load[0] == 2 * 64 and load[-1] == 2 * 64
    np.testing.assert_array_equal(load, rload)
    close(y, ry, 1e-5)
    close(aux, raux, 1e-6)


def test_expert_shares_sum_to_the_uncut_layer():
    """The guide's share test: the router's 8 experts split into shares of
    2; each share's layer (its experts, the shared experts counted once)
    adds up to the uncut layer's result."""
    d, cfg = small_config(n_routed_experts=8, num_experts=8, router_experts=8)
    p = _moe_params(d)
    x = jax.random.normal(jax.random.PRNGKey(8), (2, 16, cfg.d_model))
    shared = common.Math()
    with HIGHEST:
        whole, _, _ = moe.dropless_ffn(p, x, cfg)
        shared_out = ref.mlp(p["shared"], x, shared)
        held = 2
        share_cfg = dataclasses.replace(cfg, num_experts=held)
        total = shared_out
        for first in range(0, cfg.router_width, held):
            part = {**p, **{k: p[k][first:first + held] for k in ("w_gate", "w_up", "w_down")}}
            y, _, _ = moe.dropless_ffn(part, x, share_cfg, first_expert=first)
            total = total + (y - shared_out)
    close(total, whole, 1e-5)


def test_bias_update_follows_the_load():
    """Every step moves each MoE layer's bias by gamma * sign(mean load -
    load), as the reference's rule says; the optimizer leaves it alone."""
    d, cfg = small_config()
    tr = Trainer(cfg, RunConfig(warmup_steps=1, total_steps=10, learning_rate=1e-3))
    params = ref.init(d, jax.random.PRNGKey(0))
    opt = tr.optimizer.init(params)
    b = batch(d, seed=9)
    bias0 = np.asarray(params["decoder"]["blocks"][0]["moe"]["router_bias"])
    with HIGHEST:
        _, metrics = tr.model.loss(params, b)
    new, opt, out = tr._step_fn(params, opt, b)
    np.testing.assert_array_equal(out["expert_load"], metrics["expert_load"])
    want = np.stack([ref.bias_update(bias0[i], out["expert_load"][i]) for i in range(len(bias0))])
    np.testing.assert_allclose(new["decoder"]["blocks"][0]["moe"]["router_bias"], want, atol=1e-7)
    load = np.asarray(out["expert_load"])
    over = load > load.mean(-1, keepdims=True)
    assert over.any() and np.all((want - bias0)[over] == -ref.BIAS_UPDATE_SPEED)


def test_train_flops_count_active_experts():
    with open(CONFIG) as f:
        d = json.load(f)
    per_token = ref.train_flops_per_token(d, 8192, 0)
    assert 2.5e9 < per_token < 2.7e9
    # without attention: 6 per active weight, 0.75 routed experts a token
    active = get_config("moonshot-v1-16b-a3b")
    assert active.param_count(active_only=True) == pytest.approx(2.91e9, rel=0.01)


def test_engine_refuses_latent_attention():
    from repro.serve.engine import Engine

    _, cfg = small_config()
    params = Model(cfg).init(jax.random.PRNGKey(0))
    with pytest.raises(NotImplementedError, match="latent"):
        Engine(cfg, params)


def test_chip_path_matches_reference(monkeypatch):
    """The path a TPU takes, splash attention and the megablox grouped
    matmul, run here by the Pallas interpreter: one training step's loss
    equals the reference's, and a second step traces and runs again."""
    import functools

    from jax.experimental.pallas.ops.tpu.megablox import ops as megablox
    from jax.experimental.pallas.ops.tpu.splash_attention import splash_attention_kernel as splash

    from repro.kernels import interpret

    gmm, make = megablox.gmm, splash.make_splash_mha
    monkeypatch.setattr(interpret, "on_tpu", lambda: True)
    monkeypatch.setattr(megablox, "gmm", lambda *a: gmm(*a, None, False, True))
    monkeypatch.setattr(splash, "make_splash_mha", functools.partial(make, interpret=True))
    d, cfg = small_config()
    tr = Trainer(cfg, RunConfig(warmup_steps=1, total_steps=10, learning_rate=1e-3))
    params = ref.init(d, jax.random.PRNGKey(0))
    b = batch(d, T=256)
    with HIGHEST:
        want = ref.loss(params, b["tokens"], b["labels"], d, common.Math())
        params, opt, out = tr._step_fn(params, tr.optimizer.init(params), b)
        _, _, again = tr._step_fn(params, opt, b)
    close(out["loss"], want, 1e-6)
    assert np.isfinite(float(again["loss"]))


def test_kernels_split_over_the_mesh(dist):
    """On a TPU mesh that splits (four CPU devices here, the kernels run by
    the Pallas interpreter), the splash attention kernel and the grouped
    matmul run as shard_maps, as the Mamba scan does: values and gradients
    equal the XLA routes' on one device, where the mesh divides the rows
    or heads and where it does not. The grouped matmul's rows split across
    group boundaries and leave rows past the last group."""
    dist("""
import functools
import jax, jax.numpy as jnp, numpy as np
from jax.sharding import NamedSharding, PartitionSpec as P
from jax.experimental.pallas.ops.tpu.megablox import ops as megablox
from jax.experimental.pallas.ops.tpu.splash_attention import splash_attention_kernel as splash
from repro.dist import on_mesh
from repro.kernels import interpret, ops
from repro.models import mla

gmm, make, cpu = megablox.gmm, splash.make_splash_mha, interpret.on_tpu
megablox.gmm = lambda *a: gmm(*a, None, False, True)
splash.make_splash_mha = functools.partial(make, interpret=True)
mesh = jax.make_mesh((2, 2), ("data", "model"), axis_types=(jax.sharding.AxisType.Auto,) * 2)
rng = np.random.RandomState(0)
f32 = lambda *shape: jnp.asarray(rng.randn(*shape), jnp.float32)
sizes = jnp.array([100, 150, 200], jnp.int32)          # 62 rows past the groups


def with_grads(f):
    def run(args, ct):
        out, vjp = jax.vjp(f, *args)
        return (out,) + vjp(ct)
    return run


gm = lambda lhs, rhs: ops.grouped_matmul(lhs, rhs, sizes)
cases = [(gm, (f32(512, 64), f32(3, 64, 128)), f32(512, 128)),
         (gm, (f32(510, 64), f32(3, 64, 128)), f32(510, 128)),      # rows replicated
         (mla._attend, (f32(2, 256, 4, 24), f32(2, 256, 4, 24), f32(2, 256, 4, 16)),
          f32(2, 256, 4, 16)),
         (mla._attend, (f32(1, 256, 3, 24), f32(1, 256, 3, 24), f32(1, 256, 3, 16)),
          f32(1, 256, 3, 16))]                                        # replicated
for f, args, ct in cases:
    interpret.on_tpu = cpu
    want = jax.jit(with_grads(f))(args, ct)
    interpret.on_tpu = lambda: True
    args, ct = jax.device_put((args, ct), NamedSharding(mesh, P()))
    split = jax.jit(on_mesh(with_grads(f), mesh))
    assert "shard_map" in str(split.trace(args, ct).jaxpr)
    for g, w in zip(split(args, ct), want):
        np.testing.assert_allclose(np.asarray(g), np.asarray(w), rtol=1e-4,
                                   atol=1e-4 * float(jnp.abs(w).max()))
print("PASS")
""", devices=4)
