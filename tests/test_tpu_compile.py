"""The main path's Pallas kernels, compiled for a described TPU v5e.

Nothing runs: the TPU compiler that ships with jax compiles for a chip that
is described but not attached, and refuses what the chip would refuse
(misaligned blocks, too much fast memory). Interpret-mode tests cannot see
either. The widths are the ones the system uses: a 64 MiB f32 gradient
bucket in 16 chunks, the serving engine's 4 MiB bf16 weight bucket, and
hymba-1.5b's Mamba scan over a batch of 2 x 2048, alone and inside the
trainer's step and the engine's prefill on four chips; Moonlight-16B-A3B's
latent attention through the splash kernel, and its training step cut to
two layers on one chip and on four.

This is the only test file that describes a chip. The topology is described
inside a fixture, never on import: only one process at a time may load the
TPU library, so every worker must collect the same tests and only the one
that runs this file may touch it.
"""
from __future__ import annotations

import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P, SingleDeviceSharding

GRAD_BUCKET = (16, 1 << 20)            # 64 MiB f32 in 16 chunks
WEIGHT_BUCKET = (4 << 20) // 2 - 77    # 4 MiB bf16, ragged tail


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies

    os.environ.setdefault("TPU_LOG_DIR", "disabled")  # else libtpu logs to TMPDIR
    try:
        return topologies.get_topology_desc(platform="tpu", topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 — any failure means no described chip
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


def _compile(fn, *args):
    compiled = jax.jit(fn).lower(*args).compile()
    assert "tpu_custom_call" in compiled.as_text()
    return compiled


def test_fused_combine_update_compiles(one_chip):
    from repro.kernels.combine_update import fused_combine_update

    buf = jax.ShapeDtypeStruct(GRAD_BUCKET, jnp.float32, sharding=one_chip)
    i32 = jax.ShapeDtypeStruct((), jnp.int32, sharding=one_chip)
    _compile(
        lambda b, r, start, comb: fused_combine_update(
            b, r, start, 0, GRAD_BUCKET[0], combine=comb, interpret=False),
        buf, buf, i32, i32,
    )


def test_chunked_copy_compiles(one_chip):
    from repro.kernels.chunked_copy import chunked_copy

    x = jax.ShapeDtypeStruct((WEIGHT_BUCKET,), jnp.bfloat16, sharding=one_chip)
    _compile(lambda v: chunked_copy(v, interpret=False), x)


@pytest.mark.parametrize("fmt", ["int8", "fp8"])
def test_quantize_roundtrip_compiles(one_chip, fmt):
    from repro.kernels import quantize

    x = jax.ShapeDtypeStruct(GRAD_BUCKET, jnp.float32, sharding=one_chip)
    _compile(lambda v: quantize.quantize_blocks(v, fmt, interpret=False), x)
    wire_dtype = quantize.QUANT_DTYPES[fmt][0]
    values = jax.ShapeDtypeStruct(GRAD_BUCKET, wire_dtype, sharding=one_chip)
    scales = jax.ShapeDtypeStruct(
        (GRAD_BUCKET[0], GRAD_BUCKET[1] // quantize.BLOCK_ELEMS), jnp.float32,
        sharding=one_chip)
    _compile(lambda v, s: quantize.dequantize_blocks(v, s, interpret=False),
             values, scales)


@pytest.mark.parametrize("Bt,T,di", [
    (2, 2048, 3200),   # hymba-1.5b's training step: 16 blocks of 128 steps
    (1, 2047, 1600),   # an odd prompt on half of d_inner: padded steps and lanes
    (2, 36, 96),       # a short prompt: one block of 40 steps
])
def test_mamba_scan_compiles(one_chip, Bt, T, di):
    """Forward and backward kernels with hymba-1.5b's state of 16 and blocks
    of up to 128 steps."""
    from repro.kernels.mamba_scan import mamba_scan

    N = 16
    f32 = lambda *shape: jax.ShapeDtypeStruct(shape, jnp.float32, sharding=one_chip)
    args = (f32(Bt, T, di), f32(Bt, T, di), f32(Bt, T, N), f32(Bt, T, N), f32(di, N),
            f32(Bt, di, N))
    scan = lambda *a: mamba_scan(*a, chunk=128, interpret=False)
    assert "mamba_scan_fwd" in _compile(scan, *args).as_text()

    def loss(*a):
        y, h_last = scan(*a)
        return jnp.sum(y) + jnp.sum(h_last)

    assert "mamba_scan_bwd" in _compile(jax.grad(loss, argnums=range(6)), *args).as_text()


def test_mla_splash_attention_compiles(one_chip):
    """The splash attention kernel at Moonlight's latent-attention widths
    (16 heads, q and k 192 wide, v 128) over 2 x 8192, forward and
    backward."""
    from repro.kernels.ops import causal_attention

    bf16 = lambda *shape: jax.ShapeDtypeStruct(shape, jnp.bfloat16, sharding=one_chip)
    q, k, v = bf16(2, 8192, 16, 192), bf16(2, 8192, 16, 192), bf16(2, 8192, 16, 128)
    _compile(causal_attention, q, k, v)
    _compile(jax.grad(lambda *a: jnp.sum(causal_attention(*a).astype(jnp.float32)), argnums=(0, 1, 2)),
             q, k, v)


def test_moonlight_step_compiles_on_one_chip(one_chip, mosaic):
    """The trainer's step at Moonlight-16B-A3B's widths and the benchmark
    cell's batch of 2 x 8192, cut as the cell cuts it (8 of 64 experts held,
    a vocabulary of 20,480) and to the dense layer and one MoE layer:
    latent attention through the splash kernel, the held experts' grouped
    matmul, all within one chip's memory."""
    import dataclasses

    from repro.configs import get_config
    from repro.configs.base import RunConfig
    from repro.train.trainer import Trainer

    cfg = dataclasses.replace(get_config("moonshot-v1-16b-a3b"), num_layers=2, vocab_size=20480,
                              num_experts=8, router_experts=64)
    mesh = Mesh(np.array([one_chip.device_set.pop()]).reshape(1, 1), ("data", "model"))
    tr = Trainer(cfg, RunConfig(), mesh=mesh)
    rep = P()
    params = jax.eval_shape(tr.model.init, jax.random.PRNGKey(0))
    opt = jax.eval_shape(tr.optimizer.init, params)
    tok = jax.ShapeDtypeStruct((2, 8192), jnp.int32)
    on = lambda tree: _on(mesh, tree, jax.tree.map(lambda _: rep, tree))  # noqa: E731
    compiled = tr._step_fn.lower(on(params), on(opt), on({"tokens": tok, "labels": tok})).compile()
    text = compiled.as_text()
    assert "splash" in text and "%gmm." in text and "%tgmm." in text
    mem = compiled.memory_analysis()
    assert mem.temp_size_in_bytes + mem.argument_size_in_bytes < 15.75 * 2**30


@pytest.fixture(scope="module")
def four_chips(topo):
    from jax.sharding import AxisType

    return Mesh(np.array(topo.devices).reshape(2, 2), ("data", "model"),
                axis_types=(AxisType.Auto,) * 2)


@pytest.fixture
def mosaic(monkeypatch):
    """The kernels lower through Mosaic, as on the chip: the backend here is
    the CPU, whose default is the interpreter, which XLA could partition."""
    from repro.kernels import interpret

    monkeypatch.setattr(interpret, "on_tpu", lambda: True)


def _hymba(layers=2):
    import dataclasses

    from repro.configs import get_config

    return dataclasses.replace(get_config("hymba-1.5b"), num_layers=layers)


def _on(mesh, tree, specs):
    return jax.tree.map(lambda a, s: jax.ShapeDtypeStruct(a.shape, a.dtype,
                                                         sharding=NamedSharding(mesh, s)),
                        tree, specs, is_leaf=lambda x: isinstance(x, P))


def test_hymba_grad_allreduce_step_compiles_on_four_chips(four_chips, mosaic):
    """The trainer's default step (``grad_allreduce``: XLA partitions the
    step over data x model) at hymba-1.5b's widths: the scan kernels split
    themselves over the mesh, which XLA's partitioner cannot do."""
    from repro.configs.base import RunConfig
    from repro.dist.sharding import batch_specs
    from repro.train.trainer import Trainer

    tr = Trainer(_hymba(), RunConfig(sync_mode="grad_allreduce"), mesh=four_chips)
    params = jax.eval_shape(tr.model.init, jax.random.PRNGKey(0))
    opt = jax.eval_shape(tr.optimizer.init, params)
    tok = jax.ShapeDtypeStruct((4, 256), jnp.int32)
    batch = {"tokens": tok, "labels": tok}
    ospecs = {k: (tr._pspecs if k in ("m", "v") else P()) for k in opt}
    text = tr._step_fn.lower(_on(four_chips, params, tr._pspecs), _on(four_chips, opt, ospecs),
                             _on(four_chips, batch, batch_specs(batch, four_chips))
                             ).compile().as_text()
    assert "tpu_custom_call" in text
    assert "mamba_scan_fwd" in text and "mamba_scan_bwd" in text


def test_hymba_sharded_prefill_compiles_on_four_chips(four_chips, mosaic):
    """The serving engine's prefill on its tensor-parallel layout, with an
    odd prompt length, as ``Engine`` jits it."""
    from functools import partial

    from repro.dist.sharding import batch_specs, on_mesh, param_specs
    from repro.models import Model

    model = Model(_hymba())
    shapes = model.param_shapes()
    pspecs = param_specs(shapes, four_chips, fsdp=False, attn_fallback="head_dim")
    batch = {"tokens": jax.ShapeDtypeStruct((2, 301), jnp.int32)}
    fn = on_mesh(partial(model.prefill, max_len=320), four_chips)
    text = jax.jit(fn).lower(_on(four_chips, shapes, pspecs),
                             _on(four_chips, batch, batch_specs(batch, four_chips))
                             ).compile().as_text()
    assert "tpu_custom_call" in text and "mamba_scan_fwd" in text


def test_moonlight_step_compiles_on_four_chips(four_chips, mosaic):
    """The trainer's default step over data x model at Moonlight-16B-A3B's
    widths, cut to the dense layer and one MoE layer: the splash attention
    and grouped-matmul kernels split themselves over the mesh, which XLA's
    partitioner cannot do."""
    import dataclasses

    from repro.configs import get_config
    from repro.configs.base import RunConfig
    from repro.dist.sharding import batch_specs
    from repro.train.trainer import Trainer

    cfg = dataclasses.replace(get_config("moonshot-v1-16b-a3b"), num_layers=2, vocab_size=20480,
                              num_experts=8, router_experts=64)
    tr = Trainer(cfg, RunConfig(sync_mode="grad_allreduce"), mesh=four_chips)
    params = jax.eval_shape(tr.model.init, jax.random.PRNGKey(0))
    opt = jax.eval_shape(tr.optimizer.init, params)
    tok = jax.ShapeDtypeStruct((4, 1024), jnp.int32)
    batch = {"tokens": tok, "labels": tok}
    ospecs = {k: (tr._pspecs if k in ("m", "v") else P()) for k in opt}
    text = tr._step_fn.lower(_on(four_chips, params, tr._pspecs), _on(four_chips, opt, ospecs),
                             _on(four_chips, batch, batch_specs(batch, four_chips))
                             ).compile().as_text()
    assert "splash" in text and "%gmm." in text and "%tgmm." in text


@pytest.mark.parametrize("M", [4 << 20, 64 << 20])
@pytest.mark.parametrize("op, algo", [("bcast", "pipelined_chain"),
                                      ("allreduce", "ring_allreduce")])
def test_inkernel_rdma_replay_compiles_on_four_chips(topo, monkeypatch, op, algo, M):
    """The RDMA replay inside ``jax.shard_map`` over the four described
    chips, on an f32 buffer of ``M`` bytes per chip (64 MiB once took more
    scoped VMEM than the chip has). The backend here is the CPU, so the
    test tells the kernel module it runs on a TPU."""
    from repro.comm import plan_collective
    from repro.comm.executors import execute_inkernel
    from repro.kernels import inkernel_collective

    monkeypatch.setattr(inkernel_collective, "on_tpu", lambda: True)
    n = 4
    plan = plan_collective(op, M, n, algo=algo)
    low = plan.lowered()
    rows = low.num_chunks
    cols = -(-(M // 4) // rows)
    mesh = Mesh(np.array(topo.devices), ("x",))
    f = jax.shard_map(
        lambda b: execute_inkernel(low, b, "x", interpret=False),
        mesh=mesh, in_specs=P("x"), out_specs=P("x"), check_vma=False)
    buf = jax.ShapeDtypeStruct((n * rows, cols), jnp.float32,
                               sharding=NamedSharding(mesh, P("x")))
    _compile(f, buf)
