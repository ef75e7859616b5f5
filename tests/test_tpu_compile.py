"""The main path's Pallas kernels, compiled for a described TPU v5e.

Nothing runs: the TPU compiler that ships with jax compiles for a chip that
is described but not attached, and refuses what the chip would refuse
(misaligned blocks, too much fast memory). Interpret-mode tests cannot see
either. The widths are the ones the system uses: a 64 MiB f32 gradient
bucket in 16 chunks, and the serving engine's 4 MiB bf16 weight bucket.

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
