"""On-device correctness for the ragged collectives (simulated devices,
subprocess): pallgatherv/palltoallv across skewed size vectors including
zero-sized ranks, unrolled vs compiled executors bit-for-bit, and the MoE
alltoallv expert-dispatch transport against the einsum oracle."""
from __future__ import annotations


def test_pallgatherv_skewed_and_zero_ranks(dist):
    """Ragged allgather on 4 ranks: every rank holds its segment in the
    valid prefix of a max-padded shard; garbage beyond the prefix must not
    leak into the gathered result, for both executors."""
    dist(
        """
import numpy as np, jax, jax.numpy as jnp
from jax.sharding import Mesh, PartitionSpec as P
from repro.comm import pallgatherv

n = 4
mesh = Mesh(np.array(jax.devices()[:n]), ("x",))
rng = np.random.RandomState(0)
for sizes in [(3, 1, 0, 2), (1, 1, 1, 1), (5, 0, 0, 7)]:
    smax = max(sizes); total = sum(sizes); E = 3
    full = rng.randn(total, E).astype(np.float32)
    off = np.concatenate([[0], np.cumsum(sizes)])
    loc = np.full((n, smax, E), 99.0, np.float32)  # poison beyond prefix
    for r in range(n):
        loc[r, :sizes[r]] = full[off[r]:off[r + 1]]
    for compiled in (False, True):
        f = jax.shard_map(
            lambda v, c=compiled: pallgatherv(v, "x", sizes=sizes, compiled=c),
            mesh=mesh, in_specs=P("x"), out_specs=P(), check_vma=False)
        out = np.asarray(f(jnp.asarray(loc.reshape(n * smax, E))))
        assert out.shape == (total, E), (out.shape, total)
        assert np.array_equal(out, full), (sizes, compiled)
print("PASS")
""",
        devices=4,
    )


def test_palltoallv_compact_all_algos(dist):
    """Compact-layout alltoallv on 4 ranks across random block matrices,
    including a rank that receives nothing and a rank that sends nothing,
    for {auto, pairwise, ring} x {unrolled, compiled} — bit-exact against
    the host-side reshuffle."""
    dist(
        """
import numpy as np, jax, jax.numpy as jnp
from jax.sharding import Mesh, PartitionSpec as P
from repro.comm import palltoallv

n, E = 4, 2
mesh = Mesh(np.array(jax.devices()[:n]), ("x",))
rng = np.random.RandomState(1)
for trial in range(3):
    m = rng.randint(0, 4, size=(n, n)).astype(np.int64)
    if trial == 1: m[:, 2] = 0   # rank 2 receives nothing
    if trial == 2: m[1, :] = 0   # rank 1 sends nothing
    if m.sum() == 0: m[0, 0] = 1
    send = m.sum(axis=1); recv = m.sum(axis=0)
    smax = max(int(send.max()), 1); rmax = max(int(recv.max()), 1)
    blocks = {(s, d): rng.randn(int(m[s, d]), E).astype(np.float32)
              for s in range(n) for d in range(n)}
    xin = np.full((n, smax, E), 88.0, np.float32)
    for s in range(n):
        xin[s, :send[s]] = np.concatenate(
            [blocks[(s, d)] for d in range(n)] + [np.zeros((0, E), np.float32)])
    exp = np.zeros((n, rmax, E), np.float32)
    for r in range(n):
        exp[r, :recv[r]] = np.concatenate(
            [blocks[(s, r)] for s in range(n)] + [np.zeros((0, E), np.float32)])
    for compiled in (False, True):
        for algo in ("auto", "pairwise_alltoallv", "ring_alltoallv"):
            f = jax.shard_map(
                lambda v, a=algo, c=compiled: palltoallv(
                    v, "x", sizes=m.tolist(), algo=a, compiled=c),
                mesh=mesh, in_specs=P("x"), out_specs=P("x"), check_vma=False)
            out = np.asarray(f(jnp.asarray(xin.reshape(n * smax, E))))
            out = out.reshape(n, rmax, E)
            assert np.array_equal(out, exp), (trial, algo, compiled)
print("PASS")
""",
        devices=4,
    )


def test_palltoallv_padded_round_trip(dist):
    """Padded-in -> padded-out layout on a matrix with an all-zero source
    row: block (s, d) lands at out[d][s]'s valid prefix, padding inert."""
    dist(
        """
import numpy as np, jax, jax.numpy as jnp
from jax.sharding import Mesh, PartitionSpec as P
from repro.comm import palltoallv

n, E = 4, 2
mesh = Mesh(np.array(jax.devices()[:n]), ("x",))
rng = np.random.RandomState(2)
m = np.array([[2, 0, 1, 3], [0, 0, 0, 0], [1, 4, 0, 0], [2, 2, 2, 2]], np.int64)
bmax = int(m.max())
blocks = {(s, d): rng.randn(int(m[s, d]), E).astype(np.float32)
          for s in range(n) for d in range(n)}
xin = np.full((n, n, bmax, E), 77.0, np.float32)
for s in range(n):
    for d in range(n):
        xin[s, d, :m[s, d]] = blocks[(s, d)]
exp = np.zeros((n, n, bmax, E), np.float32)
for r in range(n):
    for s in range(n):
        exp[r, s, :m[s, r]] = blocks[(s, r)]
f = jax.shard_map(
    lambda v: palltoallv(v, "x", sizes=m.tolist(), in_padded=True, out_padded=True),
    mesh=mesh, in_specs=P("x"), out_specs=P("x"), check_vma=False)
out = np.asarray(f(jnp.asarray(xin.reshape(n * n, bmax, E)))).reshape(n, n, bmax, E)
assert np.array_equal(out, exp)
print("PASS")
""",
        devices=4,
    )


def test_moe_alltoallv_matches_einsum_oracle(dist):
    """The explicit expert-parallel transport (moe_dispatch='alltoallv',
    E=6 over 4 ranks -> ragged partition (2,2,1,1), shared experts on)
    reproduces the single-host einsum path to a few f32 ulps, aux loss included
    (me/ce are pmean'd, so aux is the global-batch value)."""
    dist(
        """
import dataclasses
import numpy as np, jax, jax.numpy as jnp
from jax.sharding import Mesh, PartitionSpec as P
from repro.configs.base import ModelConfig
from repro.models import moe as moe_lib

cfg = ModelConfig(
    name="t", family="moe", num_layers=1, d_model=8, num_heads=2,
    num_kv_heads=2, d_ff=16, vocab_size=32, num_experts=6,
    experts_per_token=2, moe_group_size=8, num_shared_experts=1)
cfga = dataclasses.replace(cfg, moe_dispatch="alltoallv")
p = moe_lib.init_moe(jax.random.PRNGKey(0), cfg, jnp.float32)
B, T, D = 8, 16, 8
x = jax.random.normal(jax.random.PRNGKey(1), (B, T, D), jnp.float32)
y_ref, aux_ref = moe_lib.moe_ffn(p, x, cfg)

mesh = Mesh(np.array(jax.devices()[:4]), ("dp",))
f = jax.shard_map(
    lambda pp, xx: moe_lib.moe_ffn(pp, xx, cfga, axis_name="dp"),
    mesh=mesh, in_specs=(P(), P("dp")), out_specs=(P("dp"), P()),
    check_vma=False)
y, aux = f(p, x)
err = float(jnp.max(jnp.abs(y - y_ref)))
aerr = abs(float(aux) - float(aux_ref))
# the expert outputs are combined in another order than the einsum path's
# (XLA's CPU backend picks the order), so allow a few f32 ulps at the scale
# of the output; a routing or dispatch error moves whole values instead
ulp = float(np.spacing(np.float32(jnp.max(jnp.abs(y_ref)))))
assert err <= 4 * ulp, (err, ulp)
assert aerr < 1e-6, (float(aux), float(aux_ref))
print("PASS")
""",
        devices=4,
    )
