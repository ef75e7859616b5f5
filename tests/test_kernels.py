"""Per-kernel shape/dtype sweeps against the pure-jnp oracles (interpret)."""
from __future__ import annotations

import jax.numpy as jnp
import numpy as np
import pytest
try:
    from hypothesis import given, settings, strategies as st
except ImportError:  # offline fallback — see tests/_compat.py
    from _compat import given, settings, strategies as st

from repro.analysis.jaxpr import pallas_eqns
from repro.kernels import ops, ref

RNG = np.random.RandomState(0)


@settings(max_examples=25, deadline=None)
@given(
    n=st.integers(1, 70_000),
    chunk=st.sampled_from([256, 1024, 8192]),
    dt=st.sampled_from(["float32", "bfloat16", "int32"]),
)
def test_chunked_copy_property(n, chunk, dt):
    x = jnp.asarray(RNG.randn(n) * 100, jnp.dtype(dt))
    got = ops.chunked_copy(x, chunk_elems=chunk)
    np.testing.assert_array_equal(np.asarray(got), np.asarray(ref.chunked_copy_ref(x)))


@pytest.mark.parametrize("n", [131, 4096, 100_000])
@pytest.mark.parametrize("dt", [jnp.float32, jnp.bfloat16])
def test_param_update(n, dt):
    w = jnp.asarray(RNG.randn(n), dt)
    u = jnp.asarray(RNG.randn(n), dt)
    np.testing.assert_allclose(
        np.asarray(ops.mix(w, u, 0.25), np.float32),
        np.asarray(ref.mix_ref(w, u, 0.25), np.float32),
        rtol=1e-2, atol=1e-2,
    )
    np.testing.assert_allclose(
        np.asarray(ops.scaled_add(w, u, 0.01), np.float32),
        np.asarray(ref.scaled_add_ref(w, u, 0.01), np.float32),
        rtol=1e-2, atol=1e-2,
    )


CASES = [
    # B, T, S, H, KV, hd, causal, window, prefix, bq, bk
    (2, 128, 128, 4, 2, 32, True, None, 0, 64, 64),
    (1, 256, 256, 4, 1, 64, True, 64, 0, 64, 64),
    (2, 128, 128, 2, 2, 32, True, None, 32, 64, 32),
    (1, 128, 128, 4, 4, 32, False, None, 0, 128, 128),
    (1, 64, 64, 8, 2, 16, True, 32, 16, 32, 32),
]


@pytest.mark.parametrize("case", CASES)
@pytest.mark.parametrize("dt", [jnp.float32, jnp.bfloat16])
def test_flash_attention(case, dt):
    B, T, S, H, KV, hd, causal, window, prefix, bq, bk = case
    q = jnp.asarray(RNG.randn(B, T, H, hd), dt)
    k = jnp.asarray(RNG.randn(B, S, KV, hd), dt)
    v = jnp.asarray(RNG.randn(B, S, KV, hd), dt)
    got = ops.flash_attention(q, k, v, causal=causal, window=window, prefix=prefix, bq=bq, bk=bk)
    want = ref.flash_attention_ref(q, k, v, causal=causal, window=window, prefix=prefix)
    tol = 2e-4 if dt == jnp.float32 else 3e-2
    np.testing.assert_allclose(
        np.asarray(got, np.float32), np.asarray(want, np.float32), rtol=tol, atol=tol
    )


def test_flash_matches_model_attention_path():
    """Kernel agrees with the model's XLA-portable chunked softmax."""
    from repro.models.layers import AttnSpec, _chunked_sdpa

    B, T, H, KV, hd = 1, 256, 4, 2, 32
    q = jnp.asarray(RNG.randn(B, T, H, hd), jnp.float32)
    k = jnp.asarray(RNG.randn(B, T, KV, hd), jnp.float32)
    v = jnp.asarray(RNG.randn(B, T, KV, hd), jnp.float32)
    spec = AttnSpec(num_heads=H, num_kv_heads=KV, head_dim=hd, window=64)
    a = _chunked_sdpa(q * hd**-0.5 / hd**-0.5, k, v, spec, prefix_len=0, block=64)
    b = ops.flash_attention(q, k, v, causal=True, window=64, bq=64, bk=64)
    np.testing.assert_allclose(np.asarray(a), np.asarray(b), rtol=2e-4, atol=2e-4)


@settings(max_examples=25, deadline=None)
@given(
    b=st.integers(1, 24),
    c=st.sampled_from([1, 7, 128, 1013, 4096]),
    lo_frac=st.floats(0.0, 1.0),
    combine=st.booleans(),
    dt=st.sampled_from(["float32", "bfloat16", "int32"]),
)
def test_fused_combine_property(b, c, lo_frac, combine, dt):
    """The compiled executor's merge kernel vs the pure-jnp oracle:
    accumulate (mode 2) or overwrite (mode 1) on the [lo, hi) row window,
    bit-exact passthrough (mode 0) elsewhere."""
    import jax.numpy as jnp

    cur = jnp.asarray(RNG.randn(b, c) * 50, jnp.dtype(dt))
    recv = jnp.asarray(RNG.randn(b, c) * 50, jnp.dtype(dt))
    lo = int(lo_frac * b)
    hi = min(b, lo + max(1, b // 2))
    rows = jnp.arange(b, dtype=jnp.int32)
    valid = (rows >= lo) & (rows < hi)
    mode = (valid.astype(jnp.int32) * (2 if combine else 1)).reshape(b, 1)
    got = ops.fused_combine(cur, recv, mode)
    want = ref.fused_combine_ref(cur, recv, mode)
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))


def test_fused_combine_update_window():
    """fused_combine_update applies exactly the [start+lo, start+hi) rows of
    a (K, chunk) buffer and leaves every other row bit-identical."""
    import jax
    import jax.numpy as jnp
    from repro.kernels.combine_update import fused_combine_update

    K, B, C = 11, 4, 33
    buf = jnp.asarray(RNG.randn(K, C).astype(np.float32))
    recv = jnp.asarray(RNG.randn(B, C).astype(np.float32))
    for start, lo, hi, combine in [(3, 1, 4, True), (7, 0, 4, False), (0, 2, 2, True)]:
        out = jax.jit(
            lambda b, r, s=start, l=lo, h=hi, cb=combine: fused_combine_update(
                b, r, jnp.int32(s), jnp.int32(l), jnp.int32(h), combine=cb
            )
        )(buf, recv)
        want = np.asarray(buf).copy()
        if hi > lo:
            win = np.asarray(recv)[lo:hi]
            if combine:
                want[start + lo: start + hi] += win
            else:
                want[start + lo: start + hi] = win
        np.testing.assert_array_equal(np.asarray(out), want, err_msg=str((start, lo, hi, combine)))


def test_chunked_copy_never_materializes_pad():
    """Satellite regression: the ragged tail rides the grid's masked final
    block — no jnp.concatenate pad copy appears in the jaxpr (it was a full
    extra HBM pass of the buffer)."""
    import jax
    import jax.numpy as jnp
    from repro.kernels.chunked_copy import chunked_copy

    x = jnp.zeros(1000, jnp.float32)  # 1000 % 256 != 0: ragged tail
    jaxpr = str(jax.make_jaxpr(
        lambda v: chunked_copy(v, chunk_elems=256, interpret=True))(x))
    assert "concatenate" not in jaxpr
    assert "pad" not in jaxpr


# ---------------------------------------------------------------------------
# Mamba selective scan


def _mamba_args(Bt, T, di, N, seed=0):
    """dt, x, B, C, A, h0 as the model makes them: dt > 0, A < 0, h0 != 0."""
    rng = np.random.RandomState(seed)
    f = lambda *s: rng.randn(*s).astype(np.float32)
    return tuple(jnp.asarray(a) for a in (
        np.log1p(np.exp(f(Bt, T, di) - 1.0)), f(Bt, T, di), f(Bt, T, N), f(Bt, T, N),
        -np.exp(0.5 * f(di, N)), f(Bt, di, N)))


def _mamba_sequential(dt, x, Bm, Cm, A, h0):
    """One step at a time, in sequence order: the plainest form."""
    import jax

    def step(h, xs):
        d, u, b, c = xs
        h = jnp.exp(d[..., None] * A) * h + (d * u)[..., None] * b[:, None, :]
        return h, jnp.einsum("bdn,bn->bd", h, c)

    h_last, ys = jax.lax.scan(step, h0, tuple(jnp.moveaxis(a, 1, 0) for a in (dt, x, Bm, Cm)))
    return jnp.moveaxis(ys, 0, 1), h_last


@pytest.mark.parametrize("Bt,T,di,N,chunk", [
    (2, 200, 1408, 16, 128),  # an odd length: 2 blocks of 128, the last padded; 11 di tiles
    (2, 36, 96, 16, 16),      # a short prompt: one block of 40; di padded to 128
    (1, 40, 256, 4, 8),       # one block of the whole sequence; 2 lane columns
    (2, 48, 384, 8, 16),      # one block of 48 steps; 3 lane columns
])
def test_mamba_scan(Bt, T, di, N, chunk):
    """y, h_last and the gradients with respect to dt, x, B, C, A and h0 of
    ``ops.mamba_scan`` against the jnp chunked scan and a sequential scan.
    Every shape takes the kernels; padded steps and channels are sliced off."""
    import functools

    import jax

    from repro.kernels.mamba_scan import time_blocks

    args = _mamba_args(Bt, T, di, N)
    rng = np.random.RandomState(1)
    cts = (jnp.asarray(rng.randn(Bt, T, di), jnp.float32),
           jnp.asarray(rng.randn(Bt, di, N), jnp.float32))
    fn = lambda *a: ops.mamba_scan(*a, chunk=chunk)
    eqns = pallas_eqns(jax.make_jaxpr(fn)(*args).jaxpr)
    assert [eq.params["name"] for eq in eqns] == ["mamba_scan_fwd"]

    @functools.partial(jax.jit, static_argnums=0)
    def run(f, args, cts):
        out, vjp = jax.vjp(f, *args)
        return out + vjp(cts)

    block = time_blocks(T, chunk)[0]
    got = run(fn, args, cts)
    chunked = run(functools.partial(ref.mamba_scan_ref, chunk=block if T % block == 0 else T),
                  args, cts)
    sequential = run(_mamba_sequential, args, cts)
    names = ("y", "h_last", "d_dt", "dx", "dB", "dC", "dA", "dh0")
    for name, g, c, s in zip(names, got, chunked, sequential):
        assert g.shape == s.shape, name
        for want in (c, s):
            scale = float(jnp.abs(want).max())
            np.testing.assert_allclose(np.asarray(g), np.asarray(want), rtol=2e-5,
                                       atol=2e-5 * scale, err_msg=name)


def test_mamba_scan_splits_over_the_mesh(dist):
    """Traced under a multi-device mesh (``dist.on_mesh``, as the trainer
    and the engine trace), the scan runs as a shard_map: rows over the data
    axis and channels over the model axis where they divide, replicated
    where they do not. Values and gradients match a sequential scan."""
    dist("""
import jax, jax.numpy as jnp, numpy as np
from jax.sharding import NamedSharding, PartitionSpec as P
sys.path.insert(0, "tests")
from test_kernels import _mamba_args, _mamba_sequential
from repro.dist import on_mesh
from repro.kernels import ops

mesh = jax.make_mesh((2, 2), ("data", "model"),
                     axis_types=(jax.sharding.AxisType.Auto,) * 2)

def with_grads(f):
    def run(args, cts):
        out, vjp = jax.vjp(f, *args)
        return out + vjp(cts)
    return run

for Bt, di in ((2, 256), (1, 96)):
    args = _mamba_args(Bt, 24, di, 8)
    rng = np.random.RandomState(1)
    cts = (jnp.asarray(rng.randn(Bt, 24, di), jnp.float32),
           jnp.asarray(rng.randn(Bt, di, 8), jnp.float32))
    want = jax.jit(with_grads(_mamba_sequential))(args, cts)
    args, cts = jax.device_put((args, cts), NamedSharding(mesh, P()))
    split = jax.jit(on_mesh(with_grads(lambda *a: ops.mamba_scan(*a, chunk=16)), mesh))
    assert "shard_map" in str(split.trace(args, cts).jaxpr), (Bt, di)
    for g, w in zip(split(args, cts), want):
        np.testing.assert_allclose(np.asarray(g), np.asarray(w), rtol=2e-5,
                                   atol=2e-5 * float(jnp.abs(w).max()))
print("PASS")
""", devices=4)


# ---------------------------------------------------------------------------
# mLSTM chunkwise recurrence


def _mlstm_args(B, T, H, hd, dtype=jnp.float32, state=True):
    """q, k, v, lf, li, C0, n0 as the model makes them: q and k scaled by
    hd^-0.5, log-sigmoid gates with the forget gates mostly open."""
    import jax

    rng = np.random.RandomState(0)
    f = lambda *s: rng.randn(*s).astype(np.float32)
    qkv = [jnp.asarray(f(B, T, H, hd) * s, dtype) for s in (hd ** -0.5, hd ** -0.5, 1.0)]
    gates = [jax.nn.log_sigmoid(jnp.asarray(f(B, T, H) + b)) for b in (2.0, 0.0)]
    scale = 0.1 if state else 0.0
    C0, n0 = (jnp.asarray(f(*s) * scale) for s in ((B, H, hd, hd), (B, H, hd)))
    return (*qkv, *gates, C0, n0)


def _mlstm_sequential(q, k, v, lf, li, C0, n0):
    """mlstm_step's recurrence one token at a time, in f32: the plainest form."""
    import jax

    def step(carry, xs):
        C, n = carry
        qt, kt, vt, f, i = xs
        f, i = jnp.exp(f)[..., None], jnp.exp(i)[..., None]
        C = C * f[..., None] + i[..., None] * kt[..., :, None] * vt[..., None, :]
        n = n * f + i * kt
        num = jnp.einsum("bhd,bhde->bhe", qt, C)
        nq = jnp.einsum("bhd,bhd->bh", qt, n)
        return (C, n), num / (jnp.abs(nq)[..., None] + 1.0)

    xs = tuple(jnp.moveaxis(a.astype(jnp.float32), 1, 0) for a in (q, k, v, lf, li))
    (C, n), hs = jax.lax.scan(step, (C0, n0), xs)
    return jnp.moveaxis(hs, 0, 1), (C, n)


def _mlstm_cts(B, T, H, hd):
    """Cotangents of h and of the final (C, n)."""
    rng = np.random.RandomState(1)
    dh, dC, dn = (jnp.asarray(rng.randn(*s), jnp.float32)
                  for s in ((B, T, H, hd), (B, H, hd, hd), (B, H, hd)))
    return dh, (dC, dn)


@pytest.mark.parametrize("B,T,H,hd,chunk,dtype,state", [
    (1, 256, 1, 128, 128, "float32", True),   # two whole chunks, a lane-wide head
    (2, 300, 2, 64, 128, "float32", True),    # an odd length: 3 chunks, the last padded
    (2, 37, 2, 32, 16, "float32", False),     # a short prompt: one chunk of 40 steps
    (1, 256, 2, 128, 128, "bfloat16", True),  # the model's dtype: bf16 operands on the MXU
])
def test_mlstm_scan(B, T, H, hd, chunk, dtype, state):
    """h, the final (C, n) and the gradients with respect to q, k, v, lf,
    li, C0 and n0 of ``ops.mlstm_scan`` against the jnp chunked scan and a
    token-by-token loop. Every shape takes the kernels: padded steps and
    head columns are sliced off. With f32 inputs every dot is f32; with bf16
    ones the kernel rounds dot operands to bf16, as XLA's DEFAULT precision
    does to the model's f32 einsums on a TPU."""
    import functools

    import jax

    from repro.kernels.mamba_scan import time_blocks

    args = _mlstm_args(B, T, H, hd, jnp.dtype(dtype), state)
    cts = _mlstm_cts(B, T, H, hd)
    fn = lambda *a: ops.mlstm_scan(*a, chunk=chunk)
    eqns = pallas_eqns(jax.make_jaxpr(fn)(*args).jaxpr)
    assert [(eq.params["name"], len(eq.outvars)) for eq in eqns] == [("mlstm_scan_fwd", 3)]

    @functools.partial(jax.jit, static_argnums=0)
    def run(f, args, cts):
        (h, (C, n)), vjp = jax.vjp(f, *args)
        return (h.astype(jnp.float32), C, n) + vjp((cts[0].astype(h.dtype), cts[1]))

    block = time_blocks(T, chunk)[0]
    got = run(fn, args, cts)
    chunked = run(functools.partial(ref.mlstm_scan_ref, chunk=block if T % block == 0 else T),
                  args, cts)
    sequential = run(_mlstm_sequential, args, cts)
    tol = 2e-5 if dtype == "float32" else 2e-2
    names = ("h", "C", "n", "dq", "dk", "dv", "d_lf", "d_li", "dC0", "dn0")
    for name, g, c, s in zip(names, got, chunked, sequential):
        assert g.shape == s.shape, name
        for want in (c, s):
            scale = float(jnp.abs(want).max())
            np.testing.assert_allclose(np.asarray(g, np.float32), np.asarray(want, np.float32),
                                       rtol=tol, atol=tol * scale, err_msg=name)


def test_mlstm_scan_splits_over_the_mesh(dist):
    """Traced under a multi-device mesh (``dist.on_mesh``), the scan runs
    as a shard_map: rows over the data axis and heads over the model axis
    where they divide, replicated where they do not. Values and gradients
    match the token-by-token loop."""
    dist("""
import jax, jax.numpy as jnp, numpy as np
from jax.sharding import NamedSharding, PartitionSpec as P
sys.path.insert(0, "tests")
from test_kernels import _mlstm_args, _mlstm_cts, _mlstm_sequential
from repro.dist import on_mesh
from repro.kernels import ops

mesh = jax.make_mesh((2, 2), ("data", "model"),
                     axis_types=(jax.sharding.AxisType.Auto,) * 2)

def with_grads(f):
    def run(args, cts):
        (h, (C, n)), vjp = jax.vjp(f, *args)
        return (h, C, n) + vjp(cts)
    return run

for B, H in ((2, 2), (1, 3)):
    args, cts = _mlstm_args(B, 24, H, 32), _mlstm_cts(B, 24, H, 32)
    want = jax.jit(with_grads(_mlstm_sequential))(args, cts)
    args, cts = jax.device_put((args, cts), NamedSharding(mesh, P()))
    split = jax.jit(on_mesh(with_grads(lambda *a: ops.mlstm_scan(*a, chunk=16)), mesh))
    assert "shard_map" in str(split.trace(args, cts).jaxpr), (B, H)
    for g, w in zip(split(args, cts), want):
        np.testing.assert_allclose(np.asarray(g), np.asarray(w), rtol=2e-5,
                                   atol=2e-5 * float(jnp.abs(w).max()))
print("PASS")
""", devices=4)


def _launches_in_grad_step(cfg):
    """The Pallas kernels of ``cfg``'s remat grad step (traced on abstract
    shapes at 2 x 2048, nothing computed) by name and number of outputs,
    with how often each runs in a step. The layer stack is a scan: a kernel
    in its body runs once per layer; none runs outside it."""
    import collections

    import jax
    from repro.models import Model

    m = Model(cfg)
    params = jax.eval_shape(m.init, jax.random.PRNGKey(0))
    tok = jax.ShapeDtypeStruct((2, 2048), jnp.int32)
    jx = jax.make_jaxpr(jax.grad(lambda p, b: m.loss(p, b, remat=True)[0]))(
        params, {"tokens": tok, "labels": tok})
    counts, in_scans = collections.Counter(), 0
    for eq in jx.jaxpr.eqns:
        if eq.primitive.name == "scan":
            eqns = pallas_eqns(eq.params["jaxpr"].jaxpr)
            in_scans += len(eqns)
            for k in eqns:
                counts[k.params["name"], len(k.outvars)] += eq.params["length"]
    assert len(pallas_eqns(jx.jaxpr)) == in_scans
    return counts


@pytest.mark.parametrize("arch,layers", [("hymba-1.5b", 8), ("xlstm-350m", 0)])
def test_mamba_scan_in_grad_step(arch, layers):
    """The grad step of the benchmark's configuration at published widths:
    each of hymba's 8 hybrid layers runs the forward kernel in the forward
    pass and again in the recompute, and the backward kernel once; xLSTM
    runs no Mamba kernel."""
    import dataclasses

    from repro.configs import get_config

    cfg = get_config(arch)
    if layers:
        cfg = dataclasses.replace(cfg, num_layers=layers)
    counts = {name: n for (name, _), n in _launches_in_grad_step(cfg).items()
              if name.startswith("mamba_scan")}
    want = {"mamba_scan_fwd": 2 * layers, "mamba_scan_bwd": layers} if layers else {}
    assert counts == want


@pytest.mark.parametrize("arch,mlstm_layers", [
    ("xlstm-350m-smoke", 14), ("xlstm-350m", 21), ("hymba-1.5b-smoke", 0)])
def test_mlstm_scan_in_grad_step(arch, mlstm_layers):
    """Each mLSTM layer of the remat grad step runs three kernels: the
    forward without chunk states in the forward pass (the custom VJP's remat
    optimisation), the forward that writes them in the recompute inside the
    backward pass, and the backward kernel. Other families run none."""
    from repro.configs import get_config

    counts = {key: n for key, n in _launches_in_grad_step(get_config(arch)).items()
              if key[0].startswith("mlstm_scan")}
    n = mlstm_layers
    want = {("mlstm_scan_fwd", 3): n, ("mlstm_scan_fwd", 5): n, ("mlstm_scan_bwd", 6): n}
    assert counts == (want if n else {})


# ---------------------------------------------------------------------------
# interpret-mode resolution: one helper, every call site


def test_resolve_interpret_tiers():
    """None defers to the backend probe; explicit bools always win."""
    from repro.kernels.ops import on_tpu, resolve_interpret

    assert resolve_interpret(True) is True
    assert resolve_interpret(False) is False
    # in the CPU CI environment the default must interpret; on real TPU
    # hardware the same None must compile
    assert resolve_interpret(None) is (not on_tpu())


def test_cpu_traces_never_embed_compiled_pallas():
    """Satellite regression: with interpret left to default on a CPU
    backend, NO pallas_call in any kernel entry point's jaxpr may carry
    interpret=False — that trace would abort at compile time."""
    import jax
    from repro.kernels.ops import on_tpu

    if on_tpu():
        pytest.skip("CPU-backend regression; interpret defaults off on TPU")

    x = jnp.zeros(1000, jnp.float32)
    w = jnp.zeros(128, jnp.float32)
    q = jnp.zeros((1, 64, 2, 16), jnp.float32)
    kv = jnp.zeros((1, 64, 1, 16), jnp.float32)
    mode = jnp.zeros((4, 1), jnp.int32)
    cases = [
        (lambda: ops.chunked_copy(x, chunk_elems=256), "chunked_copy"),
        (lambda: ops.mix(w, w, 0.5), "mix"),
        (lambda: ops.scaled_add(w, w, 0.1), "scaled_add"),
        (lambda: ops.fused_combine(jnp.zeros((4, 8)), jnp.ones((4, 8)), mode),
         "fused_combine"),
        (lambda: ops.flash_attention(q, kv, kv, causal=True, bq=32, bk=32),
         "flash_attention"),
        (lambda: ops.mamba_scan(*_mamba_args(1, 16, 128, 4), chunk=8), "mamba_scan"),
        (lambda: ops.mlstm_scan(*_mlstm_args(1, 16, 1, 32), chunk=8), "mlstm_scan"),
    ]
    found = 0
    for fn, name in cases:
        jx = jax.make_jaxpr(lambda _=None: fn())()
        eqns = pallas_eqns(jx.jaxpr)
        assert eqns, f"{name}: no pallas_call found in trace"
        for eq in eqns:
            assert eq.params["interpret"] is not False, (
                f"{name}: CPU trace embeds interpret=False"
            )
        found += len(eqns)
    assert found >= len(cases)


def test_inkernel_replay_honors_resolve_interpret():
    """The in-kernel executor's emulation kernel goes through the same
    resolver: its single pallas_call interprets on CPU."""
    import jax
    from repro.core.schedules import build, lower_schedule
    from repro.kernels.inkernel_collective import inkernel_replay_shared
    from repro.kernels.ops import on_tpu

    if on_tpu():
        pytest.skip("CPU-backend regression; interpret defaults off on TPU")

    n, K = 4, 4
    low = lower_schedule(build("pipelined_chain", n, root=0, num_chunks=K))
    shared = jnp.zeros((n, K, 8), jnp.float32)
    jx = jax.make_jaxpr(lambda s: inkernel_replay_shared(low, s))(shared)
    eqns = pallas_eqns(jx.jaxpr)
    assert len(eqns) == 1, "replay must stay a single launch"
    assert eqns[0].params["interpret"] is not False


def test_raw_kernel_defaults_follow_the_backend(monkeypatch):
    """Left at None, a raw kernel's interpret flag follows the backend: told
    that it runs on a TPU, each kernel traces a compiled pallas_call."""
    import importlib

    import jax

    # the package re-exports same-named functions; take the modules
    mod = lambda name: importlib.import_module(f"repro.kernels.{name}")
    chunked_copy, flash_attention = mod("chunked_copy"), mod("flash_attention")
    param_update = mod("param_update")
    monkeypatch.setattr(mod("interpret"), "on_tpu", lambda: True)
    x = jnp.zeros(1003, jnp.float32)  # shapes no other test traces
    q = jnp.zeros((1, 32, 2, 16), jnp.float32)
    kv = jnp.zeros((1, 32, 1, 16), jnp.float32)
    cases = {
        "mix": lambda: param_update.mix(x, x, 0.5),
        "scaled_add": lambda: param_update.scaled_add(x, x, 0.1),
        "chunked_copy": lambda: chunked_copy.chunked_copy(x, chunk_elems=256),
        "flash_attention": lambda: flash_attention.flash_attention(
            q, kv, kv, bq=32, bk=32),
        "mamba_scan": lambda: mod("mamba_scan").mamba_scan(
            *_mamba_args(1, 24, 128, 4), chunk=8),
        "mlstm_scan": lambda: mod("mlstm_scan").mlstm_scan(
            *_mlstm_args(1, 24, 1, 32), chunk=8),
    }
    for name, fn in cases.items():
        eqns = pallas_eqns(jax.make_jaxpr(fn)().jaxpr)
        assert eqns, name
        assert all(eq.params["interpret"] is False for eq in eqns), name


def test_inkernel_replay_refuses_compiled_path_off_tpu():
    """interpret=False asks for the RDMA kernel; off the TPU that is an
    error, never a quiet switch to the emulation."""
    from repro.core.schedules import build, lower_schedule
    from repro.kernels.inkernel_collective import inkernel_replay

    low = lower_schedule(build("pipelined_chain", 4, root=0, num_chunks=4))
    with pytest.raises(ValueError, match="needs a TPU backend"):
        inkernel_replay(low, jnp.zeros((4, 8), jnp.float32), "x", interpret=False)
