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
