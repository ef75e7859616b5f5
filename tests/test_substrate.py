"""Data pipeline, bucketing, checkpointing, HLO analysis, serving engine."""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np
import pytest
try:
    from hypothesis import given, settings, strategies as st
except ImportError:  # offline fallback — see tests/_compat.py
    from _compat import given, settings, strategies as st

from repro.configs import get_config
from repro.core.bucketing import pack_buckets, plan_buckets, unpack_buckets
from repro.data.pipeline import SyntheticZipf, batches, make_source
from repro.models import Model
from repro.serve.engine import Engine
from repro.train.checkpoint import latest_step, restore_checkpoint, save_checkpoint


# ------------------------------ data ---------------------------------------


def test_data_deterministic_and_shifted():
    cfg = get_config("minitron-8b-smoke")
    src = make_source(cfg, seed=3)
    it1 = batches(src, cfg, batch=4, seq=32)
    it2 = batches(src, cfg, batch=4, seq=32)
    b1, b2 = next(it1), next(it2)
    np.testing.assert_array_equal(np.asarray(b1["tokens"]), np.asarray(b2["tokens"]))
    # labels are next-token shifted
    raw = src.batch(0, 4, 32)
    np.testing.assert_array_equal(np.asarray(b1["tokens"]), raw[:, :-1])
    np.testing.assert_array_equal(np.asarray(b1["labels"]), raw[:, 1:])


def test_zipf_is_skewed():
    src = SyntheticZipf(1000, seed=0)
    toks = src.batch(0, 64, 128).ravel()
    assert (toks < 10).mean() > 0.2  # head-heavy
    assert toks.max() < 1000


def test_memmap_source(tmp_path):
    from repro.data.pipeline import MemmapTokens

    path = str(tmp_path / "toks.npy")
    np.save(path, np.arange(10_000, dtype=np.int32) % 257)
    src = MemmapTokens(path, seed=1)
    b = src.batch(0, 3, 16)
    assert b.shape == (3, 17) and b.dtype == np.int32


def test_vlm_audio_batches_have_embeds():
    for arch in ("paligemma-3b-smoke", "whisper-large-v3-smoke"):
        cfg = get_config(arch)
        b = next(batches(make_source(cfg), cfg, batch=2, seq=16))
        assert "embeds" in b and b["embeds"].shape[0] == 2


# ---------------------------- bucketing -------------------------------------


@settings(max_examples=30, deadline=None)
@given(
    sizes=st.lists(st.integers(1, 300), min_size=1, max_size=12),
    bucket_bytes=st.sampled_from([64, 256, 4096]),
)
def test_bucket_roundtrip(sizes, bucket_bytes):
    rng = np.random.RandomState(0)
    tree = {
        f"p{i}": jnp.asarray(rng.randn(s), jnp.float32 if i % 2 else jnp.bfloat16)
        for i, s in enumerate(sizes)
    }
    spec = plan_buckets(tree, bucket_bytes)
    bks = pack_buckets(tree, spec)
    # dtype purity per bucket
    for b, dt in zip(bks, spec.bucket_dtypes):
        assert b.dtype == dt
    out = unpack_buckets(bks, spec)
    for k in tree:
        np.testing.assert_array_equal(np.asarray(out[k], np.float32), np.asarray(tree[k], np.float32))


# ---------------------------- checkpoint ------------------------------------


def test_checkpoint_roundtrip(tmp_path):
    tree = {
        "a": jnp.arange(10, dtype=jnp.float32),
        "nest": {"b": jnp.ones((3, 4), jnp.bfloat16) * 1.5, "step": jnp.asarray(7, jnp.int32)},
        "lst": [jnp.zeros((2,)), jnp.full((5,), 2.0, jnp.bfloat16)],
    }
    d = str(tmp_path / "ck")
    save_checkpoint(d, 42, tree, extra={"note": "x"})
    assert latest_step(d) == 42
    like = jax.tree.map(lambda l: jax.ShapeDtypeStruct(l.shape, l.dtype), tree)
    out = restore_checkpoint(d, 42, like)
    for a, b in zip(jax.tree.leaves(tree), jax.tree.leaves(out)):
        assert a.dtype == b.dtype
        np.testing.assert_array_equal(np.asarray(a, np.float32), np.asarray(b, np.float32))


# ---------------------------- hlo analysis ----------------------------------


def test_hlo_parser_trip_counts():
    from repro.analysis.hlo import parse_hlo

    def f(ws, x):
        def body(x, w):
            return jax.nn.relu(x @ w), ()
        x, _ = jax.lax.scan(body, x, ws)
        return x.sum()

    ws = jax.ShapeDtypeStruct((5, 64, 64), jnp.float32)
    x = jax.ShapeDtypeStruct((8, 64), jnp.float32)
    compiled = jax.jit(f).lower(ws, x).compile()
    mod = parse_hlo(compiled.as_text())
    got = mod.dot_flops()
    want = 5 * 2 * 8 * 64 * 64
    assert abs(got - want) / want < 1e-6, (got, want)
    assert not mod.unknown_trip


def test_roofline_terms_positive():
    import glob
    import json

    rows = [json.load(open(p)) for p in glob.glob("experiments/dryrun/*.json")]
    if not rows:
        pytest.skip("no dry-run artifacts yet")
    for r in rows:
        assert r["t_compute_s"] > 0
        assert r["t_memory_s"] > 0
        assert r["bottleneck"] in ("compute", "memory", "collective")


# ------------------------------ serving -------------------------------------


def test_engine_greedy_generation():
    cfg = get_config("minitron-8b-smoke")
    m = Model(cfg)
    params = m.init(jax.random.PRNGKey(0))
    eng = Engine(cfg, params)
    rng = np.random.RandomState(0)
    batch = {"tokens": jnp.asarray(rng.randint(0, 500, (2, 12)))}
    res = eng.generate(batch, steps=6)
    assert res.tokens.shape == (2, 6)
    assert np.isfinite(res.logprobs).all()
    # greedy + deterministic weights -> rerunning gives the same tokens
    res2 = eng.generate(batch, steps=6)
    np.testing.assert_array_equal(res.tokens, res2.tokens)


def test_engine_never_samples_padded_vocab_ids():
    """The unembedding is padded past vocab_size; even when a padded row
    dominates the logits, greedy decode stays inside the vocabulary and the
    log-probabilities are normalised over real ids only."""
    import dataclasses

    cfg = dataclasses.replace(get_config("xlstm-350m-smoke"), vocab_size=1000)
    assert cfg.padded_vocab > cfg.vocab_size
    m = Model(cfg)
    params = m.init(jax.random.PRNGKey(0))
    tok = params["embed"]["tokens"]
    # a padded row aligned with every real row wins the argmax everywhere
    params["embed"]["tokens"] = tok.at[cfg.vocab_size].set(tok[: cfg.vocab_size].sum(0) * 4)
    prompt = jnp.asarray(np.random.RandomState(0).randint(0, cfg.vocab_size, (2, 8)))
    logits, _ = m.forward(params, {"tokens": prompt})
    assert int(jnp.argmax(logits[0, -1])) >= cfg.vocab_size  # the trap is live
    res = Engine(cfg, params).generate({"tokens": prompt}, steps=4)
    assert (res.tokens < cfg.vocab_size).all(), res.tokens
    assert np.isfinite(res.logprobs).all() and (res.logprobs <= 0).all()


def test_engine_matches_forward():
    """Greedy engine tokens == argmax of the teacher-forced forward pass."""
    cfg = get_config("xlstm-350m-smoke")
    m = Model(cfg)
    params = m.init(jax.random.PRNGKey(2))
    rng = np.random.RandomState(1)
    prompt = jnp.asarray(rng.randint(0, 500, (1, 8)))
    eng = Engine(cfg, params)
    res = eng.generate({"tokens": prompt}, steps=4)
    # teacher-force the generated tokens and check each argmax reproduces
    seq = jnp.concatenate([prompt, jnp.asarray(res.tokens)], axis=1)
    logits, _ = m.forward(params, {"tokens": seq})
    for i in range(4):
        want = int(jnp.argmax(logits[0, 7 + i]))
        assert want == int(res.tokens[0, i]), (i, want, res.tokens)


# ------------------------------ launch --------------------------------------


def test_compile_cache_placement(monkeypatch):
    """JAX_COMPILATION_CACHE_DIR wins untouched; unset, the cache sits in
    .jax_cache at the root of the checkout."""
    import os

    from repro.launch import compile_cache

    old = jax.config.jax_compilation_cache_dir
    try:
        monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", "/placed/by/operator")
        assert compile_cache.enable_compile_cache() == "/placed/by/operator"
        assert jax.config.jax_compilation_cache_dir == old
        monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR")
        path = compile_cache.enable_compile_cache()
        root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
        assert path == os.path.join(root, ".jax_cache")
        assert jax.config.jax_compilation_cache_dir == path
    finally:
        jax.config.update("jax_compilation_cache_dir", old)
