"""Bring-up check on a TPU: this repo's kernels, trainer and serving engine,
run once through their normal entry points, each checked against a reference.

    python chip_smoke.py              # one chip: kernels, train, serve
    python chip_smoke.py --chips 4    # four chips: collectives, weight
                                      # distribution, the two gradient syncs

One process; it starts no other. It exits nonzero unless JAX finds a TPU,
and any failed check fails the run. The last line of stdout is one JSON
object naming the device; everything else is printed before it. Data and
weights are random, made from ``--seed``. The persistent compilation cache
lives where ``JAX_COMPILATION_CACHE_DIR`` says, else in ``.jax_cache`` at
the root of the checkout.
"""
from __future__ import annotations

import argparse
import importlib.metadata
import json
import os
import sys
import threading
import time

sys.path.insert(0, os.path.join(os.path.dirname(os.path.abspath(__file__)), "src"))

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
import numpy as np  # noqa: E402
from jax import lax  # noqa: E402
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P  # noqa: E402

from repro import comm  # noqa: E402
from repro.configs import get_config  # noqa: E402
from repro.configs.base import RunConfig  # noqa: E402
from repro.core.cost_model import hardware_for  # noqa: E402
from repro.core.tuner import Tuner  # noqa: E402
from repro.kernels import ops, ref  # noqa: E402
from repro.kernels.combine_update import fused_combine_update  # noqa: E402
from repro.launch.compile_cache import enable_compile_cache  # noqa: E402
from repro.launch.mesh import make_local_mesh  # noqa: E402
from repro.models import Model  # noqa: E402
from repro.serve.engine import Engine, distribute_weights  # noqa: E402
from repro.train.trainer import Trainer  # noqa: E402

ARCH = "xlstm-350m"
SEQ = 2048            # the xLSTM paper's context length
TRAIN_BATCH = 8
TRAIN_STEPS = 4
PROMPT, DECODE, REQUESTS = 128, 32, 4
# The recurrent decode and the chunkwise full-sequence forward round bf16
# activations at different points through 24 layers, and the logits leave
# the unembedding in bf16. A log-probability may then differ by a few bf16
# units (2**-8 relative) of the largest logit at that position; a wrong
# cache position or state is off by whole nats.
BF16_UNITS = 4
MiB = 1 << 20


def log(msg: str) -> None:
    print(msg, flush=True)


class CompileClock:
    """Seconds spent in XLA compilation, and persistent-cache hits."""

    def __init__(self):
        self.seconds = 0.0
        self.cache_hits = 0
        jax.monitoring.register_event_duration_secs_listener(self._duration)
        jax.monitoring.register_event_listener(self._event)

    def _duration(self, name, secs, **_):
        if name == "/jax/core/compile/backend_compile_duration":
            self.seconds += secs

    def _event(self, name, **_):
        if name == "/jax/compilation_cache/cache_hits":
            self.cache_hits += 1


def phase_device(chips: int):
    devs = jax.devices()
    if devs[0].platform != "tpu":
        sys.exit(f"chip_smoke: no TPU found (jax sees platform "
                 f"{devs[0].platform!r}); this check runs on a TPU only")
    if len(devs) < chips:
        sys.exit(f"chip_smoke: --chips {chips} needs {chips} TPU devices, "
                 f"jax sees {len(devs)}")
    kind = devs[0].device_kind
    log(f"device: platform=tpu kind={kind!r} visible={len(devs)} used={chips} "
        f"jax={jax.__version__} jaxlib={importlib.metadata.version('jaxlib')} "
        f"libtpu={importlib.metadata.version('libtpu')}")
    hw = hardware_for(kind)
    log(f"device: hardware constants {hw.name}")
    return devs[:chips], Tuner(hw)


def _bits(a) -> np.ndarray:
    a = np.asarray(a)
    return a.view(np.dtype(f"u{a.dtype.itemsize}"))


def _block_amax(x: np.ndarray) -> np.ndarray:
    B, C = x.shape
    amax = np.abs(x).reshape(B, C // 256, 256).max(-1)
    return np.repeat(amax, 256, axis=1)


def phase_kernels(seed: int) -> None:
    from repro.kernels.interpret import resolve_interpret

    assert resolve_interpret(None) is False, "kernels would run interpreted"
    rng = np.random.RandomState(seed)

    n = 4 * MiB // 2 - 77   # 4 MiB bf16 weight bucket, ragged tail
    x = jnp.asarray(rng.randn(n).astype(np.float32), jnp.bfloat16)
    out = jax.jit(ops.chunked_copy)(x)
    assert np.array_equal(_bits(out), _bits(ref.chunked_copy_ref(x))), "chunked_copy"
    log(f"kernels: chunked_copy {n} bf16 == chunked_copy_ref bit for bit")

    B, C = 16, 1 << 20       # 64 MiB f32 gradient bucket in 16 chunks
    buf = rng.randn(B, C).astype(np.float32)
    recv = rng.randn(B, C).astype(np.float32)
    lo, hi = 3, 13
    step = jax.jit(lambda b, r, comb: fused_combine_update(
        b, r, 0, lo, hi, combine=comb))
    rows = np.arange(B)[:, None]
    for comb, name in ((1, "combine"), (0, "overwrite")):
        got = step(jnp.asarray(buf), jnp.asarray(recv), comb)
        mode = ((rows >= lo) & (rows < hi)) * (1 + comb)
        want = ref.fused_combine_ref(jnp.asarray(buf), jnp.asarray(recv),
                                     jnp.asarray(mode))
        assert np.array_equal(_bits(got), _bits(want)), f"fused_combine_update {name}"
        log(f"kernels: fused_combine_update ({B}, {C}) f32 {name} round == "
            f"fused_combine_ref bit for bit")

    amax = _block_amax(buf)
    for fmt in ("int8", "fp8"):
        roundtrip = jax.jit(lambda v, f=fmt: ops.dequantize_blocks(
            *ops.quantize_blocks(v, f)))
        err = np.abs(buf - np.asarray(roundtrip(jnp.asarray(buf))))
        if fmt == "int8":
            # half a quantization step per 256-block, plus the f32 rounding
            # of the divide and the multiply back (each within amax * 2**-24
            # here), which 16M elements reach
            bound = amax / (2 * 127.0) + amax * 2.0**-22
        else:               # half an e4m3 ulp plus the subnormal step
            bound = (np.abs(buf) / 16.0 + amax / 448.0 * 2.0**-9 + 1e-12) * (1 + 1e-5)
        assert (err <= bound).all(), (fmt, float((err - bound).max()))
        log(f"kernels: quantize->dequantize {fmt} ({B}, {C}) within the per-block "
            f"bound, max err {float(err.max())!r}")


def _steady(hist) -> list:
    """Per-step wall times after step 0 (step 0 includes compilation)."""
    return [b["time_s"] - a["time_s"] for a, b in zip(hist[1:], hist[2:])]


def phase_train(cfg, seed: int, dev):
    run = RunConfig(sync_mode="grad_allreduce", seed=seed, warmup_steps=1,
                    total_steps=TRAIN_STEPS)
    trainer = Trainer(cfg, run, mesh=make_local_mesh(1))
    params, _opt, hist = trainer.train(batch=TRAIN_BATCH, seq=SEQ,
                                       steps=TRAIN_STEPS, log_every=1)
    losses = [h["loss"] for h in hist]
    assert all(np.isfinite(losses)), losses
    assert losses[-1] < losses[0], f"loss did not fall: {losses}"
    dts = _steady(hist)
    dt = float(np.median(dts))
    # buffers and the compiled programs' reservations are counted apart
    mem = dev.memory_stats()
    log(f"train: {ARCH} batch={TRAIN_BATCH} seq={SEQ} losses={losses!r}")
    log(f"train: step 0 (with compile) {hist[0]['time_s']!r} s; steady step "
        f"times {dts!r} s; median {dt!r} s; {TRAIN_BATCH * SEQ / dt!r} tokens/s")
    log(f"train: peak HBM {mem['peak_bytes_in_use']} bytes in buffers + "
        f"{mem['peak_bytes_reserved']} bytes reserved by programs, of "
        f"{mem['bytes_limit']}")
    return params


def phase_serve(cfg, params, seed: int) -> None:
    engine = Engine(cfg, params, max_len=PROMPT + DECODE)
    rng = np.random.RandomState(seed + 1)
    prompts = jnp.asarray(rng.randint(0, cfg.vocab_size, (REQUESTS, PROMPT)), jnp.int32)
    batch = {"tokens": prompts}
    engine.generate(batch, steps=2)            # compiles prefill and decode
    t0 = time.perf_counter()
    engine.generate(batch, steps=1)
    t1 = time.perf_counter()
    res = engine.generate(batch, steps=DECODE)
    t2 = time.perf_counter()
    per_token = ((t2 - t1) - (t1 - t0)) / (DECODE - 1)
    prefill = (t1 - t0) - per_token

    toks, lps = res.tokens, res.logprobs
    assert toks.shape == (REQUESTS, DECODE), toks.shape
    assert ((toks >= 0) & (toks < cfg.vocab_size)).all(), "token out of vocabulary"
    assert np.isfinite(lps).all(), "non-finite logprob"
    # teacher-forced reference: one plain forward over prompt + the tokens
    # the engine generated; position PROMPT-1+i predicts generated token i
    seq = jnp.concatenate([prompts, jnp.asarray(toks[:, :-1], jnp.int32)], axis=1)
    logits, _ = jax.jit(engine.model.forward)(engine.params, {"tokens": seq})
    logits = np.asarray(logits[:, PROMPT - 1:, :cfg.vocab_size], np.float32)
    want = np.asarray(jax.nn.log_softmax(logits, axis=-1))
    want = np.take_along_axis(want, toks[:, :, None], axis=-1)[..., 0]
    diff = np.abs(want - lps)
    tol = BF16_UNITS * 2.0**-8 * np.abs(logits).max(-1)
    assert (diff <= tol).all(), (float(diff.max()), float(tol.min()))
    last = logits[:, -1]
    top2 = np.sort(last, axis=-1)[:, -2:]
    tie = top2[:, 1] - top2[:, 0] <= 2 * tol[:, -1]
    agree = (last.argmax(-1) == toks[:, -1]) | tie
    assert agree.all(), (last.argmax(-1), toks[:, -1])
    log(f"serve: {REQUESTS} requests, prompt {PROMPT}, {DECODE} greedy steps; "
        f"decode logprobs vs full forward max |diff| {float(diff.max())!r} "
        f"(tolerance {BF16_UNITS} bf16 units of the largest logit, smallest "
        f"{float(tol.min())!r}); last-step greedy token == forward argmax")
    log(f"serve: prefill {prefill!r} s; decode {per_token!r} s/token")


# ---------------------------------------------------------------------------
# four chips
# ---------------------------------------------------------------------------


# a deadlocked collective kernel never returns to Python; this bounds how
# long it can hold the chips (its compiles take seconds, its runs less)
INKERNEL_DEADLINE_S = 240


def _deadline(seconds: int, what: str) -> threading.Timer:
    """Exit the process if ``what`` has not finished within ``seconds``."""
    def expire():
        print(f"{what}: not finished after {seconds} s; giving up", flush=True)
        os._exit(3)

    timer = threading.Timer(seconds, expire)
    timer.daemon = True
    timer.start()
    return timer


def _shard_devices(arr) -> list:
    return sorted(str(s.device) for s in arr.addressable_shards)


EXECUTORS = {"unrolled": dict(compiled=False), "compiled": dict(compiled=True),
             "inkernel": dict(inkernel=True)}


def phase_collectives(devs, tuner, seed: int, executors) -> None:
    """pbcast and pallreduce over a 4-chip axis through each named executor,
    pinned explicitly: bcast against the root's buffer bit for bit,
    allreduce against ``lax.psum``."""
    n = len(devs)
    mesh = Mesh(np.array(devs), ("x",))
    assert len({d.id for d in mesh.devices.flat}) == n, mesh.devices
    rng = np.random.RandomState(seed)
    for M in (4 * MiB, 64 * MiB):
        elems = M // 4
        host = rng.randn(n, elems).astype(np.float32)
        x = jax.device_put(host.reshape(-1), NamedSharding(mesh, P("x")))
        log(f"collectives: {M // MiB} MiB per rank on {_shard_devices(x)}")
        psum = jax.jit(jax.shard_map(lambda v: lax.psum(v, "x"), mesh=mesh,
                                     in_specs=P("x"), out_specs=P("x"),
                                     check_vma=False))
        want_sum = np.asarray(psum(x)).reshape(n, elems)
        # f32 sums of n terms in another order: n ulps of sum |x_i|
        tol = n * np.finfo(np.float32).eps * np.abs(host).sum(0)
        for label in executors:
            kw = EXECUTORS[label]
            bcast = jax.jit(jax.shard_map(
                lambda v, kw=kw: comm.pbcast(v, "x", root=0, tuner=tuner, **kw),
                mesh=mesh, in_specs=P("x"), out_specs=P("x"), check_vma=False))
            got = np.asarray(bcast(x)).reshape(n, elems)
            assert all(np.array_equal(_bits(got[r]), _bits(host[0])) for r in range(n)), \
                f"pbcast {label} {M}"
            allreduce = jax.jit(jax.shard_map(
                lambda v, kw=kw: comm.pallreduce(v, "x", tuner=tuner, **kw),
                mesh=mesh, in_specs=P("x"), out_specs=P("x"), check_vma=False))
            got = np.asarray(allreduce(x)).reshape(n, elems)
            err = np.abs(got - want_sum[None, 0])
            assert (err <= tol[None]).all(), f"pallreduce {label} {M}"
            log(f"collectives: {M // MiB} MiB {label}: pbcast == root bit for bit; "
                f"pallreduce vs psum max |err| {float(err.max())!r}")


def phase_distribute(cfg, devs, seed: int) -> None:
    mesh = Mesh(np.array(devs).reshape(len(devs), 1), ("data", "model"))
    params = jax.jit(Model(cfg).init)(jax.random.PRNGKey(seed))
    want = jax.device_get(params)
    out = distribute_weights(params, mesh, double_buffer=True)
    leaves = jax.tree.leaves(out)
    assert _shard_devices(leaves[0]) == sorted(str(d) for d in devs)
    for got, w in zip(leaves, jax.tree.leaves(want)):
        for shard in got.addressable_shards:
            assert np.array_equal(_bits(shard.data), _bits(w)), shard.device
    log(f"distribute: {len(leaves)} leaves, double-buffered, bit-identical on "
        f"{_shard_devices(leaves[0])}")


def phase_sync_modes(cfg, seed: int) -> None:
    """grad_allreduce (XLA psum) against tuned_allreduce (repro.comm) on a
    data=4 mesh. Bounds are those of the tier-1 test: step-0 loss within one
    bf16 unit roundoff, last loss within 2e-2, params within atol 5e-3 /
    rtol 1e-2."""
    steps, batch = 3, 8
    runs = {}
    for mode in ("grad_allreduce", "tuned_allreduce"):
        run = RunConfig(sync_mode=mode, seed=seed, warmup_steps=1, total_steps=steps)
        params, _, hist = Trainer(cfg, run, mesh=make_local_mesh(1)).train(
            batch=batch, seq=SEQ, steps=steps, log_every=1)
        runs[mode] = (jax.device_get(params), [h["loss"] for h in hist])
    (p1, l1), (p2, l2) = runs["grad_allreduce"], runs["tuned_allreduce"]
    d0, dl = abs(l1[0] - l2[0]), abs(l1[-1] - l2[-1])
    worst = max(float(np.max(np.abs(np.asarray(a, np.float32) - np.asarray(b, np.float32))))
                for a, b in zip(jax.tree.leaves(p1), jax.tree.leaves(p2)))
    log(f"sync: losses grad_allreduce {l1!r} tuned_allreduce {l2!r}")
    log(f"sync: step-0 |dloss| {d0!r} (bound {2**-8 * abs(l1[0])!r}); last |dloss| "
        f"{dl!r} (bound 2e-2); max |dparam| {worst!r}")
    assert d0 <= 2**-8 * abs(l1[0]) and dl < 2e-2
    for a, b in zip(jax.tree.leaves(p1), jax.tree.leaves(p2)):
        np.testing.assert_allclose(np.asarray(a, np.float32), np.asarray(b, np.float32),
                                   atol=5e-3, rtol=1e-2)


def main() -> None:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1)
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args()

    devs, tuner = phase_device(args.chips)
    log(f"compile cache: {enable_compile_cache()}")
    clock = CompileClock()
    cfg = get_config(ARCH)
    t0 = time.perf_counter()
    if args.chips == 1:
        phase_kernels(args.seed)
        params = phase_train(cfg, args.seed, devs[0])
        phase_serve(cfg, params, args.seed)
    else:
        phase_distribute(cfg, devs, args.seed)
        phase_sync_modes(cfg, args.seed)
        phase_collectives(devs, tuner, args.seed, ("unrolled", "compiled"))
        # last: the first run of the RDMA kernel on a chip
        timer = _deadline(INKERNEL_DEADLINE_S, "collectives: inkernel")
        phase_collectives(devs, tuner, args.seed, ("inkernel",))
        timer.cancel()
    log(f"wall {time.perf_counter() - t0!r} s; compile {clock.seconds!r} s; "
        f"persistent cache hits {clock.cache_hits}")
    print(json.dumps({"ok": True, "device": {
        "platform": devs[0].platform, "kind": devs[0].device_kind,
        "count": len(devs)}}), flush=True)


if __name__ == "__main__":
    main()
