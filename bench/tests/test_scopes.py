"""Device time by scope (``bench/scopes.py``) and the readers built on it,
on a hand-made trace, on a small trace recorded on a TPU v5e with scope
paths (``data/trace_scoped_small.json``: device operations of a traced
window of cell xlstm350m.train1), and the earlier readers pinned on
``data/trace_small.json``."""
import os
import types

import jax
import jax.numpy as jnp
import pytest

from bench import scopes, tracefile
from bench.metrics import bwd_ms, device_idle_share, fwd_ms, mixer_ms, optimizer_ms, step_mfu

DATA = os.path.join(os.path.dirname(__file__), "data")

# chip 0: a forward while loop holding backward, recompute, a nested while
# and an op without a scope; then the optimizer, one sync bucket and an op
# without a scope outside any loop
HAND = {
    "ops": {
        0: [["%fusion.1", 100, 200], ["%while.3", 200, 600], ["%fusion.4", 220, 300],
            ["%fusion.5", 300, 350], ["%while.6", 400, 550], ["%fusion.7", 410, 500],
            ["%pad.11", 560, 580], ["%fusion.8", 650, 700], ["%all-reduce.9", 700, 760],
            ["%copy.10", 800, 900]],
        1: [["%fusion.1", 100, 300]],
    },
    "spans": [["bench.window", 100, 1000]],
    "scopes": {
        0: ["jvp(/fwd", "jvp(/fwd/mixer/mlstm", "transpose(/jvp(/fwd/mixer/mlstm",
            "transpose(/jvp(/fwd/rematted_computation/mixer/mlstm", "transpose(/jvp(/fwd",
            "transpose(/jvp(/fwd/head", "", "optimizer", "grad_sync/bucket0", ""],
        1: ["jvp(/fwd"],
    },
}


def _ctx(trace, steps=1, lo=None, hi=None):
    wlo, whi = tracefile.window(trace)
    lines = []
    return types.SimpleNamespace(trace=trace, lo=wlo if lo is None else lo, hi=whi if hi is None else hi,
                                 steps=steps, chips=len(trace["ops"]), log=lines.append, lines=lines,
                                 flops_per_step=1e12, peaks={"bf16_flops": 197e12})


def _fresh(trace):
    return {k: v for k, v in trace.items() if not k.startswith("_")}


def test_innermost_attribution():
    ns = scopes.innermost_ns(HAND["ops"][0], 100, 1000)
    # while.3 keeps 200-220, 350-400, 550-560 and 580-600; while.6 keeps
    # 400-410 and 500-550
    assert ns == [100, 100, 80, 50, 60, 90, 20, 50, 60, 100]
    assert sum(ns) == tracefile.busy_ns(HAND, 0, 100, 1000) == 710
    # clipped to the window
    assert sum(scopes.innermost_ns(HAND["ops"][0], 250, 420)) == 170


def test_innermost_tolerates_overhang():
    # a child that outlasts its container is still counted once
    ops = [["a", 0, 100], ["b", 50, 150], ["c", 120, 130]]
    ns = scopes.innermost_ns(ops, 0, 200)
    assert ns == [50, 90, 10]
    assert sum(ns) == tracefile.busy_ns({"ops": {0: ops}}, 0, 0, 200)


def test_classes_partition_busy_time():
    trace = _fresh(HAND)
    got = scopes.by_class(trace, 0, 100, 1000)
    assert got == {"sync": 60, "optimizer": 50, "backward": 230, "recompute": 50, "forward": 220,
                   "unattributed": 100}
    assert sum(got.values()) == tracefile.busy_ns(trace, 0, 100, 1000)
    # pad.11 takes its loop's path; copy.10 is in no loop and keeps none
    paths = scopes.inherited(trace["ops"][0], trace[scopes.KEY][0])
    assert paths[6] == "jvp(/fwd/mixer/mlstm" and paths[9] == ""
    assert scopes.by_path(trace, 0, 100, 1000)[""] == 100


def test_readers():
    ctx = _ctx(_fresh(HAND), steps=2)
    per = 2 * 2 * 1e6  # two chips, two steps, ns to ms
    assert fwd_ms.read(ctx) == pytest.approx((220 + 200) / per)
    assert bwd_ms.read(ctx) == pytest.approx(280 / per)
    assert optimizer_ms.read(ctx) == pytest.approx(50 / per)
    assert mixer_ms.read(ctx) == pytest.approx(250 / per)
    text = "\n".join(ctx.lines)
    assert "unattributed share" in text and "top paths" in text
    assert "recompute" in text and "'mlstm'" in text and "grad_sync/bucket0" in text
    # the ten paths with most time, forward first
    assert '["jvp(/fwd", ' in text


def test_readers_silent_without_scopes():
    plain = _fresh(HAND)
    plain["scopes"] = {0: [""] * 10, 1: [""]}
    for reader in (fwd_ms, bwd_ms, optimizer_ms, mixer_ms):
        assert reader.read(_ctx(dict(plain))) is None
    # no scope key and no cell on the command line: nothing is read, nothing raises
    bare = {"ops": HAND["ops"], "spans": HAND["spans"]}
    ctx = _ctx(bare)
    assert fwd_ms.read(ctx) is None
    assert any("no scope names" in line for line in ctx.lines)


def test_align_maps_names_across_metadata():
    """Two compiles of one program that differ only in a scope: the names
    of the unscoped one map to the op_names of the scoped one."""
    def make(scope):
        def step(x, w):
            with jax.named_scope(scope):
                return jnp.tanh(x @ w).sum()
        return jax.jit(jax.grad(step, argnums=1))

    args = (jnp.ones((8, 16)), jnp.ones((16, 4)))
    ran = make("other").lower(*args).compile().as_text()
    fresh = make("fwd").lower(*args).compile().as_text()
    names = scopes.align(ran, fresh)
    assert names is not None
    assert set(names) <= {n.lstrip("%") for n in scopes._canonical(ran)[1]}
    assert {scopes.classify(v) for v in names.values()} & {"forward", "backward"}
    assert scopes.align(ran, ran.replace("tanh", "exponential")) is None


def test_recorded_scoped_trace():
    trace = scopes.load(os.path.join(DATA, "trace_scoped_small.json"))
    assert set(trace[scopes.KEY]) == set(trace["ops"])
    for c, ops in trace["ops"].items():
        assert len(trace[scopes.KEY][c]) == len(ops)
    lo, hi = tracefile.window(trace)
    end = max(e for ev in trace["ops"].values() for _, _, e in ev)
    got = scopes.by_class(trace, 0, lo, end)
    busy = tracefile.busy_ns(trace, 0, lo, end)
    assert sum(got.values()) == pytest.approx(busy)
    for c in ("forward", "backward", "recompute", "optimizer"):
        assert got[c] > 0, (c, got)
    assert got["unattributed"] < 0.05 * busy
    mixers = {scopes.mixer(p) for p in trace[scopes.KEY][0]}
    assert {"mlstm", "slstm"} <= mixers


def test_earlier_readers_unchanged():
    """step_mfu, device_idle_share, top_ops and named_gaps read what they
    read before scope paths were kept beside the ops."""
    trace = tracefile.load(os.path.join(DATA, "trace_small.json"))
    lo, _ = tracefile.window(trace)
    end = max(e for ev in trace["ops"].values() for _, _, e in ev)
    ctx = _ctx(trace, lo=lo, hi=end)
    assert device_idle_share.read(ctx) == pytest.approx(1.9677513213448794, rel=1e-12)
    assert step_mfu.read(ctx) == pytest.approx(5.192711388640062, rel=1e-12)
    assert tracefile.top_ops(trace, lo, end, k=3) == [
        ["%while.1683", 0.095359293], ["%while.1744", 0.001318043],
        ["%convolution_add_fusion.73", 0.000362746]]
    assert tracefile.named_gaps(trace, 0, lo, end, k=3) == [
        ["bench.feed", 0.001920085], ["bench.dispatch", 3.4e-06], ["bench.dispatch", 3e-09]]
    # and the same with scope paths attached
    scoped = dict(trace)
    scopes.attach(scoped, {})
    assert device_idle_share.read(_ctx(scoped, lo=lo, hi=end)) == pytest.approx(1.9677513213448794, rel=1e-12)
    assert tracefile.top_ops(scoped, lo, end, k=3) == tracefile.top_ops(trace, lo, end, k=3)
