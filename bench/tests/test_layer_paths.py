"""The readers of layers named below the scopes ``bench/scopes.py`` keeps
(``bench/layer_paths.py``: ``mla_ms``, ``moe_ms``) and the gradient
sync's reader (``sync_ms``), on hand-made traces and on the small trace
recorded on a TPU v5e (``data/trace_scoped_small.json``, one chip, so no
sync)."""
import os

import pytest

from bench import layer_paths, scopes, tracefile
from bench.metrics import mla_ms, moe_ms, sync_ms
from bench.tests.test_scopes import DATA, HAND, _ctx, _fresh

# one chip: the forward of an MLA layer (its splash kernel without an
# op_name) and of an MoE layer's parts, a
# backward loop holding the transposed experts and an op without metadata,
# the optimizer
OPS = [["%fusion.1", 0, 100], ["%splash_mha_fwd_residuals.2", 100, 180], ["%fusion.3", 200, 230],
       ["%fusion.4", 230, 250], ["%ragged-dot.5", 250, 330], ["%fusion.6", 330, 350],
       ["%fusion.7", 350, 400], ["%while.8", 400, 600], ["%ragged-dot.9", 410, 500],
       ["%copy.10", 500, 520], ["%fusion.11", 650, 700]]
NAMES = ["jit(step)/jvp(fwd)/mixer/mla/dot_general",
         "",                                          # the splash kernel: no op_name
         "jit(step)/jvp(fwd)/mlp/moe/route/dot_general",
         "jit(step)/jvp(fwd)/mlp/moe/dispatch/sort",
         "jit(step)/jvp(fwd)/mlp/moe/experts/ragged_dot",
         "jit(step)/jvp(fwd)/mlp/moe/combine/dot_general",
         "jit(step)/jvp(fwd)/mlp/moe/shared/dot_general",
         "jit(step)/transpose(jvp(fwd))/rematted_computation/mlp/moe/experts/while",
         "jit(step)/transpose(jvp(fwd))/mlp/moe/experts/ragged_dot",
         "",
         "jit(step)/optimizer/add"]


def _moe_trace():
    return {"ops": {0: OPS}, "spans": [["bench.window", 0, 1000]], layer_paths.KEY: {0: NAMES}}


def test_mla_and_moe_readers():
    ctx = _ctx(_moe_trace(), steps=2)
    per = 2 * 1e6
    assert mla_ms.read(ctx) == pytest.approx(180 / per)
    # the loop keeps 400-410 and 520-600; copy.10 takes the loop's path
    assert moe_ms.read(ctx) == pytest.approx((30 + 20 + 80 + 20 + 50 + 200) / per)
    line = next(x for x in ctx.lines if x.startswith("moe_ms:"))
    for part, ns in {"route": 30, "dispatch": 20, "experts": 280, "combine": 20, "shared": 50}.items():
        assert f"'{part}': {ns / per!r}" in line, (part, line)


def test_layer_readers_silent_without_their_layers():
    trace = _moe_trace()
    trace["ops"] = {0: [["%fusion.2" if n.startswith("%splash") else n, s, e] for n, s, e in OPS]}
    trace[layer_paths.KEY] = {0: ["jit(step)/jvp(fwd)/mixer/attn/x"] * len(OPS)}
    ctx = _ctx(trace)
    assert mla_ms.read(ctx) is None and moe_ms.read(ctx) is None
    # no names and no cell on the command line: nothing raises, and only the
    # splash kernel, known by its own name, is read
    bare = {"ops": {0: OPS}, "spans": [["bench.window", 0, 1000]]}
    ctx = _ctx(bare)
    assert moe_ms.read(ctx) is None
    assert mla_ms.read(ctx) == pytest.approx(80 / 1e6)
    assert any("no scope names" in x for x in ctx.lines)


def test_sync_ms():
    ctx = _ctx(_fresh(HAND), steps=2)
    assert sync_ms.read(ctx) == pytest.approx(60 / (2 * 2 * 1e6))


def test_sync_ms_silent_on_one_chip():
    trace = scopes.load(os.path.join(DATA, "trace_scoped_small.json"))
    lo, _ = tracefile.window(trace)
    end = max(e for ev in trace["ops"].values() for _, _, e in ev)
    assert sync_ms.read(_ctx(trace, lo=lo, hi=end)) is None

