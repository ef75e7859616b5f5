"""The train step's named scopes reach the compiled HLO's op_name metadata,
where the benchmark's device-trace readers (``bench/scopes.py``) classify
them: forward, backward, recompute, optimizer, each sequence mixer, and
the gradient sync's pack, buckets and unpack."""
from __future__ import annotations

import collections
import os
import re
import sys

import jax
import jax.numpy as jnp
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from bench import scopes  # noqa: E402
from repro.configs import get_config  # noqa: E402
from repro.configs.base import RunConfig  # noqa: E402
from repro.train.trainer import Trainer  # noqa: E402


@pytest.mark.parametrize("arch,mixers", [
    ("xlstm-350m-smoke", {"mlstm", "slstm"}),
    ("hymba-1.5b-smoke", {"mamba", "attn"}),
])
def test_train_step_scopes(arch, mixers):
    tr = Trainer(get_config(arch), RunConfig(total_steps=4, warmup_steps=1, remat=True))
    params, opt = tr.init_state(0)
    batch = {"tokens": jnp.zeros((2, 32), jnp.int32), "labels": jnp.zeros((2, 32), jnp.int32)}
    text = tr._step_fn.lower(params, opt, batch).compile().as_text()
    names = scopes.op_names(text)
    paths = [scopes.scope_path(n) for n in names.values()]
    classes = collections.Counter(scopes.classify(p) for p in paths)
    for c in ("forward", "backward", "recompute", "optimizer"):
        assert classes[c] > 0, (c, classes)
    assert classes["sync"] == 0
    assert {scopes.mixer(p) for p in paths} - {None} == mixers
    # a mixer is seen in the forward pass, the recompute and the backward pass
    for c in ("forward", "recompute", "backward"):
        assert {scopes.mixer(p) for p in paths if scopes.classify(p) == c} >= mixers, c
    assert any("head" in scopes.components(p) for p in paths)
    if "mamba" in mixers:
        # Mamba's scan kernels under remat: the forward kernel in the forward
        # pass and the recompute, the backward kernel in the backward pass,
        # each under mixer/mamba (here the interpreted kernels' ops; on a TPU
        # the kernel's one custom call). A reduce's combiner runs inside the
        # reduce, and one in an interpreted branch keeps a name relative to
        # the branch, so combiners are left out.
        kernels = {(k, scopes.classify(n), scopes.mixer(n))
                   for n in scopes.op_names(_without_combiners(text)).values()
                   for k in ("mamba_scan_fwd", "mamba_scan_bwd") if k in n}
        assert kernels == {("mamba_scan_fwd", "forward", "mamba"),
                           ("mamba_scan_fwd", "recompute", "mamba"),
                           ("mamba_scan_bwd", "backward", "mamba")}, kernels


def _without_combiners(hlo_text: str) -> str:
    """The HLO module's text without the computations that reduces (and
    reduce-windows) apply: every ``to_apply`` target but a call's."""
    combiners = {m.group(1) for line in hlo_text.splitlines() if " call(" not in line
                 for m in re.finditer(r"to_apply=%([\w.\-]+)", line)}
    blocks = re.split(r"\n(?=\S)", hlo_text)   # a computation starts at column 0
    return "\n".join(b for b in blocks if b.split(" ", 1)[0].lstrip("%") not in combiners)


def test_classify_first_match_wins():
    assert scopes.classify("jit(train_step)/shard_map/grad_sync/bucket3/while/body/add") == "sync"
    assert scopes.classify("jit(train_step)/shard_map/optimizer/mul") == "optimizer"
    remat = "jit(train_step)/transpose(jvp(fwd))/while/body/checkpoint/rematted_computation/mixer/mlstm/dot"
    assert scopes.classify(remat) == "recompute"
    assert scopes.scope_path(remat) == "transpose(/jvp(/fwd/rematted_computation/mixer/mlstm"
    assert scopes.classify(scopes.scope_path(remat)) == "recompute"
    assert scopes.mixer(remat) == "mlstm"
    assert scopes.classify("jit(train_step)/transpose(jvp(fwd))/head/dot_general") == "backward"
    assert scopes.classify("jit(train_step)/jvp(fwd)/mixer/mamba/while") == "forward"
    assert scopes.classify("jit(train_step)/fwdx/add") == "unattributed"
    assert scopes.classify("reduce_sum") == "unattributed"
    assert scopes.scope_path("jit(step)/shard_map/grad_sync/bucket12/ppermute") == "grad_sync/bucket12"


def test_pallreduce_tree_scopes(dist):
    dist(
        f"""
import sys
sys.path.insert(0, {ROOT!r})
import jax, jax.numpy as jnp
from jax.sharding import PartitionSpec as P
from repro.comm import pallreduce_tree
from bench import scopes

mesh = jax.make_mesh((4,), ("x",), axis_types=(jax.sharding.AxisType.Auto,))
# several leaves a bucket, so packing and unpacking are ops of their own
tree = {{k: jnp.ones((4, n), jnp.float32) for k, n in zip("abcdef", (300, 200, 64, 32, 16, 8))}}
f = jax.jit(jax.shard_map(lambda t: pallreduce_tree(t, ["x"], bucket_bytes=1024),
                          mesh=mesh, in_specs=P("x"), out_specs=P("x"), check_vma=False))
names = scopes.op_names(f.lower(tree).compile().as_text())
parts = set(c for n in names.values() for c in scopes.components(n))
buckets = sorted(int(c[6:]) for c in parts if c.startswith("bucket"))
assert "pack" in parts and "unpack" in parts, parts
assert len(buckets) >= 3 and buckets == list(range(len(buckets))), buckets
print("PASS")
""",
        devices=4,
        timeout=300,
    )
