"""Rule-based layout: PartitionSpecs for params, batches, and KV caches.

One source of truth for how every tensor in the system lands on a
``(data, model)`` or ``(pod, data, model)`` mesh.  The serving engine, the
trainer, and the dry-run all consume these specs; ``core.bcast`` derives
its hierarchical axes from the same mesh metadata (``dist.topology``), so
collective tuning and tensor layout stay co-designed.

Layout rules (the fallback policy is per-dim: any dim not divisible by the
product of its mesh-axis sizes is replicated instead):

parameters (``param_specs``)
  * attention: the heads dim shards on ``model`` (q-heads for wq/wo,
    kv-heads for wk/wv).  Non-divisible head counts (hymba's 25, MQA's 1)
    fall back per ``attn_fallback``: ``"replicate"`` (train/prefill — a
    head_dim shard would all-reduce score blocks every layer) or
    ``"head_dim"`` (decode — serving memory wins).  Latent attention:
    ``wkv_b`` (r, H, nope + v) by heads like wq; ``wkv_a`` (d, r + rope)
    makes the latent every head reads, so it stays whole on ``model``.
  * MoE: the expert dim shards on ``model`` when divisible (qwen3's 128
    experts), else the expert FFN width does (mixtral's 8 < 16); shared
    experts follow the dense-MLP rule; the routing bias (1-D) replicates.
  * dense matmuls: the FFN-width / output-feature dim shards on ``model``.
  * FSDP (``fsdp=True``, the training default) additionally shards the
    d_model-side dim over the data axes — ('pod','data') jointly when
    divisible, else 'data' alone, else replicated.  ``fsdp=False``
    (serving) never places a data axis: weights are broadcast, not
    gathered per step.
  * norm scales, 1-D biases, and scalars replicate.

batches (``batch_specs``)
  * dim 0 (global batch) shards over the joint data axes, falling back to
    'data' alone, then replication (long-context batch=1).

KV caches (``cache_specs``)
  * k/v ``(B, S, KV, hd)``: batch over the data axes; kv-heads on
    ``model`` when divisible, else the sequence dim takes ``model``
    (flash-decoding split).  When the batch cannot shard (long_500k's
    B=1), the sequence dim also takes 'data'.
  * recurrent state (mamba/mLSTM/sLSTM): batch over data axes; the widest
    trailing state dim on ``model``.
  * position rings replicate.

Specs are always full-rank: ``len(spec) == leaf.ndim``.  Scan-stacked
block leaves (under a ``'blocks'`` key) get a leading ``None`` for the
superblock dim.  Functions only read ``mesh.axis_names`` /
``mesh.devices.shape``, so they run on abstract stand-ins with no devices.
"""
from __future__ import annotations

import functools
import math
from typing import Any

import jax
from jax.sharding import PartitionSpec as P

from .topology import DP_AXES, TP_AXIS, axis_sizes

__all__ = ["param_specs", "batch_specs", "cache_specs", "on_mesh"]

_ATTN_PROJ = {"wq", "wk", "wv", "wo", "bq", "bk", "bv"}


def _key_names(path) -> list:
    names = []
    for k in path:
        if hasattr(k, "key"):
            names.append(str(k.key))
        elif hasattr(k, "idx"):
            names.append(f"[{k.idx}]")
        elif hasattr(k, "name"):
            names.append(str(k.name))
        else:  # pragma: no cover - unknown path entry kinds
            names.append(str(k))
    return names


class _Axes:
    """Divisibility-checked axis assignment for one mesh. Also reused by
    ``dist.hints`` so the activation fallback policy cannot drift from the
    tensor-layout one (``dp``/``tp`` override the topology defaults)."""

    def __init__(self, mesh, *, dp=None, tp=None):
        self.sizes = axis_sizes(mesh)
        tp = TP_AXIS if tp is None else tp
        self.tp = tp if tp in self.sizes else None
        self.dp = tuple(a for a in (DP_AXES if dp is None else dp) if a in self.sizes)

    def fits(self, dim: int, axes) -> bool:
        if not axes:
            return False
        axes = axes if isinstance(axes, tuple) else (axes,)
        return dim % math.prod(self.sizes[a] for a in axes) == 0

    def tp_if_divisible(self, dim: int):
        return self.tp if (self.tp and self.fits(dim, self.tp)) else None

    def dp_if_divisible(self, dim: int):
        """Joint data axes when divisible, else the innermost data axis
        alone, else None."""
        if self.dp and self.fits(dim, self.dp):
            return self.dp
        if len(self.dp) > 1 and self.fits(dim, self.dp[-1]):
            return self.dp[-1:]
        return None


def _stacked(names) -> int:
    """Leaves under a 'blocks' key carry a leading scan-stacked dim."""
    return 1 if "blocks" in names else 0


def param_specs(shapes: Any, mesh, *, fsdp: bool = True,
                attn_fallback: str = "replicate") -> Any:
    """PartitionSpec tree for a parameter tree (see module layout rules).

    ``shapes``: pytree of arrays or ShapeDtypeStructs (``Model.param_shapes``).
    ``fsdp``: additionally shard the d_model-side dim over the data axes.
    ``attn_fallback``: 'replicate' | 'head_dim' — what to do with attention
    projections whose head count does not divide the ``model`` axis.
    """
    if attn_fallback not in ("replicate", "head_dim"):
        raise ValueError(f"attn_fallback must be 'replicate' or 'head_dim', got {attn_fallback!r}")
    ax = _Axes(mesh)

    def one(path, leaf):
        names = _key_names(path)
        stacked = _stacked(names)
        dims = list(leaf.shape[stacked:])
        ent = [None] * len(dims)
        leaf_key = names[-1] if names else ""
        in_attn = ("attn" in names or "cross" in names) and leaf_key in _ATTN_PROJ
        in_moe = "moe" in names and "shared" not in names

        def fsdp_put(i):
            if fsdp and ent[i] is None:
                ent[i] = ax.dp_if_divisible(dims[i])

        def head_rule(i_heads, i_hd):
            got = ax.tp_if_divisible(dims[i_heads])
            if got is not None:
                ent[i_heads] = got
            elif attn_fallback == "head_dim":
                ent[i_hd] = ax.tp_if_divisible(dims[i_hd])

        if len(dims) <= 1:
            pass  # scalars, norm scales, 1-D biases: replicate
        elif in_attn:
            if leaf_key in ("wq", "wk", "wv"):      # (d, H|KV, hd)
                head_rule(-2, -1)
                fsdp_put(-3)
            elif leaf_key == "wo":                  # (H, hd, d)
                head_rule(-3, -2)
                fsdp_put(-1)
            else:                                   # bq/bk/bv (H|KV, hd)
                head_rule(-2, -1)
        elif "attn" in names and leaf_key == "wkv_a":  # MLA (d, r + rope): the latent
            fsdp_put(-2)                                # every head reads: replicated on model
        elif "attn" in names and leaf_key == "wkv_b":  # MLA (r, H, nope + v)
            head_rule(-2, -1)
        elif in_moe and leaf_key == "router":       # (d, E)
            ent[-1] = ax.tp_if_divisible(dims[-1])
            fsdp_put(-2)
        elif in_moe and leaf_key in ("w_gate", "w_up", "w_down"):
            # w_gate/w_up: (E, d, f); w_down: (E, f, d)
            i_ff = -1 if leaf_key != "w_down" else -2
            i_dm = -2 if leaf_key != "w_down" else -1
            got = ax.tp_if_divisible(dims[-3])
            if got is not None:
                ent[-3] = got                        # expert parallelism
            else:
                ent[i_ff] = ax.tp_if_divisible(dims[i_ff])  # expert-FFN shard
            fsdp_put(i_dm)
        elif "embed" in names and leaf_key in ("tokens", "unembed"):  # (V, D)
            ent[-2] = ax.tp_if_divisible(dims[-2])
            fsdp_put(-1)
        elif leaf_key in ("w_up", "w_gate", "w_down"):  # dense / shared MLP
            i_ff = -1 if leaf_key != "w_down" else -2
            i_dm = -2 if leaf_key != "w_down" else -1
            ent[i_ff] = ax.tp_if_divisible(dims[i_ff])
            fsdp_put(i_dm)
        else:
            # generic matmul-ish leaf (SSM projections, gates, recurrent
            # kernels): output-feature dim on `model`, FSDP on the input dim
            ent[-1] = ax.tp_if_divisible(dims[-1])
            if len(dims) >= 2 and ent[0] is None:
                fsdp_put(0)
        return P(*([None] * stacked + ent))

    return jax.tree_util.tree_map_with_path(one, shapes)


def on_mesh(fn, mesh):
    """``fn`` traced with ``mesh`` as the ambient abstract mesh, for
    ``jax.jit``: code inside that splits itself by hand over the mesh (the
    Mosaic kernels, which XLA cannot partition) sees its axes. ``fn`` itself
    where the mesh has one device or none."""
    if mesh is None or mesh.devices.size <= 1:
        return fn

    @functools.wraps(fn)
    def traced(*args, **kwargs):
        with jax.sharding.use_abstract_mesh(mesh.abstract_mesh):
            return fn(*args, **kwargs)

    return traced


def batch_specs(tree: Any, mesh) -> Any:
    """PartitionSpecs for model inputs: dim 0 (global batch) over the joint
    data axes when divisible, else 'data', else replicated."""
    ax = _Axes(mesh)

    def one(leaf):
        if leaf.ndim == 0:
            return P()
        return P(ax.dp_if_divisible(leaf.shape[0]), *([None] * (leaf.ndim - 1)))

    return jax.tree.map(one, tree)


def cache_specs(tree: Any, mesh, cfg) -> Any:
    """PartitionSpecs for a decode/prefill cache tree (see layout rules).

    ``cfg`` is accepted for rule symmetry with the engine call sites; the
    rules themselves are shape-driven so they hold for windowed ring
    buffers, cross caches, and recurrent state alike.
    """
    del cfg  # shape-driven; see docstring
    ax = _Axes(mesh)

    def one(path, leaf):
        names = _key_names(path)
        stacked = _stacked(names)
        dims = list(leaf.shape[stacked:])
        ent = [None] * len(dims)
        leaf_key = names[-1] if names else ""

        if leaf_key in ("k", "v") and len(dims) == 4:   # (B, S, KV, hd)
            B, S, KV, _hd = dims
            b_ax = ax.dp_if_divisible(B)
            ent[0] = b_ax
            seq = []
            if ax.tp_if_divisible(KV) is not None:
                ent[2] = ax.tp                      # kv-head sharding
            elif ax.tp_if_divisible(S) is not None:
                seq.append(ax.tp)                   # flash-decoding: seq on model
            if b_ax is None and "data" in ax.sizes and ax.fits(S, "data"):
                seq.insert(0, "data")               # long-context: seq on data
            if seq:
                ent[1] = tuple(seq) if len(seq) > 1 else seq[0]
        elif leaf_key == "pos" or len(dims) <= 1:
            pass                                    # position rings replicate
        else:
            # recurrent state (B, ...): batch over data axes; the widest
            # trailing divisible dim takes `model`.
            ent[0] = ax.dp_if_divisible(dims[0])
            trailing = sorted(range(1, len(dims)), key=lambda i: -dims[i])
            for i in trailing:
                if ax.tp_if_divisible(dims[i]) is not None:
                    ent[i] = ax.tp
                    break
        return P(*([None] * stacked + ent))

    return jax.tree_util.tree_map_with_path(one, tree)
