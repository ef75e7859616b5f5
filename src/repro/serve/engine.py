"""Serving engine: batched prefill + step-synchronous greedy decode.

``serve_step`` (one new token against the KV cache) is the function the
decode-shape dry-runs lower. Weight distribution at engine start uses the
paper's tuned broadcast (weights enter on the root and are pbcast to the
data axis) when a multi-device mesh is present.
"""
from __future__ import annotations

import dataclasses
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import NamedSharding, PartitionSpec as P

from .. import comm
from ..configs.base import ModelConfig
from ..dist import topology
from ..dist.sharding import cache_specs, on_mesh, param_specs
from ..models import Model

__all__ = [
    "Engine",
    "GenerationResult",
    "distribute_weights",
    "distribution_stream_graph",
    "plan_distribution",
]


def _placements(mesh, specs):
    return jax.tree.map(
        lambda s: NamedSharding(mesh, s), specs, is_leaf=lambda x: isinstance(x, P)
    )


@dataclasses.dataclass
class GenerationResult:
    tokens: np.ndarray          # (B, steps)
    logprobs: np.ndarray        # (B, steps)
    prefill_len: int


class Engine:
    """On a multi-device mesh the engine consumes ``repro.dist`` layouts:
    weights land on ``param_specs(fsdp=False, attn_fallback='head_dim')``
    (TP-only serving layout, head_dim split for non-divisible heads) and
    prefill-built KV caches are placed per ``cache_specs``."""

    def __init__(self, cfg: ModelConfig, params, *, mesh=None, max_len: int = 0,
                 distribute: bool = False, double_buffer: bool = False,
                 drain_dir: Optional[str] = None):
        if cfg.mla:
            raise NotImplementedError(
                f"{cfg.name}: multi-head latent attention needs a latent KV cache (the "
                "compressed c_kv and the shared RoPE key) for prefill and decode; the "
                "engine has none yet")
        self.cfg = cfg
        self.model = Model(cfg)
        self.mesh = mesh
        self.max_len = max_len
        self._sharded = mesh is not None and mesh.devices.size > 1
        if self._sharded:
            pspecs = param_specs(
                self.model.param_shapes(), mesh, fsdp=False, attn_fallback="head_dim"
            )
            if distribute:
                # the engine owns the freshly-loaded weights here — donate
                # them so distribution never doubles the resident footprint
                params = distribute_weights(
                    params, mesh, specs=pspecs, double_buffer=double_buffer,
                    donate=True, drain_dir=drain_dir,
                )
            else:
                params = jax.device_put(params, _placements(mesh, pspecs))
        self.params = params
        self._prefill = jax.jit(
            on_mesh(lambda p, b, ml: self.model.prefill(p, b, max_len=ml), mesh),
            static_argnums=(2,),
        )
        self._step = jax.jit(self.model.decode_step)

    def _place_caches(self, caches):
        if not self._sharded:
            return caches
        specs = cache_specs(caches, self.mesh, self.cfg)
        return jax.device_put(caches, _placements(self.mesh, specs))

    def generate(
        self,
        batch: dict,
        *,
        steps: int,
        greedy: bool = True,
        temperature: float = 1.0,
        seed: int = 0,
    ) -> GenerationResult:
        cfg = self.cfg
        T = batch["tokens"].shape[1]
        max_len = self.max_len or (T + steps)
        logits, caches = self._prefill(self.params, batch, max_len)
        caches = self._place_caches(caches)
        offset = cfg.prefix_len if cfg.frontend == "vision" else 0
        # the unembedding is padded past the vocabulary; a padded id is no
        # token, so sampling and log-probabilities see the real ids only
        vocab = cfg.vocab_size
        cur = logits[:, -1, :vocab]
        toks, lps = [], []
        key = jax.random.PRNGKey(seed)
        for i in range(steps):
            if greedy:
                nxt = jnp.argmax(cur, axis=-1)
            else:
                key, sub = jax.random.split(key)
                nxt = jax.random.categorical(sub, cur / temperature, axis=-1)
            lp = jax.nn.log_softmax(cur, axis=-1)
            lps.append(np.asarray(jnp.take_along_axis(lp, nxt[:, None], axis=-1)[:, 0]))
            toks.append(np.asarray(nxt))
            logits, caches = self._step(
                self.params,
                nxt[:, None].astype(jnp.int32),
                caches,
                jnp.asarray(T + offset + i, jnp.int32),
            )
            cur = logits[:, 0, :vocab]
        return GenerationResult(
            tokens=np.stack(toks, axis=1), logprobs=np.stack(lps, axis=1), prefill_len=T
        )


def plan_distribution(params, mesh, *, algo: str = "auto", tuner=None,
                      bucket_bytes: int = 4 << 20, stream: str | None = None):
    """Host-side planning for weight distribution: pack the parameter tree
    into same-dtype buckets and resolve one :class:`~repro.comm.
    CollectivePlan` per (bucket, mesh level) — inter-pod level first, priced
    with the tuner's ``inter_pod`` constants. Returns ``(bucket_spec,
    {axis_name: [plan per bucket]})``; the plans are inspectable (algorithm,
    chunking, predicted time, bytes on wire) before anything is traced.
    ``stream`` keys the plan cache on a stream-graph fingerprint (see
    :func:`distribution_stream_graph`)."""
    from ..core import bucketing

    spec = bucketing.plan_buckets(params, bucket_bytes)
    sizes = topology.axis_sizes(mesh)
    plans = {}
    for ax in topology.bcast_axes(mesh):
        n = sizes[ax]
        # plan_cached: identical (bucket size, axis) points — across buckets
        # AND across engine restarts in one process — share one resolved
        # plan and its pre-lowered round tables
        plans[ax] = [
            comm.plan_cached(
                "bcast", M, n, algo=algo, tuner=tuner,
                inter_pod=topology.is_inter_pod(ax), stream=stream,
            )
            for M in spec.bucket_bytes()
        ]
    return spec, plans


def distribution_stream_graph(params, mesh, *, algo: str = "auto", tuner=None,
                              bucket_bytes: int = 4 << 20,
                              double_buffer: bool = False,
                              overlap_depth: int = 2, drain: bool = False):
    """Weight distribution as a :class:`~repro.comm.StreamGraph`.

    Two prioritized entries on distinct links:

    * ``ckpt_drain`` (present when ``drain``) — the host-side snapshot of
      the pre-distribution weights, priority 2 on the ``host`` link. It
      carries the same bucket mix but no collective plans (one round per
      bucket over the host link in the simulator's accounting).
    * ``distribute`` — the tuned hierarchical broadcast over
      ``topology.bcast_axes(mesh)``, DAG-ordered ``after`` the drain
      (snapshot-before-donate: the drain must hold a valid copy before
      donation can invalidate the buffers), ``overlap_depth`` staging
      buffers deep when ``double_buffer``.

    The graph fingerprint is computed from the raw request BEFORE any plan
    resolves and keys ``plan_cached`` (``stream=``), so distribution plans
    never collide with another graph shape's at the same (op, M, n) point.
    Returns ``(graph, bucket_spec, plans)``."""
    from ..comm import streams as comm_streams
    from ..core import bucketing

    spec = bucketing.plan_buckets(params, bucket_bytes)
    sizes = topology.axis_sizes(mesh)
    axes = list(topology.bcast_axes(mesh))
    depth = max(1, int(overlap_depth)) if double_buffer else 1
    gkey = comm_streams.graph_key({
        "consumer": "serve.distribute_weights",
        "op": "bcast",
        "algo": algo,
        "axes": [[ax, int(sizes[ax])] for ax in axes],
        "buckets": list(spec.bucket_bytes()),
        "depth": depth,
        "drain": bool(drain),
    })
    bucket_spec, plans = plan_distribution(
        params, mesh, algo=algo, tuner=tuner, bucket_bytes=bucket_bytes,
        stream=gkey,
    )
    order = tuple(range(bucket_spec.num_buckets))  # load order, not reversed
    entries = []
    after: tuple[str, ...] = ()
    if drain:
        entries.append(comm_streams.StreamEntry(
            name="ckpt_drain", op="drain", spec=bucket_spec, axes=(),
            plans={}, order=order, overlap_depth=1, compute_s=0.0,
            depth_source="manual", priority=2, after=(), link="host",
        ))
        after = ("ckpt_drain",)
    entries.append(comm_streams.StreamEntry(
        name="distribute", op="bcast", spec=bucket_spec, axes=tuple(plans),
        plans={ax: tuple(ax_plans) for ax, ax_plans in plans.items()},
        order=order, overlap_depth=depth, compute_s=0.0,
        depth_source="manual", priority=1, after=after, link="ici",
    ))
    graph = comm_streams.StreamGraph(tuple(entries), key=gkey)
    return graph, bucket_spec, plans


def distribute_weights(params, mesh, *, algo: str = "auto", tuner=None, specs=None,
                       bucket_bytes: int = 4 << 20, return_plans: bool = False,
                       double_buffer: bool = False, overlap_depth: int = 2,
                       stage_chunk: int = 64 * 1024, donate: bool = False,
                       compiled: bool | None = None,
                       drain_dir: Optional[str] = None):
    """Broadcast freshly-loaded weights across the data axes with the tuned
    library (the paper's 'training parameters exchange' applied at load).

    The collective sequence is fully planned host-side
    (:func:`plan_distribution`) and the shard_map program replays those
    plans verbatim via ``comm.apply_plan`` — hierarchically per
    ``dist.topology.bcast_axes(mesh)``, inter-pod level first when a pod
    axis exists. When ``specs`` (a ``param_specs`` tree) is given, the
    replicated result is then laid out per those specs, so the weights land
    exactly where the serving/training layout declares. ``return_plans=True``
    additionally returns the executed plan table.

    Execution rides the multi-stream layer: distribution is the
    ``distribute`` entry of :func:`distribution_stream_graph` (with a
    ``ckpt_drain`` entry DAG-ordered before it when ``drain_dir`` is set —
    program order realizes the edge: the snapshot is fetched before the
    broadcast program runs). ``double_buffer=True`` widens the entry's
    staging window: bucket k+1 is staged through the ``chunked_copy``
    Pallas pipeline (Sec. IV-C) while bucket k's broadcast is in flight —
    ``overlap_depth`` staging buffers deep, buckets in load order.
    Per-bucket collectives are the SAME plans either way, so the
    distributed weights are identical.

    ``donate=True`` donates the incoming weight buffers to the broadcast
    program (``jax.jit(..., donate_argnums)``): combined with the compiled
    executor's in-place loop carry, distribution then never holds two full
    copies of a bucket in device memory. The caller's ``params`` are
    invalidated — pass it when the engine owns the freshly-loaded weights
    (the ``Engine(distribute=True)`` path does). ``compiled`` routes the
    per-bucket replay (None = tuned policy, see ``comm.api.apply_plan``).

    ``drain_dir``: graceful degradation on unrecoverable failure. If the
    distribution program itself raises (mesh lost a device mid-broadcast,
    compile failure, OOM), the pre-distribution weights are drained to an
    atomic checkpoint under ``drain_dir`` and a typed
    :class:`~repro.comm.WeightSyncError` is raised chaining the cause —
    never a silent partial distribution. The drain fetches the host copy
    before donation hands the buffers to the program, so the snapshot is
    valid even when ``donate=True`` invalidated the device buffers."""
    graph, bucket_spec, plans = distribution_stream_graph(
        params, mesh, algo=algo, tuner=tuner, bucket_bytes=bucket_bytes,
        double_buffer=double_buffer, overlap_depth=overlap_depth,
        drain=drain_dir is not None,
    )
    dist_entry = graph.entry("distribute")

    def run(p):
        return comm.execute_stream_entry(
            dist_entry, p, stage=double_buffer, stage_chunk=stage_chunk,
            compiled=compiled,
        )

    f = jax.shard_map(
        run,
        mesh=mesh,
        in_specs=(jax.tree.map(lambda _: P(), params),),
        out_specs=jax.tree.map(lambda _: P(), params),
        check_vma=False,
    )
    snapshot = None
    if drain_dir is not None:
        # host copy taken before donation can invalidate the device buffers;
        # host RAM is the cheap side of the serving node, device HBM is not
        snapshot = jax.tree.map(lambda a: np.asarray(jax.device_get(a)), params)
    try:
        out = jax.jit(f, donate_argnums=(0,) if donate else ())(params)
        if specs is not None:
            out = jax.device_put(out, _placements(mesh, specs))
    except Exception as e:  # noqa: BLE001 — rewrapped as a typed, actionable error
        if snapshot is None:
            raise
        from ..comm.faults import WeightSyncError
        from ..train import checkpoint as ckpt_lib

        try:
            fname = ckpt_lib.save_checkpoint(drain_dir, 0, snapshot)
        except Exception as drain_err:  # pragma: no cover - disk-full etc.
            raise WeightSyncError(
                f"weight distribution failed ({type(e).__name__}: {e}) AND the "
                f"drain to {drain_dir!r} also failed "
                f"({type(drain_err).__name__}: {drain_err}); weights may be lost"
            ) from e
        raise WeightSyncError(
            f"weight distribution failed ({type(e).__name__}: {e}); "
            f"pre-distribution weights drained to {fname} — restore from the "
            f"checkpoint and replan on a healthy mesh"
        ) from e
    return (out, plans) if return_plans else out
