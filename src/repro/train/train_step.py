"""Train-step factories.

Three data-parallel synchronization modes (DESIGN.md Sec. 4):

* ``grad_allreduce`` — the modern baseline: pjit/GSPMD inserts the gradient
  all-reduce (and FSDP all-gathers/reduce-scatters) automatically. This is
  the "vendor collective" path, analogous to NCCL allreduce.

* ``param_bcast`` — the paper's CA-CNTK pattern as an explicit shard_map
  program over the data-parallel axis: per-rank gradients are reduced to the
  root with the reversed-binomial schedule, and the synchronized buffers are
  then *broadcast* with the tuned algorithm library (pipelined chain et al.)
  via ``core.bcast.pbcast_tree``. SPMD note recorded in DESIGN.md: we
  broadcast the root's reduced gradient rather than the updated parameters —
  byte-identical traffic and the same collective, but every rank can then
  apply the optimizer deterministically, keeping per-rank optimizer state
  coherent (CNTK keeps the optimizer on the root instead).

* ``tuned_allreduce`` — the follow-up-work pattern (Awan et al. 1810.11112,
  Mamidala 1802.06949): gradients sync through the ``repro.comm`` allreduce
  plan layer — bucketed (``core.bucketing``), hierarchical over the
  ``dist.topology`` data axes (intra-pod level first, the pod level priced
  with inter-pod constants), per-bucket algorithm selected by the per-op
  tuner (reduce_then_bcast / fused_rsb / ring_allreduce windows).

Per-bucket plans resolve through the host-side plan cache
(``comm.plan.plan_cached``) — identical (op, M, n) points across steps and
buckets share one ``CollectivePlan`` and its pre-lowered round tables — and
``run_cfg.compiled_collectives`` routes the replay between the exact
unrolled executor and the O(1)-HLO compiled fori_loop executor (DESIGN.md
Sec. 9). The step is jitted with params/opt-state donated (see
``train.trainer``), so the compiled replay updates gradient buckets in
place.
"""
from __future__ import annotations

from functools import partial
from typing import Callable

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import PartitionSpec as P

from ..comm import hierarchical_allreduce_axes, overlap_allreduce_tree, pallreduce_tree
from ..comm.streams import StreamSpec, execute_stream_entry, plan_streams
from ..configs.base import RunConfig
from ..core.algorithms import ring_allreduce
from ..core.bcast import pbcast_tree, preduce_sum
from ..core.tuner import Tuner
from ..launch.mesh import dp_axes
from ..optim.optimizers import Optimizer, clip_by_global_norm

__all__ = [
    "make_train_step",
    "make_bcast_train_step",
    "make_tuned_allreduce_train_step",
    "make_overlap_allreduce_train_step",
    "make_compressed_allreduce_train_step",
    "make_degraded_psum_train_step",
    "with_error_feedback",
]


def _microbatch(batch, k: int):
    return jax.tree.map(lambda x: x.reshape((k, x.shape[0] // k) + x.shape[1:]), batch)


def _grad_fn(model, run_cfg: RunConfig, grad_specs=None):
    def loss_fn(params, mb):
        # the backward pass and its recompute come out of this scope as
        # transpose(jvp(fwd)) and .../rematted_computation
        with jax.named_scope("fwd"):
            return model.loss(params, mb, remat=run_cfg.remat)

    vg = jax.value_and_grad(loss_fn, has_aux=True)

    def constrain(tree):
        if grad_specs is None:
            return tree
        return jax.tree.map(jax.lax.with_sharding_constraint, tree, grad_specs)

    def compute(params, batch):
        k = run_cfg.num_microbatches
        if k == 1:
            (loss, metrics), grads = vg(params, batch)
            return loss, metrics, grads

        def body(acc, mb):
            (loss, metrics), grads = vg(params, mb)
            acc = jax.tree.map(
                lambda a, g: a + g.astype(jnp.float32) / k, acc, constrain(grads)
            )
            return constrain(acc), (loss, metrics)

        zeros = constrain(
            jax.tree.map(lambda p: jnp.zeros(p.shape, jnp.float32), params)
        )
        grads, (losses, metricss) = jax.lax.scan(body, zeros, _microbatch(batch, k))
        metrics = jax.tree.map(lambda m: jnp.mean(m, axis=0), metricss)
        return jnp.mean(losses), metrics, grads

    return compute


def _apply_update(optimizer: Optimizer, lr_fn: Callable, grads, opt_state, params):
    """Clip, learning rate and optimizer update of every step maker, under
    one ``optimizer`` scope. Returns (params, opt_state, grad_norm, lr).
    Each step maker then hands the new parameters, the old ones and the
    step's metrics to ``model.update_router_bias``: a routing bias is a
    buffer moved by load, which the optimizer's result for it does not
    touch."""
    with jax.named_scope("optimizer"):
        grads, gnorm = clip_by_global_norm(grads, 1.0)
        lr = lr_fn(opt_state["step"])
        params, opt_state = optimizer.update(grads, opt_state, params, lr)
    return params, opt_state, gnorm, lr


def make_train_step(model, run_cfg: RunConfig, optimizer: Optimizer, lr_fn: Callable, grad_specs=None):
    """pjit path: sharding comes from in/out shardings; collectives are
    GSPMD-inserted (the baseline the paper's mode is compared against).
    ``grad_specs``: optional NamedSharding tree pinning the f32 grad
    accumulator to the parameter sharding (prevents a replicated buffer)."""
    compute = _grad_fn(model, run_cfg, grad_specs)

    def train_step(params, opt_state, batch):
        loss, metrics, grads = compute(params, batch)
        new, opt_state, gnorm, lr = _apply_update(optimizer, lr_fn, grads, opt_state, params)
        out = {"loss": loss, "grad_norm": gnorm, "lr": lr}
        out.update(metrics)
        return model.update_router_bias(new, params, out), opt_state, out

    return train_step


def make_bcast_train_step(
    model,
    run_cfg: RunConfig,
    optimizer: Optimizer,
    lr_fn: Callable,
    mesh,
    *,
    tuner: Tuner | None = None,
    root: int = 0,
):
    """The paper's sync mode: explicit reduce-to-root + tuned broadcast over
    the data axis. Requires a pure data-parallel mesh (model axis size 1) —
    the setting of the paper (n GPUs, replicated model)."""
    axis_sizes = dict(zip(mesh.axis_names, mesh.devices.shape))
    assert axis_sizes.get("model", 1) == 1, "param_bcast mode is pure-DP (paper setting)"
    dp = dp_axes(mesh)
    assert len(dp) >= 1
    compute = _grad_fn(model, run_cfg)
    n_dp = 1
    for a in dp:
        n_dp *= axis_sizes[a]

    def bcast_sync(grads):
        if run_cfg.bcast_algo == "ring_allreduce":
            # paper Sec. VII future work: the explicit bandwidth-optimal
            # ring allreduce from the same ppermute substrate
            for ax in dp:
                grads = jax.tree.map(lambda g: ring_allreduce(g, ax), grads)
            grads = jax.tree.map(lambda g: g / n_dp, grads)
        else:
            # --- the paper's collective sequence, bucketed & tuned ---
            for ax in dp:
                grads = jax.tree.map(lambda g: preduce_sum(g, ax, root=root), grads)
            grads = jax.tree.map(lambda g: g / n_dp, grads)
            for ax in reversed(dp):
                grads = pbcast_tree(
                    grads,
                    ax,
                    root=root,
                    algo=run_cfg.bcast_algo,
                    tuner=tuner,
                    bucket_bytes=run_cfg.bcast_bucket_bytes,
                    inter_pod=(ax == "pod"),
                )
        return grads

    def local_step(params, opt_state, batch):
        # per-rank grads on the local shard of the batch
        loss, metrics, grads = compute(params, batch)
        with jax.named_scope("grad_sync"):
            grads = bcast_sync(grads)
        # deterministic, identical update on every rank
        new, opt_state, gnorm, lr = _apply_update(optimizer, lr_fn, grads, opt_state, params)
        loss = jax.lax.pmean(loss, dp)
        out = {"loss": loss, "grad_norm": gnorm, "lr": lr}
        out.update({k: jax.lax.pmean(v, dp) for k, v in metrics.items()})
        return model.update_router_bias(new, params, out), opt_state, out

    return _wrap_dp_step(local_step, mesh, dp)


def _wrap_dp_step(local_step, mesh, dp):
    """shard_map wrapper shared by the explicit-sync modes: params/opt state
    replicated, batch sharded over the data axes, outputs replicated."""
    replicated = P()

    def batch_spec(x):
        return P(dp, *([None] * (x.ndim - 1)))

    def train_step(params, opt_state, batch):
        in_specs = (
            jax.tree.map(lambda _: replicated, params),
            jax.tree.map(lambda _: replicated, opt_state),
            jax.tree.map(batch_spec, batch),
        )
        out_specs = (
            jax.tree.map(lambda _: replicated, params),
            jax.tree.map(lambda _: replicated, opt_state),
            replicated,
        )
        fn = jax.shard_map(
            local_step, mesh=mesh, in_specs=in_specs, out_specs=out_specs,
            check_vma=False,
        )
        return fn(params, opt_state, batch)

    return train_step


def make_tuned_allreduce_train_step(
    model,
    run_cfg: RunConfig,
    optimizer: Optimizer,
    lr_fn: Callable,
    mesh,
    *,
    tuner: Tuner | None = None,
):
    """Gradient sync through the ``repro.comm`` collective-plan subsystem.

    Per-rank gradients are packed into same-dtype buckets and all-reduced
    hierarchically: intra-pod data axes first, then the pod level with
    inter-pod pricing (``comm.hierarchical_allreduce_axes``). Each bucket's
    algorithm/chunking is a per-op ``CollectivePlan`` decision — set
    ``run_cfg.allreduce_algo`` to pin one. Pure-DP like ``param_bcast``
    (model axis size 1), and produces the same update as ``grad_allreduce``
    up to float summation order.
    """
    def sync(grads, axes, inter_pod_axes):
        return pallreduce_tree(
            grads,
            axes,
            algo=run_cfg.allreduce_algo,
            tuner=tuner,
            bucket_bytes=run_cfg.bcast_bucket_bytes,
            inter_pod_axes=inter_pod_axes,
            compiled=run_cfg.compiled_collectives,
        )

    return _make_comm_sync_step(
        model, run_cfg, mesh, sync, optimizer, lr_fn, mode="tuned_allreduce"
    )


def make_overlap_allreduce_train_step(
    model,
    run_cfg: RunConfig,
    optimizer: Optimizer,
    lr_fn: Callable,
    mesh,
    *,
    tuner: Tuner | None = None,
):
    """Gradient sync through the overlap engine (``repro.comm.overlap``).

    Same bucketing, hierarchy levels, and per-bucket ``CollectivePlan``s as
    ``tuned_allreduce`` — so parameters match it (and the GSPMD psum
    baseline) up to float summation order — but buckets stream in
    backward-dispatch order inside the tuned in-flight window
    (``run_cfg.overlap_depth``; ``None`` = tuned), letting the scheduler
    hide collectives behind the rest of the step (the CNTK end-to-end
    pattern, paper Sec. V-D; Awan et al. 1810.11112).

    With ``run_cfg.prefetch_stream`` the step carries a SECOND comm stream:
    right after ``optimizer.update`` the updated (replicated) parameters are
    re-broadcast as a lower-priority ``weight_prefetch`` entry of a 2-entry
    :class:`~repro.comm.streams.StreamGraph`, DAG-ordered ``after`` the
    ``grad_sync`` entry. The bcast is value-identical (every rank already
    holds the same params), so results are bit-unchanged — what it buys is
    the wire schedule: next step's weights are pre-staged on the link the
    arbiter grants between gradient buckets. Both entries resolve through
    ``plan_streams`` (shared ``plan_cached`` path keyed on the graph
    fingerprint), and the DAG edge is realized by program order — grad sync
    executes inside the step, the prefetch entry after the update.
    """
    if not run_cfg.prefetch_stream:

        def sync(grads, axes, inter_pod_axes):
            return overlap_allreduce_tree(
                grads,
                axes,
                algo=run_cfg.allreduce_algo,
                tuner=tuner,
                bucket_bytes=run_cfg.bcast_bucket_bytes,
                inter_pod_axes=inter_pod_axes,
                overlap_depth=run_cfg.overlap_depth,
                compute_s=run_cfg.overlap_compute_s,
                compiled=run_cfg.compiled_collectives,
            )

        return _make_comm_sync_step(
            model, run_cfg, mesh, sync, optimizer, lr_fn, mode="overlap_allreduce"
        )

    from ..dist import topology

    if tuner is not None:
        # surface the stream decisions in the tuner table (stream:* entries
        # survive save/load, so a calibrated table pins them for later runs)
        tuner.record_stream(
            "grad_sync", priority=1, overlap_depth=run_cfg.overlap_depth
        )
        tuner.record_stream("weight_prefetch", priority=0)

    axis_sizes = dict(zip(mesh.axis_names, mesh.devices.shape))
    sized_axes = tuple(
        (a, axis_sizes[a])
        for a in hierarchical_allreduce_axes(mesh)
        if axis_sizes.get(a, 1) > 1
    )
    inter = tuple(topology.inter_pod_axes(mesh))
    pshapes = model.param_shapes()
    # grads share the params' treedef/shapes; the microbatch accumulator
    # holds them in f32 (see _grad_fn), so the grad_sync bucket mix must be
    # planned at that dtype
    gshapes = pshapes
    if run_cfg.num_microbatches > 1:
        gshapes = jax.tree.map(
            lambda p: jax.ShapeDtypeStruct(p.shape, jnp.float32), pshapes
        )
    graph = plan_streams(
        [
            StreamSpec(
                name="grad_sync", tree=gshapes, axes=sized_axes,
                op="allreduce", algo=run_cfg.allreduce_algo, priority=1,
                overlap_depth=run_cfg.overlap_depth,
                compute_s=run_cfg.overlap_compute_s,
                bucket_bytes=run_cfg.bcast_bucket_bytes,
                inter_pod_axes=inter, reverse=True,
            ),
            StreamSpec(
                name="weight_prefetch", tree=pshapes, axes=sized_axes,
                op="bcast", algo=run_cfg.bcast_algo, priority=0,
                after=("grad_sync",),
                bucket_bytes=run_cfg.bcast_bucket_bytes,
                inter_pod_axes=inter, reverse=False,
            ),
        ],
        tuner=tuner,
    )
    grad_entry = graph.entry("grad_sync")
    prefetch_entry = graph.entry("weight_prefetch")

    def sync(grads, axes, inter_pod_axes):
        return execute_stream_entry(
            grad_entry, grads, compiled=run_cfg.compiled_collectives
        )

    def post_update(params, axes, inter_pod_axes):
        return execute_stream_entry(
            prefetch_entry, params, compiled=run_cfg.compiled_collectives
        )

    return _make_comm_sync_step(
        model, run_cfg, mesh, sync, optimizer, lr_fn,
        mode="overlap_allreduce", post_update=post_update,
    )


def with_error_feedback(optimizer: Optimizer) -> Optimizer:
    """Wrap an :class:`Optimizer` so its state carries the error-feedback
    residual tree at ``state['ef']`` (f32 zeros like params at init).

    ``update`` passes the residual through unchanged — the compressed train
    step owns the residual's read-modify-write (it must see the residual
    BEFORE the optimizer step and store the new one after). Wrapping here
    (rather than ad-hoc state surgery in the step) keeps ``init``,
    ``jax.eval_shape(optimizer.init, ...)`` for checkpoint restore, and the
    donation contract all consistent with one state treedef."""
    from ..comm.compress import CompressionState

    def init(params):
        state = dict(optimizer.init(params))
        state["ef"] = CompressionState.init(params)
        return state

    def update(grads, state, params, lr):
        inner = {k: v for k, v in state.items() if k != "ef"}
        new_params, new_inner = optimizer.update(grads, inner, params, lr)
        new_state = dict(new_inner)
        new_state["ef"] = state["ef"]
        return new_params, new_state

    return Optimizer(optimizer.name + "+ef", init, update)


def make_compressed_allreduce_train_step(
    model,
    run_cfg: RunConfig,
    optimizer: Optimizer,
    lr_fn: Callable,
    mesh,
    *,
    tuner: Tuner | None = None,
):
    """Gradient sync over a compressed wire with error feedback.

    Same bucketing, hierarchy, and per-bucket ``CollectivePlan``s as
    ``tuned_allreduce``, but every hop ships ``run_cfg.wire_format``
    ('bf16'|'fp8'|'int8'): compressed formats quantize each chunk to 1
    byte/element plus per-256-element-block f32 scales at the ppermute seam
    (combine arithmetic stays f32). The quantization error is not discarded
    — each step's residual ``e`` is carried in ``opt_state['ef']`` (the
    optimizer must be wrapped with :func:`with_error_feedback`) and
    re-injected into the next step's gradient (EF-SGD, Karimireddy et al.):

        c_t = g_t + e_t            # compensate
        sync = allreduce(Q(c_t))   # compressed wire
        e_{t+1} = c_t - Q(c_t)     # this rank's quantization error

    The residual models the rank's OWN first-hop quantization error;
    multi-hop recompression error inside the schedule is not re-captured
    (standard EF approximation — the residual still bounds the bias, which
    is what makes the trajectory track the full-precision baseline).

    With ``wire_format='bf16'`` the wire is the bit-identical passthrough:
    the step skips compensation entirely (the residual is identically zero,
    and even a value-preserving ``g.astype(f32)`` would change the sync's
    bucket dtype and summation precision), so it syncs exactly the buffers
    ``tuned_allreduce`` syncs and produces bit-identical parameters.
    """
    from ..comm.compress import CompressionState, normalize_wire_format
    from ..dist import topology

    fmt = normalize_wire_format(run_cfg.wire_format)
    axis_sizes = dict(zip(mesh.axis_names, mesh.devices.shape))
    assert axis_sizes.get("model", 1) == 1, "compressed_allreduce mode is pure-DP"
    dp = dp_axes(mesh)
    assert len(dp) >= 1
    compute = _grad_fn(model, run_cfg)
    n_dp = 1
    for a in dp:
        n_dp *= axis_sizes[a]
    axes = [a for a in hierarchical_allreduce_axes(mesh) if axis_sizes.get(a, 1) > 1]
    inter_pod_axes = topology.inter_pod_axes(mesh)

    def local_step(params, opt_state, batch):
        loss, metrics, grads = compute(params, batch)
        comp = (
            CompressionState.compensate(grads, opt_state["ef"])
            if fmt.compressed
            else grads
        )
        with jax.named_scope("grad_sync"):
            synced = pallreduce_tree(
                comp,
                axes,
                algo=run_cfg.allreduce_algo,
                tuner=tuner,
                bucket_bytes=run_cfg.bcast_bucket_bytes,
                inter_pod_axes=inter_pod_axes,
                compiled=run_cfg.compiled_collectives,
                wire_format=fmt.value,
            )
        new_ef = (
            CompressionState.update(comp, fmt.value)
            if fmt.compressed
            else opt_state["ef"]
        )
        with jax.named_scope("grad_sync"):
            grads = jax.tree.map(lambda g: g / n_dp, synced)
        new, opt_state, gnorm, lr = _apply_update(optimizer, lr_fn, grads, opt_state, params)
        opt_state = dict(opt_state, ef=new_ef)
        loss = jax.lax.pmean(loss, dp)
        out = {"loss": loss, "grad_norm": gnorm, "lr": lr}
        out.update({k: jax.lax.pmean(v, dp) for k, v in metrics.items()})
        return model.update_router_bias(new, params, out), opt_state, out

    return _wrap_dp_step(local_step, mesh, dp)


def make_degraded_psum_train_step(
    model,
    run_cfg: RunConfig,
    optimizer: Optimizer,
    lr_fn: Callable,
    mesh,
    *,
    health,
):
    """Graceful-degradation sync: psum over SURVIVORS with corrected mean
    normalization (``comm.faults.MeshHealth``).

    When ranks die mid-run the tuned schedules are unusable until a replan,
    but training can limp on: every rank's gradient is masked by its
    liveness bit before the psum and the mean divides by the survivor count
    — so the surviving ranks compute exactly the ``n_surv``-way
    data-parallel update (dividing by the full ``n_dp`` would silently
    shrink the effective learning rate by ``n_surv / n_dp``; that silent
    skew is the bug this factory exists to prevent). Ranks are linearized
    over the data axes in mesh order, matching ``MeshHealth`` rank ids.

    The dead ranks' processes (when still running — e.g. a degraded link
    rather than a lost host) contribute zeros and receive the same
    replicated update, so the mesh stays parameter-coherent for a later
    recovery replan."""
    from ..comm.faults import DeadRankError

    axis_sizes = dict(zip(mesh.axis_names, mesh.devices.shape))
    assert axis_sizes.get("model", 1) == 1, "degraded_psum mode is pure-DP"
    dp = dp_axes(mesh)
    assert len(dp) >= 1
    compute = _grad_fn(model, run_cfg)
    n_dp = 1
    for a in dp:
        n_dp *= axis_sizes[a]
    if health.n != n_dp:
        raise ValueError(f"health report is for n={health.n}, mesh has n_dp={n_dp}")
    survivors = health.survivors()
    n_surv = len(survivors)
    if n_surv == 0:
        raise DeadRankError("no surviving data-parallel ranks; restore from checkpoint")
    alive = np.zeros((n_dp,), np.float32)
    alive[list(survivors)] = 1.0

    def local_step(params, opt_state, batch):
        loss, metrics, grads = compute(params, batch)
        r = jnp.zeros((), jnp.int32)
        for a in dp:
            r = r * axis_sizes[a] + jax.lax.axis_index(a)
        m = jnp.asarray(alive)[r]

        def survivor_mean(v):
            v = v * m.astype(v.dtype)
            for ax in dp:
                v = jax.lax.psum(v, ax)
            return v / n_surv

        with jax.named_scope("grad_sync"):
            grads = jax.tree.map(survivor_mean, grads)
        new, opt_state, gnorm, lr = _apply_update(optimizer, lr_fn, grads, opt_state, params)
        loss = survivor_mean(loss)
        out = {"loss": loss, "grad_norm": gnorm, "lr": lr}
        out.update({k: survivor_mean(v) for k, v in metrics.items()})
        return model.update_router_bias(new, params, out), opt_state, out

    return _wrap_dp_step(local_step, mesh, dp)


def _make_comm_sync_step(model, run_cfg, mesh, sync, optimizer, lr_fn, *, mode,
                         post_update=None):
    """Shared body of the repro.comm gradient-sync modes: pure-DP shard_map
    step whose gradient all-reduce is ``sync(grads, axes, inter_pod_axes)``.
    ``post_update(params, axes, inter_pod_axes)`` runs right after the
    optimizer step — the hook the weight-prefetch stream entry rides
    (value-preserving: it must return params unchanged up to layout)."""
    from ..dist import topology

    axis_sizes = dict(zip(mesh.axis_names, mesh.devices.shape))
    assert axis_sizes.get("model", 1) == 1, f"{mode} mode is pure-DP"
    dp = dp_axes(mesh)
    assert len(dp) >= 1
    compute = _grad_fn(model, run_cfg)
    n_dp = 1
    for a in dp:
        n_dp *= axis_sizes[a]
    axes = [a for a in hierarchical_allreduce_axes(mesh) if axis_sizes.get(a, 1) > 1]
    inter_pod_axes = topology.inter_pod_axes(mesh)

    def local_step(params, opt_state, batch):
        loss, metrics, grads = compute(params, batch)
        with jax.named_scope("grad_sync"):
            grads = sync(grads, axes, inter_pod_axes)
            grads = jax.tree.map(lambda g: g / n_dp, grads)
        new, opt_state, gnorm, lr = _apply_update(optimizer, lr_fn, grads, opt_state, params)
        if post_update is not None:
            new = post_update(new, axes, inter_pod_axes)
        loss = jax.lax.pmean(loss, dp)
        out = {"loss": loss, "grad_norm": gnorm, "lr": lr}
        out.update({k: jax.lax.pmean(v, dp) for k, v in metrics.items()})
        return model.update_router_bias(new, params, out), opt_state, out

    return _wrap_dp_step(local_step, mesh, dp)
