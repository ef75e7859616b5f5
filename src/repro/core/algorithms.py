"""Fused shard_map executors + XLA one-shot baselines for the bcast family.

Generic schedule replay lives in :mod:`repro.comm.executors`
(``execute_collective`` unrolled / ``execute_compiled`` fori_loop over the
host-side lowering — the production path, compact HLO independent of chunk
count for EVERY schedule); :func:`execute_schedule` /
:func:`execute_reduce_schedule` here are thin compatibility wrappers. The
hand-written :func:`pipelined_chain_fused` / :func:`ring_allreduce`
fori_loop executors remain as the original single-op references the generic
compiled executor is tested against.

All functions here run *inside* ``jax.shard_map`` over a named axis. The
buffer convention is ``(num_chunks, chunk_elems)``; every rank holds a buffer
of identical shape, only the root's content matters on entry, and on exit all
ranks hold the root's data.

Baselines ("the vendor library"): :func:`xla_psum_bcast` and
:func:`xla_allgather_bcast` use XLA's native one-shot collectives — the TPU
stand-ins for NCCL's broadcast (see DESIGN.md Sec. 2).
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
from jax import lax

from .schedules import Schedule, build

__all__ = [
    "execute_schedule",
    "execute_reduce_schedule",
    "pipelined_chain_fused",
    "xla_psum_bcast",
    "xla_allgather_bcast",
    "schedule_bcast",
]


def _axis_size(axis_name) -> int:
    return lax.axis_size(axis_name)


def execute_schedule(schedule: Schedule, buf: jax.Array, axis_name) -> jax.Array:
    """Replay a bcast schedule. ``buf``: (num_chunks, chunk_elems).

    Thin wrapper over the ONE generalized executor
    (:func:`repro.comm.executors.execute_collective`) — kept for the
    original API surface.
    """
    if schedule.kind != "bcast":
        raise ValueError("use execute_reduce_schedule for reduce schedules")
    from ..comm.executors import execute_collective

    return execute_collective(schedule, buf, axis_name)


def execute_reduce_schedule(schedule: Schedule, buf: jax.Array, axis_name) -> jax.Array:
    """Replay a reduce-to-root schedule (sum combiner) over a whole buffer
    of any shape. Wrapper over the generalized executor."""
    if schedule.kind != "reduce":
        raise ValueError("not a reduce schedule")
    from ..comm.executors import execute_collective

    shape = buf.shape
    out = execute_collective(schedule, jnp.ravel(buf).reshape(1, -1), axis_name)
    return out.reshape(shape)


def pipelined_chain_fused(
    buf: jax.Array, axis_name, *, root: int = 0, unroll: int = 1
) -> jax.Array:
    """Fused executor for the paper's pipelined chain (Eq. 5).

    ``buf``: (num_chunks, chunk_elems). Emits ONE ppermute inside a
    ``fori_loop`` of ``num_chunks + n - 2`` rounds — HLO size is independent
    of the chunk count, unlike the generic unrolled executor.

    Round ``s``: the rank at logical chain position ``p`` sends chunk
    ``s - p`` (if valid) to position ``p + 1`` and accepts chunk
    ``s - p + 1`` from position ``p - 1``.
    """
    n = _axis_size(axis_name)
    if n == 1:
        return buf
    num_chunks, chunk = buf.shape
    perm = [((root + j) % n, (root + j + 1) % n) for j in range(n - 1)]
    pos = (lax.axis_index(axis_name) - root) % n

    def body(s, b):
        c_send = jnp.clip(s - pos, 0, num_chunks - 1)
        operand = lax.dynamic_slice(b, (c_send, 0), (1, chunk))
        received = lax.ppermute(operand, axis_name, perm)
        c_in = s - pos + 1
        valid = (pos >= 1) & (c_in >= 0) & (c_in < num_chunks)
        c_recv = jnp.clip(c_in, 0, num_chunks - 1)
        current = lax.dynamic_slice(b, (c_recv, 0), (1, chunk))
        merged = jnp.where(valid, received, current)
        return lax.dynamic_update_slice(b, merged, (c_recv, 0))

    return lax.fori_loop(0, num_chunks + n - 2, body, buf, unroll=unroll)


def ring_allreduce(x: jax.Array, axis_name, *, unroll: int = 1) -> jax.Array:
    """PAPER FUTURE-WORK (Sec. VII): explicit bandwidth-optimal ring
    allreduce — reduce-scatter phase (n-1 rounds, each rank accumulates one
    chunk) followed by an all-gather phase (n-1 rounds), built from the same
    ppermute substrate as the broadcast library. Total wire: 2M(n-1)/n per
    rank — matches the one-shot psum's bandwidth while staying inside the
    explicit-schedule framework (tunable, hierarchical-composable).
    """
    n = _axis_size(axis_name)
    if n == 1:
        return x
    shape, dtype = x.shape, x.dtype
    flat = jnp.ravel(x)
    chunk = -(-flat.size // n)
    pad = n * chunk - flat.size
    if pad:
        flat = jnp.concatenate([flat, jnp.zeros((pad,), dtype)])
    buf = flat.reshape(n, chunk)
    rank = lax.axis_index(axis_name)
    perm = [(i, (i + 1) % n) for i in range(n)]

    # reduce-scatter: at step s, rank r sends chunk (r - s) mod n; after
    # n-1 steps rank r owns the full sum of chunk (r + 1) mod n.
    def rs_body(s, state):
        b, acc = state
        send_idx = (rank - s) % n
        operand = jnp.where(
            s == 0,
            lax.dynamic_slice(b, (send_idx, 0), (1, chunk))[0],
            acc,
        )
        received = lax.ppermute(operand, axis_name, perm)
        recv_idx = (rank - s - 1) % n
        acc = received + lax.dynamic_slice(b, (recv_idx, 0), (1, chunk))[0]
        return b, acc

    acc0 = lax.pcast(jnp.zeros((chunk,), dtype), axis_name, to="varying")
    _, acc = lax.fori_loop(0, n - 1, rs_body, (buf, acc0), unroll=unroll)
    owned = (rank + 1) % n
    buf = lax.dynamic_update_slice(buf, acc[None], (owned, 0))

    # all-gather: circulate the reduced chunks for n-1 rounds.
    def ag_body(s, b):
        idx = (rank + 1 - s) % n
        operand = lax.dynamic_slice(b, (idx, 0), (1, chunk))
        received = lax.ppermute(operand, axis_name, perm)
        recv_idx = (rank - s) % n
        return lax.dynamic_update_slice(b, received, (recv_idx, 0))

    buf = lax.fori_loop(0, n - 1, ag_body, buf, unroll=unroll)
    out = buf.reshape(-1)
    if pad:
        out = out[: flat.size - pad]
    return out.reshape(shape)


# ---------------------------------------------------------------------------
# XLA-native one-shot baselines (the "NCCL" of the TPU world)
# ---------------------------------------------------------------------------


def xla_psum_bcast(x: jax.Array, axis_name, *, root: int = 0) -> jax.Array:
    """Broadcast by masking non-root contributions and all-reducing."""
    keep = lax.axis_index(axis_name) == root
    return lax.psum(jnp.where(keep, x, jnp.zeros_like(x)), axis_name)


def xla_allgather_bcast(x: jax.Array, axis_name, *, root: int = 0) -> jax.Array:
    """Broadcast via all_gather + select of the root slice (n*M on the wire)."""
    gathered = lax.all_gather(x, axis_name, axis=0)
    return gathered[root]


# ---------------------------------------------------------------------------
# Convenience: build + execute for a named algorithm over a chunked buffer
# ---------------------------------------------------------------------------


def schedule_bcast(
    buf: jax.Array,
    axis_name,
    *,
    algo: str,
    root: int = 0,
    fused: bool = True,
    **algo_kw,
) -> jax.Array:
    """Broadcast a (num_chunks, chunk) buffer with the named algorithm."""
    n = _axis_size(axis_name)
    if n == 1:
        return buf
    num_chunks = buf.shape[0]
    # The compiled fori_loop executor emits one ppermute per lane class
    # regardless of chunk count, but its constant perms transmit garbage
    # during pipeline fill/drain ((K + n - 2)/K x the useful bytes). The
    # unrolled schedule executor sends EXACTLY the schedule's transfers.
    # Use the exact one while its HLO stays small; fall back to the generic
    # compiled replay for huge round counts (same policy as
    # comm.api.apply_plan).
    if algo in ("pipelined_chain", "bidir_chain") and fused and (num_chunks + n - 2) > 256:
        from ..comm.executors import execute_compiled

        sched = build(algo, n, root, num_chunks=num_chunks, **algo_kw)
        return execute_compiled(sched, buf, axis_name)
    if algo in ("pipelined_chain", "bidir_chain"):
        sched = build(algo, n, root, num_chunks=num_chunks, **algo_kw)
    elif algo == "scatter_allgather":
        if num_chunks != n:
            raise ValueError(f"scatter_allgather wants num_chunks == n ({n}), got {num_chunks}")
        sched = build(algo, n, root, **algo_kw)
    else:
        if num_chunks != 1:
            # whole-message algorithms view the buffer as one chunk
            buf2 = buf.reshape(1, -1)
            out = schedule_bcast(buf2, axis_name, algo=algo, root=root, fused=fused, **algo_kw)
            return out.reshape(buf.shape)
        sched = build(algo, n, root, **algo_kw)
    return execute_schedule(sched, buf, axis_name)
