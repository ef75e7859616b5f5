"""Collective entry points (callable inside ``jax.shard_map``).

Every function resolves a :class:`CollectivePlan` at trace time (tuned
decision + schedule) and executes it with the generalized executor — the
per-op analogue of how ``MPI_Bcast``/``MPI_Allreduce`` route through
MVAPICH2-GDR's tuned tables. ``*_tree`` variants communicate whole pytrees
through same-dtype buckets (``core.bucketing``), optionally staging each
packed bucket through the :func:`repro.kernels.chunked_copy` Pallas pipeline
(the paper's pipelined-copy primitive, Sec. IV-C).
"""
from __future__ import annotations

import math
from typing import Any, Sequence

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

import functools

from ..core import algorithms, bucketing
from ..core.tuner import Tuner
from .compress import CompressedWire, normalize_wire_format
from .executors import execute_collective, execute_compiled, execute_inkernel
from .plan import ONE_SHOT, CollectivePlan, plan_cached
from .schedules import alltoallv_matrix

__all__ = [
    "apply_plan",
    "apply_plan_resilient",
    "pbcast",
    "pbcast_tree",
    "preduce",
    "pallreduce",
    "pallgather",
    "pallgatherv",
    "palltoallv",
    "preduce_scatter",
    "pallreduce_tree",
    "hierarchical_allreduce_axes",
]

# unrolled-executor round budget before the auto policy switches to the
# compiled fori_loop replay (HLO size; core.algorithms.schedule_bcast
# applies the same policy). Zero-waste lowerings (the ring family,
# ring_allreduce included — per-round combine flags let both its phases
# share one fully-active class) switch much earlier: compiled then
# strictly dominates on both HLO size and wire bytes, so only the very
# smallest rings stay on the exact unrolled replay.
_MAX_UNROLLED_ROUNDS = 256
_MIN_COMPILED_ROUNDS_ZERO_WASTE = 8


def _use_compiled(plan: CollectivePlan, *, fused: bool, compiled: bool | None) -> bool:
    """Executor routing: an explicit ``compiled`` wins; then a tuned
    ``Decision.fused_path`` flag; then the round-count/zero-waste policy.
    ``fused=False`` forces the exact unrolled replay (the parity baseline).
    """
    if compiled is not None:
        return compiled
    if not fused:
        return False
    if plan.decision.fused_path is not None:
        return plan.decision.fused_path
    lowered = plan.lowered()
    if lowered is None or lowered.num_rounds == 0:
        return False
    if lowered.zero_waste:
        return lowered.num_rounds >= _MIN_COMPILED_ROUNDS_ZERO_WASTE
    return lowered.num_rounds > _MAX_UNROLLED_ROUNDS


_EXECUTORS = {
    "inkernel": execute_inkernel,
    "compiled": execute_compiled,
    "unrolled": execute_collective,
}


def _resolve_exec_path(
    plan: CollectivePlan,
    *,
    fused: bool = True,
    compiled: bool | None = None,
    inkernel: bool | None = None,
) -> str:
    """Three-tier executor routing: an explicit ``inkernel=`` flag wins;
    then a tuned ``Decision.exec_path``; then the compiled/unrolled policy
    (:func:`_use_compiled` — which itself honors an explicit ``compiled=``
    and ``Decision.fused_path``). Returns 'inkernel'|'compiled'|'unrolled'.

    The auto policy never picks inkernel on its own: the in-kernel executor
    enters only through the explicit flag or a tuned table entry.
    ``inkernel=False`` vetoes a tuned 'inkernel' without disturbing a tuned
    'compiled'/'unrolled'; an explicit ``compiled=`` bypasses the tuned tier
    entirely (it is a stronger, caller-level pin).

    Compressed wire formats veto the in-kernel path: the persistent kernel
    moves raw buffer blocks and has no quantize seam, so an explicit
    ``inkernel=True`` on a compressed plan raises, and a tuned 'inkernel'
    entry silently falls through to the compiled/unrolled policy (a stale
    table row must not disable compression).
    """
    compressed = plan.wire_format.compressed
    if inkernel:
        if compressed:
            raise ValueError(
                "the in-kernel executor does not support compressed wire "
                f"formats (plan wire_format={plan.wire_format.value!r}); "
                "use the compiled or unrolled executor"
            )
        return "inkernel"
    if compiled is None and fused:
        tuned = plan.decision.exec_path
        if tuned == "inkernel" and inkernel is None and not compressed:
            return "inkernel"
        if tuned in ("compiled", "unrolled"):
            return tuned
    return "compiled" if _use_compiled(plan, fused=fused, compiled=compiled) else "unrolled"


def _flat(x: jax.Array):
    flat = jnp.ravel(x)
    return flat, flat.size * flat.dtype.itemsize


# Reduce-family combiners the comm layer understands. The schedule executors
# (execute_collective / execute_compiled) implement SUM only; max/min route to
# the XLA one-shot collectives. Identity elements justify the pad tail a
# non-divisible buffer grows before chunking: a pad lane must never perturb
# the combined value (zeros are only sound for sum — the original bug).
_COMBINERS = ("sum", "max", "min")
_ONE_SHOT_REDUCERS = {"max": lax.pmax, "min": lax.pmin}


def _check_combiner(combiner: str, op: str) -> None:
    if combiner not in _COMBINERS:
        raise ValueError(f"unknown combiner {combiner!r} for {op}; have {_COMBINERS}")


def _chunked(flat: jax.Array, k: int, *, combiner: str | None = None):
    """Pad + reshape a flat buffer to (k, ceil(size/k)). ``k`` is honored
    even when it exceeds the element count (tiny buffers pad up), because
    the schedule's chunk count is load-bearing for the executor.

    ``combiner`` declares the reduce-family combine the schedule will apply
    to this buffer (``None`` for overwrite-only ops like bcast/allgather).
    Zero padding is the identity for SUM only; any other combiner must have
    been routed off the schedule path before the buffer grows a pad tail —
    this guard is what keeps a future combiner from silently corrupting the
    last chunk."""
    k = max(1, k)
    chunk_elems = max(1, -(-flat.size // k))
    pad = k * chunk_elems - flat.size
    if pad:
        if combiner is not None and combiner != "sum":
            raise ValueError(
                f"zero pad is only the identity for the 'sum' combiner, got "
                f"{combiner!r} — route non-sum reduces through the XLA "
                "one-shot collectives (pmax/pmin)"
            )
        flat = jnp.concatenate([flat, jnp.zeros((pad,), flat.dtype)])
    return flat.reshape(k, chunk_elems), pad


def _unchunked(buf: jax.Array, pad: int, shape, dtype):
    out = buf.reshape(-1)
    if pad:
        out = out[: out.size - pad]
    return out.reshape(shape).astype(dtype)


# ---------------------------------------------------------------------------
# ragged layout tables (host-side numpy, lifted to traced constants)
#
# The ragged schedules move rows of one global (total_rows, elems) buffer
# whose layout is fixed by the size vector: allgatherv concatenates the
# per-rank segments in rank order; alltoallv lays the n^2 blocks out
# row-major by (src, dst). The SPMD entry points scatter each rank's local
# shard into that global frame, replay the schedule, and gather the rank's
# result back out — all index arithmetic happens here on the host, so the
# traced program only sees constant gather tables and one `where` mask.
# ---------------------------------------------------------------------------


def _gatherv_tables(sizes, n: int):
    """allgatherv scatter layout: global row ``g`` is owned by rank
    ``src_of[g]`` and lives at row ``loc[g]`` of that rank's local shard."""
    sz = np.asarray(sizes, dtype=np.int64)
    off = np.concatenate([[0], np.cumsum(sz)])
    src_of = np.repeat(np.arange(n, dtype=np.int64), sz)
    loc = np.arange(int(off[-1]), dtype=np.int64) - off[src_of]
    return src_of, loc


def _a2av_tables(m: np.ndarray, n: int, *, in_padded: bool, out_padded: bool,
                 in_rows: int):
    """alltoallv scatter/gather layout for block matrix ``m`` (rows rank s
    sends to rank d). Returns host arrays:

    - ``src_of[g]``/``loc[g]``: global row ``g`` (row-major (s, d) blocks)
      is owned by rank ``src_of[g]`` at local row ``loc[g]``. For compact
      inputs ``loc`` indexes the destination-major concatenation; for padded
      inputs it indexes the flattened ``(n, in_rows)`` block layout.
    - ``gidx``/``gvalid``: per-rank output gather table. Row ``i`` of rank
      r's output is global row ``gidx[r, i]`` where ``gvalid[r, i]``, zero
      elsewhere. Compact outputs are the source-major concatenation (width
      ``max_r recv_r``); padded outputs are ``(n, bmax)`` blocks with each
      incoming block at a valid prefix (``bmax = m.max()``).
    """
    total = int(m.sum())
    boff = np.concatenate([[0], np.cumsum(m.reshape(-1))])
    bmax = int(m.max())
    recv = m.sum(axis=0)
    in_off = np.concatenate(
        [np.zeros((n, 1), np.int64), np.cumsum(m, axis=1)], axis=1
    )
    src_of = np.repeat(np.arange(n * n, dtype=np.int64) // n, m.reshape(-1))
    loc = np.zeros(total, dtype=np.int64)
    for s in range(n):
        for d in range(n):
            b = s * n + d
            j = np.arange(int(m[s, d]), dtype=np.int64)
            loc[boff[b]:boff[b + 1]] = (d * in_rows + j) if in_padded else (in_off[s, d] + j)
    out_rows = n * bmax if out_padded else max(int(recv.max()), 1)
    gidx = np.zeros((n, out_rows), dtype=np.int64)
    gvalid = np.zeros((n, out_rows), dtype=bool)
    for r in range(n):
        pos = 0
        for s in range(n):
            b = s * n + r
            h = int(m[s, r])
            lo = s * bmax if out_padded else pos
            gidx[r, lo:lo + h] = np.arange(boff[b], boff[b] + h)
            gvalid[r, lo:lo + h] = True
            pos += h
    return src_of, loc, gidx, gvalid, bmax


def _ragged_scatter(x2d: jax.Array, src_of, loc, axis_name) -> jax.Array:
    """Build the global (total_rows, elems) buffer: this rank's rows in
    place, zeros elsewhere (the executors' pre-condition for ragged ops)."""
    rank = lax.axis_index(axis_name)
    owned = jnp.asarray(src_of)[:, None] == rank
    return jnp.where(owned, x2d[jnp.asarray(loc)], jnp.zeros((), x2d.dtype))


def _run_allgatherv(plan: CollectivePlan, x: jax.Array, axis_name, run):
    sz = plan.sizes
    total = sum(sz)
    x2d = jnp.reshape(x, (x.shape[0], -1))
    src_of, loc = _gatherv_tables(sz, plan.n)
    out = run(plan.schedule, _ragged_scatter(x2d, src_of, loc, axis_name), axis_name)
    return out.reshape((total,) + x.shape[1:])


def _run_alltoallv(plan: CollectivePlan, x: jax.Array, axis_name, run, *,
                   in_padded: bool, out_padded: bool):
    n = plan.n
    m = np.asarray(plan.sizes, dtype=np.int64).reshape(n, n)
    elem = x.shape[2:] if in_padded else x.shape[1:]
    if in_padded and x.shape[0] != n:
        raise ValueError(f"in_padded alltoallv expects a (n={n}, bmax, ...) "
                         f"block layout, got leading dim {x.shape[0]}")
    in_rows = x.shape[1] if in_padded else x.shape[0]
    src_of, loc, gidx, gvalid, bmax = _a2av_tables(
        m, n, in_padded=in_padded, out_padded=out_padded, in_rows=int(in_rows))
    need = bmax if in_padded else int(m.sum(axis=1).max())
    if in_rows < need:
        raise ValueError(
            f"alltoallv input has {in_rows} rows per "
            f"{'block' if in_padded else 'rank'}, size matrix needs {need}")
    x2d = jnp.reshape(x, (-1, math.prod(elem) if elem else 1))
    out = run(plan.schedule, _ragged_scatter(x2d, src_of, loc, axis_name), axis_name)
    rank = lax.axis_index(axis_name)
    idx = jnp.asarray(gidx)[rank]
    valid = jnp.asarray(gvalid)[rank]
    picked = jnp.where(valid[:, None], out[idx], jnp.zeros((), out.dtype))
    if out_padded:
        return picked.reshape((n, bmax) + elem)
    return picked.reshape((picked.shape[0],) + elem)


# ---------------------------------------------------------------------------
# plan execution (consumers that pre-build CollectivePlans host-side —
# serving weight distribution, hillclimb — replay them here verbatim)
# ---------------------------------------------------------------------------


def apply_plan(
    plan: CollectivePlan,
    x: jax.Array,
    axis_name,
    *,
    fused: bool = True,
    compiled: bool | None = None,
    inkernel: bool | None = None,
) -> jax.Array:
    """Execute a pre-built :class:`CollectivePlan` on ``x`` inside
    ``shard_map`` — exactly the schedule the plan carries, no re-deciding.

    bcast/reduce/allreduce take and return the full buffer; allgather takes
    the per-rank shard and returns the ``(n, *shard)`` stack; reduce_scatter
    takes the full buffer and returns the rank's flat shard. The ragged ops
    use the compact conventions: allgatherv takes the valid-prefix row shard
    and returns the ``(sum(sizes), ...)`` concatenation; alltoallv takes the
    destination-major compact rows and returns the source-major compact rows
    (use :func:`palltoallv` for the padded block layouts).

    Executor routing (see :func:`_resolve_exec_path`): ``inkernel=True``
    forces the single-launch persistent-kernel replay (``execute_inkernel``),
    ``inkernel=False`` vetoes a tuned inkernel pin; otherwise
    ``compiled=True`` forces the fori_loop compiled replay
    (``execute_compiled`` — O(1) HLO in chunk count), ``compiled=False`` the
    exact unrolled replay, ``None`` the tuned (``Decision.exec_path`` /
    ``fused_path``) / round-count policy. Donation contract: consumers jit
    the surrounding
    program with the communicated buffers donated
    (``jax.jit(..., donate_argnums)``) so the compiled replay's loop carry
    and the fused kernel's aliasing update the buffer in place.
    """
    if plan.algo == "noop":
        if plan.op in ("allgatherv", "alltoallv"):
            # n == 1: the rank's valid prefix IS the result (alltoallv's
            # 1x1 block matrix degenerates to the same slice)
            return x[: plan.sizes[0]]
        return x if plan.op != "allgather" else x[None]
    if plan.algo == "xla_psum":
        if plan.op == "bcast":
            return algorithms.xla_psum_bcast(x, axis_name, root=plan.root)
        return lax.psum(x, axis_name)
    if plan.algo == "xla_allgather":
        if plan.op == "bcast":
            return algorithms.xla_allgather_bcast(x, axis_name, root=plan.root)
        return lax.all_gather(x, axis_name, axis=0)
    sched = plan.schedule
    path = _resolve_exec_path(plan, fused=fused, compiled=compiled, inkernel=inkernel)
    run = _EXECUTORS[path]
    out_dtype = x.dtype
    if plan.wire_format.compressed:
        # the inkernel path is vetoed above; both remaining executors take
        # the wire seam. The communicated buffer is cast to f32 so the wire
        # accounting (4 bytes/elem full precision vs 1 byte + amortized
        # scale compressed) matches what actually crosses each hop; the
        # result comes back in the caller's dtype.
        run = functools.partial(run, wire=CompressedWire(plan.wire_format))
        x = x.astype(jnp.float32)
    if plan.op == "allgatherv":
        return _run_allgatherv(plan, x, axis_name, run).astype(out_dtype)
    if plan.op == "alltoallv":
        return _run_alltoallv(plan, x, axis_name, run,
                              in_padded=False, out_padded=False).astype(out_dtype)
    if plan.op == "allgather":
        flat = jnp.ravel(x)
        buf = jnp.zeros((plan.n, flat.size), flat.dtype)
        buf = lax.dynamic_update_slice(buf, flat[None], (lax.axis_index(axis_name), 0))
        out = run(sched, buf, axis_name)
        return out.reshape((plan.n,) + x.shape).astype(out_dtype)
    if plan.op == "reduce_scatter":
        buf, _pad = _chunked(jnp.ravel(x), plan.n, combiner="sum")
        out = run(sched, buf, axis_name)
        return lax.dynamic_slice(
            out, (lax.axis_index(axis_name), 0), (1, buf.shape[1])
        )[0].astype(out_dtype)
    flat, _M = _flat(x)
    combiner = "sum" if plan.op in ("reduce", "allreduce") else None
    buf, pad = _chunked(flat, sched.num_chunks, combiner=combiner)
    out = run(sched, buf, axis_name)
    return _unchunked(out, pad, x.shape, out_dtype)


def _one_shot_fallback(plan: CollectivePlan, x: jax.Array, axis_name) -> jax.Array:
    """Terminal fallback stage: implement the plan's op with a single native
    XLA collective, bypassing the schedule executors entirely. Output
    shape/dtype contracts match :func:`apply_plan`. The ragged ops have no
    native one-shot (variable per-rank shapes) — they raise, and the chain
    reports them as exhausted."""
    op = plan.op
    if op == "bcast":
        return algorithms.xla_psum_bcast(x, axis_name, root=plan.root)
    if op in ("reduce", "allreduce"):
        return lax.psum(x, axis_name)
    if op == "allgather":
        return lax.all_gather(x, axis_name, axis=0)
    if op == "reduce_scatter":
        buf, _pad = _chunked(lax.psum(jnp.ravel(x), axis_name), plan.n, combiner="sum")
        return lax.dynamic_slice(buf, (lax.axis_index(axis_name), 0), (1, buf.shape[1]))[0]
    raise RuntimeError(f"no XLA one-shot collective implements ragged op {op!r}")


def apply_plan_resilient(
    plan: CollectivePlan,
    x: jax.Array,
    axis_name,
    *,
    policy=None,
    watchdog=None,
    fused: bool = True,
    on_event=None,
) -> jax.Array:
    """:func:`apply_plan` behind a typed fallback chain.

    Walks ``policy.chain`` (default inkernel -> compiled -> unrolled -> XLA
    one-shot) with per-stage retries and exponential backoff; the first stage that
    completes wins. Typed :class:`~.faults.FaultError`\\ s propagate
    immediately (they are diagnoses with recovery actions, not transient
    failures); any other exception burns a retry and then degrades the
    chain. A completed attempt slower than ``policy.timeout_s`` still
    returns its result but is flagged as a straggler — to the optional
    ``watchdog`` (which can land it in ``Tuner.record``) and the optional
    ``on_event`` callback. All stages failing raises
    :class:`~.faults.FallbackExhaustedError` naming every cause.

    Note: the timings observed here wrap trace + dispatch of the collective
    from the host's perspective, which is what a host-side watchdog can see;
    device-accurate straggler attribution comes from the benchmark harness
    feeding :meth:`Watchdog.observe` with measured times.
    """
    import time as _time

    from .faults import FallbackExhaustedError, FaultError
    from .resilience import FallbackEvent, FallbackPolicy

    policy = policy or FallbackPolicy()
    causes: list[str] = []
    for stage in policy.chain:
        delay = policy.backoff_s
        for attempt in range(policy.max_retries + 1):
            t0 = _time.perf_counter()
            try:
                if stage == "xla":
                    out = _one_shot_fallback(plan, x, axis_name)
                else:
                    # pin the executor to exactly this stage: inkernel=True
                    # for the head, inkernel=False + explicit compiled flag
                    # below it (a tuned exec_path must not re-route a
                    # degraded stage back onto the executor that just failed)
                    out = apply_plan(
                        plan, x, axis_name, fused=fused,
                        compiled=(None if stage == "inkernel"
                                  else stage == "compiled"),
                        inkernel=(stage == "inkernel"),
                    )
            except FaultError:
                raise
            except Exception as e:  # noqa: BLE001 — the chain is the handler
                dt = _time.perf_counter() - t0
                causes.append(f"{stage}[{attempt}]: {type(e).__name__}: {e}")
                if on_event is not None:
                    on_event(FallbackEvent(stage, attempt, "error", dt, repr(e)))
                if attempt < policy.max_retries:
                    _time.sleep(delay)
                    delay *= policy.backoff_mult
                continue
            dt = _time.perf_counter() - t0
            straggled = policy.timeout_s is not None and dt > policy.timeout_s
            if on_event is not None:
                on_event(FallbackEvent(stage, attempt, "straggler" if straggled else "ok", dt))
            if watchdog is not None:
                watchdog.observe(plan, dt)
            return out
    raise FallbackExhaustedError(
        f"every fallback stage failed for {plan.op}/{plan.algo} "
        f"(M={plan.M}, n={plan.n}): " + "; ".join(causes)
    )


# ---------------------------------------------------------------------------
# bcast / reduce (the paper's ops, now plan-driven)
# ---------------------------------------------------------------------------


def pbcast(
    x: jax.Array,
    axis_name,
    *,
    root: int = 0,
    algo: str = "auto",
    num_chunks: int | None = None,
    tuner: Tuner | None = None,
    inter_pod: bool = False,
    fused: bool = True,
    compiled: bool | None = None,
    inkernel: bool | None = None,
    wire_format: str | None = None,
) -> jax.Array:
    """Broadcast ``x`` from ``root`` over the named mesh axis (must be called
    inside ``shard_map``; every rank passes a same-shape buffer and receives
    the root's).

    ``wire_format`` ('bf16'|'fp8'|'int8', default full-precision passthrough)
    compresses every hop at the ppermute seam; compressed payloads travel in
    the f32 wire domain (``M`` counts 4 bytes/element before compression) and
    the result comes back in ``x``'s dtype.
    """
    x = jnp.asarray(x)
    n = lax.axis_size(axis_name)
    if n == 1:
        return x
    fmt = normalize_wire_format(wire_format)
    if algo in ("xla_psum", "xla_allgather"):
        if fmt.compressed:
            raise ValueError(
                f"wire_format={fmt.value!r} requires a schedule-backed algo; "
                f"the one-shot {algo!r} has no compression seam"
            )
        if algo == "xla_psum":
            return algorithms.xla_psum_bcast(x, axis_name, root=root)
        return algorithms.xla_allgather_bcast(x, axis_name, root=root)
    _flat_x, M = _flat(x.astype(jnp.float32) if fmt.compressed else x)
    plan = plan_cached(
        "bcast", M, n, root=root, algo=algo, num_chunks=num_chunks,
        tuner=tuner, inter_pod=inter_pod, wire_format=wire_format,
    )
    return apply_plan(plan, x, axis_name, fused=fused, compiled=compiled,
                      inkernel=inkernel)


def preduce(
    x: jax.Array,
    axis_name,
    *,
    root: int = 0,
    algo: str = "auto",
    num_chunks: int | None = None,
    tuner: Tuner | None = None,
    inter_pod: bool = False,
    combiner: str = "sum",
    compiled: bool | None = None,
    inkernel: bool | None = None,
    wire_format: str | None = None,
) -> jax.Array:
    """Reduce-to-root (``combiner``: sum by default). Non-root ranks return
    garbage partial sums by design (MPI_Reduce semantics) — only the root's
    output is meaningful. Non-sum combiners route through the XLA one-shot
    collectives (the schedule executors combine by sum, and zero pad tails
    are only the identity for sum)."""
    _check_combiner(combiner, "preduce")
    x = jnp.asarray(x)  # n == 1 must return the communicating path's
    # dtype/shape contract (a committed jnp array), not the caller's object
    n = lax.axis_size(axis_name)
    if n == 1:
        return x
    fmt = normalize_wire_format(wire_format)
    if combiner != "sum":
        if fmt.compressed:
            raise ValueError(
                f"wire_format={fmt.value!r} supports the 'sum' combiner only "
                f"(non-sum combiners route through the XLA one-shots)"
            )
        if algo != "auto":
            raise ValueError(f"combiner {combiner!r} supports algo='auto' only")
        return _ONE_SHOT_REDUCERS[combiner](x, axis_name)
    _flat_x, M = _flat(x.astype(jnp.float32) if fmt.compressed else x)
    plan = plan_cached(
        "reduce", M, n, root=root, algo=algo, num_chunks=num_chunks,
        tuner=tuner, inter_pod=inter_pod, wire_format=wire_format,
    )
    return apply_plan(plan, x, axis_name, compiled=compiled, inkernel=inkernel)


# ---------------------------------------------------------------------------
# allreduce / allgather / reduce_scatter (beyond-paper ops, Sec. VII)
# ---------------------------------------------------------------------------


def pallreduce(
    x: jax.Array,
    axis_name,
    *,
    algo: str = "auto",
    num_chunks: int | None = None,
    tuner: Tuner | None = None,
    inter_pod: bool = False,
    fused: bool = True,
    combiner: str = "sum",
    compiled: bool | None = None,
    inkernel: bool | None = None,
    wire_format: str | None = None,
) -> jax.Array:
    """All-reduce (``combiner``: sum by default) over the named axis through
    the tuned plan layer.

    ``algo``: 'auto', 'reduce_then_bcast', 'fused_rsb', 'ring_allreduce', or
    the one-shot baseline 'xla_psum'. Non-sum combiners (max/min) route to
    the XLA one-shots — the schedule executors combine by sum only.
    ``wire_format`` ('bf16'|'fp8'|'int8') compresses every hop at the
    ppermute seam (combine arithmetic stays full precision); compressed
    payloads travel in the f32 wire domain.
    """
    _check_combiner(combiner, "pallreduce")
    x = jnp.asarray(x)
    n = lax.axis_size(axis_name)
    if n == 1:
        return x
    fmt = normalize_wire_format(wire_format)
    if combiner != "sum":
        if fmt.compressed:
            raise ValueError(
                f"wire_format={fmt.value!r} supports the 'sum' combiner only "
                f"(non-sum combiners route through the XLA one-shots)"
            )
        if algo not in ("auto", "xla_psum"):
            raise ValueError(
                f"combiner {combiner!r} supports algo='auto' or 'xla_psum' only"
            )
        return _ONE_SHOT_REDUCERS[combiner](x, axis_name)
    if algo == "xla_psum":
        if fmt.compressed:
            raise ValueError(
                f"wire_format={fmt.value!r} requires a schedule-backed algo; "
                "the one-shot 'xla_psum' has no compression seam"
            )
        return lax.psum(x, axis_name)
    _flat_x, M = _flat(x.astype(jnp.float32) if fmt.compressed else x)
    plan = plan_cached(
        "allreduce", M, n, algo=algo, num_chunks=num_chunks,
        tuner=tuner, inter_pod=inter_pod, wire_format=wire_format,
    )
    return apply_plan(plan, x, axis_name, fused=fused, compiled=compiled,
                      inkernel=inkernel)


def pallgather(
    x: jax.Array,
    axis_name,
    *,
    algo: str = "auto",
    tuner: Tuner | None = None,
    inter_pod: bool = False,
    compiled: bool | None = None,
    inkernel: bool | None = None,
    wire_format: str | None = None,
) -> jax.Array:
    """All-gather the per-rank shard ``x`` into a stacked ``(n, *x.shape)``
    array (the ``lax.all_gather(axis=0)`` convention).

    ``algo``: 'auto', 'ring_allgather', 'doubling_allgather' (power-of-two
    n), or the one-shot baseline 'xla_allgather'.
    """
    x = jnp.asarray(x)
    n = lax.axis_size(axis_name)
    if n == 1:
        return x[None]
    fmt = normalize_wire_format(wire_format)
    if algo == "xla_allgather":
        if fmt.compressed:
            raise ValueError(
                f"wire_format={fmt.value!r} requires a schedule-backed algo; "
                "the one-shot 'xla_allgather' has no compression seam"
            )
        return lax.all_gather(x, axis_name, axis=0)
    # full gathered payload; compressed wires ship in the f32 wire domain
    M = n * x.size * (4 if fmt.compressed else x.dtype.itemsize)
    plan = plan_cached(
        "allgather", M, n, algo=algo, tuner=tuner, inter_pod=inter_pod,
        wire_format=wire_format,
    )
    return apply_plan(plan, x, axis_name, compiled=compiled, inkernel=inkernel)


def preduce_scatter(
    x: jax.Array,
    axis_name,
    *,
    algo: str = "auto",
    tuner: Tuner | None = None,
    inter_pod: bool = False,
    combiner: str = "sum",
    compiled: bool | None = None,
    inkernel: bool | None = None,
    wire_format: str | None = None,
) -> jax.Array:
    """Reduce-scatter (``combiner``: sum by default): every rank contributes
    the full flat buffer and receives its rank-indexed shard of the combined
    result — a flat array of ``ceil(x.size / n)`` elements (zero-padded tail
    on the last shard). Non-sum combiners combine FIRST through the XLA
    one-shot (pmax/pmin), then shard — the pad tail is appended after the
    combine, so the identity-element question never arises."""
    _check_combiner(combiner, "preduce_scatter")
    n = lax.axis_size(axis_name)
    flat = jnp.ravel(x)
    if n == 1:
        return flat
    fmt = normalize_wire_format(wire_format)
    if combiner != "sum":
        if fmt.compressed:
            raise ValueError(
                f"wire_format={fmt.value!r} supports the 'sum' combiner only "
                f"(non-sum combiners route through the XLA one-shots)"
            )
        if algo != "auto":
            raise ValueError(f"combiner {combiner!r} supports algo='auto' only")
        full = _ONE_SHOT_REDUCERS[combiner](flat, axis_name)
        buf, _pad = _chunked(full, n)
        return lax.dynamic_slice(buf, (lax.axis_index(axis_name), 0), (1, buf.shape[1]))[0]
    M = flat.size * (4 if fmt.compressed else flat.dtype.itemsize)
    plan = plan_cached(
        "reduce_scatter", M, n, algo=algo, tuner=tuner, inter_pod=inter_pod,
        wire_format=wire_format,
    )
    if plan.algo == "noop":
        return flat
    return apply_plan(plan, x, axis_name, compiled=compiled, inkernel=inkernel)


# ---------------------------------------------------------------------------
# ragged collectives (allgatherv / alltoallv — MPI_Allgatherv/MPI_Alltoallv
# analogues on the schedule IR; the MoE expert-dispatch transport)
# ---------------------------------------------------------------------------


def pallgatherv(
    x: jax.Array,
    axis_name,
    *,
    sizes: Sequence[int],
    algo: str = "auto",
    tuner: Tuner | None = None,
    inter_pod: bool = False,
    fused: bool = True,
    compiled: bool | None = None,
    inkernel: bool | None = None,
) -> jax.Array:
    """Ragged all-gather: rank ``r`` contributes the first ``sizes[r]`` rows
    of ``x`` (rows beyond the valid prefix are ignored) and every rank
    receives the ``(sum(sizes), *x.shape[1:])`` concatenation in rank order.

    ``x`` must have the same static shape on every rank with leading dim
    >= ``max(sizes)`` (SPMD). Zero-sized ranks are fine — they contribute
    nothing but still receive the full result. ``algo``: 'auto',
    'ring_allgatherv', or 'doubling_allgatherv' (power-of-two n); 'auto'
    routes through the skew-aware tuner (``Tuner.select(..., sizes=)``).
    """
    x = jnp.asarray(x)
    n = lax.axis_size(axis_name)
    sz = tuple(int(s) for s in sizes)
    if len(sz) != n:
        raise ValueError(f"allgatherv sizes has {len(sz)} entries for axis size {n}")
    if any(s < 0 for s in sz) or sum(sz) == 0:
        raise ValueError(f"allgatherv sizes must be non-negative and non-empty: {sz}")
    if x.ndim < 1 or x.shape[0] < max(sz):
        raise ValueError(
            f"allgatherv input has {x.shape[0] if x.ndim else 0} rows, "
            f"size vector needs max(sizes)={max(sz)}")
    total = sum(sz)
    if n == 1:
        return x[: sz[0]]
    elems = math.prod(x.shape[1:]) if x.ndim > 1 else 1
    if elems == 0:
        return jnp.zeros((total,) + x.shape[1:], x.dtype)
    M = total * elems * x.dtype.itemsize
    plan = plan_cached(
        "allgatherv", M, n, algo=algo, tuner=tuner, inter_pod=inter_pod,
        sizes=sz,
    )
    return apply_plan(plan, x, axis_name, fused=fused, compiled=compiled,
                      inkernel=inkernel)


def palltoallv(
    x: jax.Array,
    axis_name,
    *,
    sizes,
    algo: str = "auto",
    tuner: Tuner | None = None,
    inter_pod: bool = False,
    in_padded: bool = False,
    out_padded: bool = False,
    fused: bool = True,
    compiled: bool | None = None,
    inkernel: bool | None = None,
) -> jax.Array:
    """Ragged all-to-all: ``sizes`` gives the block matrix ``m[s][d]`` (rows
    rank ``s`` sends to rank ``d``) as an n x n nested sequence, a flat
    row-major n^2 vector, or a length-n per-destination vector (every source
    sends the same counts). Rank ``r`` sends block ``m[r][d]`` to each
    ``d`` and receives block ``m[s][r]`` from each ``s``.

    Layouts (``elem = x.shape[1:]`` compact, ``x.shape[2:]`` padded):

    - compact in (default): ``x`` is the destination-major concatenation —
      the first ``sum_d m[r][d]`` rows are blocks for d=0..n-1 back-to-back;
      leading dim >= ``max_r sum_d m[r][d]`` (static, shared by all ranks).
    - padded in (``in_padded=True``): ``x`` is ``(n, bmax_in, *elem)`` with
      the block for destination ``d`` at ``x[d, :m[r][d]]``.
    - compact out (default): source-major concatenation, shape
      ``(max_r sum_s m[s][r], *elem)``, zero beyond the rank's valid prefix.
    - padded out (``out_padded=True``): ``(n, max(m), *elem)`` with the
      block from source ``s`` at ``out[s, :m[s][r]]``, zeros elsewhere.

    The padded layouts keep per-rank shapes static when block heights vary
    per rank — the MoE expert-dispatch contract. ``algo``: 'auto',
    'pairwise_alltoallv', or 'ring_alltoallv' (store-and-forward).
    """
    x = jnp.asarray(x)
    n = lax.axis_size(axis_name)
    m = alltoallv_matrix(sizes, n)
    flat = tuple(v for row in m for v in row)
    total = sum(flat)
    if total == 0:
        raise ValueError("alltoallv size matrix is all zeros")
    elem = x.shape[2:] if in_padded else x.shape[1:]
    elems = math.prod(elem) if elem else 1
    if n == 1:
        c = m[0][0]
        if in_padded:
            return x[:, :c] if out_padded else x[0, :c]
        return x[:c][None] if out_padded else x[:c]
    if elems == 0:
        bmax = max(flat)
        rmax = max(sum(m[s][r] for s in range(n)) for r in range(n))
        shape = ((n, bmax) + elem) if out_padded else ((rmax,) + elem)
        return jnp.zeros(shape, x.dtype)
    M = total * elems * x.dtype.itemsize
    plan = plan_cached(
        "alltoallv", M, n, algo=algo, tuner=tuner, inter_pod=inter_pod,
        sizes=flat,
    )
    run = _EXECUTORS[
        _resolve_exec_path(plan, fused=fused, compiled=compiled, inkernel=inkernel)
    ]
    return _run_alltoallv(plan, x, axis_name, run,
                          in_padded=in_padded, out_padded=out_padded)


# ---------------------------------------------------------------------------
# pytree variants (bucketed; the application regime of paper Sec. V-D)
# ---------------------------------------------------------------------------


def _tree_collective(op_fn, tree, axis_name, *, bucket_bytes, stage, stage_chunk, **kw):
    spec = bucketing.plan_buckets(tree, bucket_bytes)
    buckets = bucketing.pack_buckets(tree, spec)
    out = []
    for b in buckets:
        if not b.size:
            out.append(b)
            continue
        if stage:
            from ..kernels.chunked_copy import chunked_copy

            b = chunked_copy(b, chunk_elems=stage_chunk)
        out.append(op_fn(b, axis_name, **kw))
    return bucketing.unpack_buckets(out, spec)


def pbcast_tree(
    tree: Any,
    axis_name,
    *,
    root: int = 0,
    algo: str = "auto",
    tuner: Tuner | None = None,
    bucket_bytes: int = 4 << 20,
    inter_pod: bool = False,
    stage: bool = False,
    stage_chunk: int = 64 * 1024,
) -> Any:
    """Broadcast a pytree via same-dtype buckets, each tuned independently.

    The bucket mix reproduces the application regime of the paper (Sec.
    V-D): a few large buckets (pipelined-chain territory) plus a tail of
    small ones (k-nomial territory). ``stage=True`` routes each packed
    bucket through the ``chunked_copy`` Pallas staging pipeline first.
    """
    return _tree_collective(
        pbcast, tree, axis_name, bucket_bytes=bucket_bytes, stage=stage,
        stage_chunk=stage_chunk, root=root, algo=algo, tuner=tuner,
        inter_pod=inter_pod,
    )


def pallreduce_tree(
    tree: Any,
    axes: Sequence,
    *,
    algo: str = "auto",
    tuner: Tuner | None = None,
    bucket_bytes: int = 4 << 20,
    inter_pod_axes: Sequence = (),
    stage: bool = False,
    stage_chunk: int = 64 * 1024,
    compiled: bool | None = None,
    wire_format: str | None = None,
) -> Any:
    """Hierarchical bucketed all-reduce over one or more mesh axes.

    Axes run in the given order (use :func:`hierarchical_allreduce_axes` for
    the intra-pod-first convention); axes named in ``inter_pod_axes`` are
    priced with the tuner's inter-pod constants, so the pod level can pick a
    different algorithm than the fast intra-pod level. The tree is packed
    into buckets ONCE; all hierarchy levels run over the packed buffers.
    ``wire_format`` applies to every bucket at every level (see
    :func:`pallreduce`).
    """
    spec = bucketing.plan_buckets(tree, bucket_bytes)
    with jax.named_scope("pack"):
        buckets = bucketing.pack_buckets(tree, spec)
    inter = tuple(inter_pod_axes)
    out = []
    for i, b in enumerate(buckets):
        if not b.size:
            out.append(b)
            continue
        with jax.named_scope(f"bucket{i}"):  # index in packing order
            if stage:
                from ..kernels.chunked_copy import chunked_copy

                b = chunked_copy(b, chunk_elems=stage_chunk)
            for ax in axes:
                b = pallreduce(b, ax, algo=algo, tuner=tuner, inter_pod=(ax in inter),
                               compiled=compiled, wire_format=wire_format)
        out.append(b)
    with jax.named_scope("unpack"):
        return bucketing.unpack_buckets(out, spec)


def hierarchical_allreduce_axes(mesh) -> tuple:
    """Axis order for hierarchical allreduce: intra-pod data axes first,
    then the inter-pod level (the reverse of ``topology.bcast_axes`` —
    reduce locally before touching the slow fabric)."""
    from ..dist import topology

    return tuple(reversed(topology.bcast_axes(mesh)))
