"""Persistent in-kernel collective executor: one Pallas launch per schedule.

The compiled executor (``comm.executors.execute_compiled``) already collapsed
HLO size to O(lane classes), but each replay round still pays a
``lax.ppermute`` -> combine-kernel launch boundary and two HBM round-trips.
This module deletes that overhead: ONE Pallas kernel launch replays the whole
lowered schedule — the kernel itself moves each round's block (async remote
copy on TPU, a shared-buffer write in the interpret-mode emulation) and merges
it into the destination window in the same VMEM pass, using exactly the
where-chain of ``repro.kernels.combine_update`` so the result stays
bit-identical to the unrolled oracle.

The static metadata the kernel needs is the PR 5 lowering, stacked into the
kernel-resident layout of :class:`repro.core.schedules.KernelTables`:
``send_start``/``recv_start``/``lo``/``hi`` as dense int32
``(num_classes, num_rounds, n)`` operands (scalar-prefetch on TPU) and the
per-class permutations/block heights as kernel *structure* (static python
loops). ``grid=(num_rounds,)`` walks rounds; the buffer block is revisited
every step (constant index map + ``input_output_aliases``), which is what
keeps the whole replay inside one launch.

Two paths, one control flow:

* **Interpret / CPU CI** — the mesh is emulated through a shared
  ``(n, num_chunks, chunk)`` buffer (``lax.all_gather`` of the per-rank
  buffers); the kernel replays every rank's sends and merges directly on the
  shared buffer, then the caller slices its own row. This is the executable
  contract: parity suites compare it bit-for-bit against
  ``simulate_lowered`` and the unrolled executor.
* **TPU** — the same round/class loop issues
  ``pltpu.make_async_remote_copy`` RDMA per active pair, straight from the
  sender's HBM buffer into the receiver's VMEM landing slot, with a
  per-class ready signal so a sender never overwrites a landing slot its
  partner has not consumed. CI compiles it for a described v5e
  (``tests/test_tpu_compile.py``); the interpret path above pins the
  semantics it must reproduce.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax
from jax.experimental import pallas as pl

from ..core.schedules import KernelTables, LoweredSchedule, pack_tables
from .interpret import on_tpu, resolve_interpret

__all__ = ["inkernel_replay", "inkernel_replay_shared"]


@functools.lru_cache(maxsize=256)
def _packed_planes(tables: KernelTables) -> np.ndarray:
    """Fold the round tables into the gather/merge planes the emulation
    kernel consumes: ONE int32 operand of shape
    ``(num_rounds, num_classes, 2, n, num_chunks)`` where

    * plane 0 (``idx``) — for receiver ``dst`` and row ``r`` of its buffer,
      the FLAT index (into the shared buffer viewed as ``(n*K, cols)``) of
      the source row that lands there this round:
      ``src*K + send_start[src] + clip(r - recv_start[dst], 0, block-1)``
      (identity ``dst*K + r`` for ranks that never receive in the class);
    * plane 1 (``mode``) — the KEEP/OVERWRITE/ACCUMULATE selector of
      ``combine_update._merge_kernel``: ``(1 + combine)`` inside the row
      window ``[recv_start+lo, recv_start+hi)``, else 0.

    ALL index arithmetic happens here, on the host, at pack time — the
    tables are static schedule metadata, so the kernel body needs exactly
    one gather and one where-chain per lane class. That is what keeps the
    interpret-mode program both tiny and flat: every dynamic-slice the
    interpreter lowers costs a fixed clamp chain of HLO, so the fewer
    in-kernel index computations, the smaller the emulated program."""
    C, T, n = tables.send_start.shape
    K = tables.num_chunks
    src_of = np.tile(np.arange(n, dtype=np.int32), (C, 1))
    active = np.zeros((C, n), np.int32)
    for c, perm in enumerate(tables.perms):
        for src, dst in perm:
            src_of[c, dst] = src
            active[c, dst] = 1
    rows = np.arange(K, dtype=np.int32)[None, :]                 # (1, K)
    planes = np.zeros((T, C, 2, n, K), np.int32)
    for c in range(C):
        block = max(tables.blocks[c], 1)
        for s in range(T):
            send = tables.send_start[c, s]
            rel = rows - tables.recv_start[c, s][:, None]        # (n, K)
            idx = (src_of[c] * K + send[src_of[c]])[:, None] + np.clip(
                rel, 0, block - 1
            )
            ident = np.arange(n, dtype=np.int32)[:, None] * K + rows
            act = active[c][:, None]
            planes[s, c, 0] = np.where(act == 1, idx, ident)
            inwin = (rel >= tables.lo[c, s][:, None]) & (
                rel < tables.hi[c, s][:, None]
            )
            planes[s, c, 1] = inwin * act * (1 + tables.combine[c, s])
    return np.ascontiguousarray(planes)


def _shared_kernel(tables: KernelTables, cols: int,
                   tab_ref, shared_ref, out_ref):
    """Replay ALL rounds over the shared (n, K, cols) buffer in one kernel
    body: a ``lax.fori_loop`` over rounds whose carry is the buffer value,
    so the whole schedule is one launch and the program size is independent
    of the round count.

    Classes apply sequentially inside a round (matching
    ``simulate_lowered``); within a class every source row is read BEFORE
    any destination write (the class snapshot is the carry value) — a rank
    can be src of one pair and dst of another in the same class. Per class
    the body is one precomputed gather (``_packed_planes`` plane 0) pulling
    every receiver's incoming rows out of the snapshot, then the
    KEEP/OVERWRITE/ACCUMULATE where-chain of ``combine_update._merge_kernel``
    under the precomputed mode plane — kept rows round-trip bit-identically.
    """
    n, K = tables.n, tables.num_chunks
    tab = tab_ref[...]

    def round_body(s, out):
        planes = tab[s]                              # (C, 2, n, K)
        for c, (perm, block) in enumerate(zip(tables.perms, tables.blocks)):
            if block == 0 or not perm:
                continue
            flat = out.reshape(n * K, cols)
            rec = flat[planes[c, 0]]                 # (n, K, cols) gather
            m = planes[c, 1][:, :, None]
            out = jnp.where(m == 2, out + rec,
                            jnp.where(m == 1, rec, out))
        return out

    out_ref[...] = lax.fori_loop(0, tables.num_rounds, round_body,
                                 shared_ref[...])


def inkernel_replay_shared(lowered: LoweredSchedule, shared: jax.Array, *,
                           interpret: bool | None = None) -> jax.Array:
    """Replay every round of ``lowered`` on the shared ``(n, K, cols)``
    buffer in ONE ``pallas_call`` (row r = rank r's local buffer)."""
    interpret = resolve_interpret(interpret)
    tables = pack_tables(lowered)
    T = tables.num_rounds
    if T == 0 or tables.num_classes == 0:
        return shared
    n, K, cols = shared.shape
    # gridless whole-array launch: the round loop lives INSIDE the kernel
    # (carry-valued fori_loop), so there is no per-round grid machinery at
    # all — the packed table plane rides along as the one extra operand
    return pl.pallas_call(
        functools.partial(_shared_kernel, tables, cols),
        out_shape=jax.ShapeDtypeStruct(shared.shape, shared.dtype),
        input_output_aliases={1: 0},
        interpret=interpret,
    )(jnp.asarray(_packed_planes(tables)), shared)


# ---------------------------------------------------------------------------
# TPU RDMA path — kernel-initiated transfers (exercised on hardware only)
# ---------------------------------------------------------------------------


def _neighbor_tables(tables: KernelTables):
    """Per-class partner maps: ``dst_of[c, r]`` is where rank r sends this
    class (r itself when inactive), ``src_of[c, r]`` who sends to it."""
    C, n = tables.num_classes, tables.n
    dst_of = np.tile(np.arange(n, dtype=np.int32), (C, 1))
    src_of = dst_of.copy()
    for c, perm in enumerate(tables.perms):
        for src, dst in perm:
            dst_of[c, src] = dst
            src_of[c, dst] = src
    return dst_of, src_of


def _rdma_kernel(tables: KernelTables, axis_name: str,
                 send_t, recv_t, lo_t, hi_t, comb_t, dst_of_t, src_of_t,
                 talks_t, _buf_ref, out_ref, land, stage, send_sem, recv_sem,
                 *ready):
    """One grid step per round. The buffer stays in HBM (``out_ref``, the
    aliased input) as ``(K, sub, 128)``, so that a chunk row is a slice of
    the untiled leading axis; VMEM holds one landing slot and one merge
    stage.

    Flow control is per lane class: a receiver signals its sender's
    ``ready[c]`` semaphore once its landing slot is free, and the sender
    waits for that signal before its remote copy. Each class has one fixed
    partner per rank, so the signals of successive rounds cannot be
    confused with another partner's. The barrier semaphore is used once, at
    the first step, so that no rank touches a partner that has not entered
    the kernel yet."""
    from jax.experimental.pallas import tpu as pltpu

    logical = pltpu.DeviceIdType.LOGICAL
    s = pl.program_id(0)
    me = lax.axis_index(axis_name)

    @pl.when(s == 0)
    def _handshake():
        barrier = pltpu.get_barrier_semaphore()
        count = jnp.int32(0)
        for q in range(tables.n):
            @pl.when(talks_t[me, q] == 1)
            def _signal(q=q):
                pltpu.semaphore_signal(barrier, device_id=q,
                                       device_id_type=logical)

            count = count + talks_t[me, q]
        pltpu.semaphore_wait(barrier, count)

    for c, (perm, block) in enumerate(zip(tables.perms, tables.blocks)):
        if block == 0 or not perm:
            continue
        dst = dst_of_t[c, me]
        src = src_of_t[c, me]
        slot = land.at[pl.ds(0, block)]

        @pl.when(src != me)
        def _slot_free():
            pltpu.semaphore_signal(ready[c], device_id=src,
                                   device_id_type=logical)

        @pl.when(dst != me)
        def _send():
            pltpu.semaphore_wait(ready[c], 1)
            rdma = pltpu.make_async_remote_copy(
                src_ref=out_ref.at[pl.ds(send_t[c, s, me], block)],
                dst_ref=slot, send_sem=send_sem, recv_sem=recv_sem,
                device_id=dst, device_id_type=logical,
            )
            rdma.start()
            rdma.wait_send()

        @pl.when(src != me)
        def _merge():
            window = out_ref.at[pl.ds(recv_t[c, s, me], block)]
            # a DMA semaphore is waited through a descriptor of the copy
            # that lands here: same landing ref and semaphores
            pltpu.make_async_remote_copy(
                src_ref=window, dst_ref=slot, send_sem=send_sem,
                recv_sem=recv_sem, device_id=src, device_id_type=logical,
            ).wait_recv()
            cur_ref = stage.at[pl.ds(0, block)]
            pltpu.sync_copy(window, cur_ref)
            rows = lax.broadcasted_iota(jnp.int32, (block, 1, 1), 0)
            mode = ((rows >= lo_t[c, s, me]) & (rows < hi_t[c, s, me])
                    ).astype(jnp.int32) * (1 + comb_t[c, s])
            sub = cur_ref.shape[1]
            tile = _merge_tile(sub)

            # merge one (block, tile, 128) slab at a time, so that the
            # values in flight stay small next to the two slots
            def merge_slab(t, carry):
                i = pl.multiple_of(t * tile, tile)
                cur = cur_ref[:, pl.ds(i, tile), :]
                rec = slot[:, pl.ds(i, tile), :]
                cur_ref[:, pl.ds(i, tile), :] = jnp.where(
                    mode == 2, cur + rec, jnp.where(mode == 1, rec, cur)
                )
                return carry

            lax.fori_loop(0, sub // tile, merge_slab, 0)
            pltpu.sync_copy(cur_ref, window)


_LANES = 128
# the buffer's middle axis is padded to a multiple of this many sublanes
_SUBLANES = 8


def _vmem_bytes(shape, dtype) -> int:
    """VMEM footprint of a buffer on the (sublane, 128) tiling."""
    itemsize = jnp.dtype(dtype).itemsize
    *lead, sub, lanes = shape
    tile = 8 * (4 // itemsize)
    return (int(np.prod(lead)) * -(-sub // tile) * tile
            * -(-lanes // _LANES) * _LANES * itemsize)


def _merge_tile(sub: int) -> int:
    """The largest power-of-two slab height, at most 512, dividing ``sub``."""
    tile = 512
    while sub % tile:
        tile //= 2
    return tile


def _rdma_replay(tables: KernelTables, buf: jax.Array,
                 axis_name: str) -> jax.Array:
    from jax.experimental.pallas import tpu as pltpu

    K, cols = buf.shape
    wide = -(-cols // (_SUBLANES * _LANES)) * _SUBLANES * _LANES
    x = buf if wide == cols else jnp.pad(buf, ((0, 0), (0, wide - cols)))
    x = x.reshape(K, wide // _LANES, _LANES)
    dst_of, src_of = _neighbor_tables(tables)
    n = tables.n
    talks = np.zeros((n, n), np.int32)
    talks[np.arange(n)[None, :], dst_of] = 1
    talks[np.arange(n)[None, :], src_of] = 1
    np.fill_diagonal(talks, 0)
    slot = (max(tables.blocks),) + x.shape[1:]
    hbm = pl.BlockSpec(memory_space=pl.ANY)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=8,
        grid=(tables.num_rounds,),
        in_specs=[hbm],
        out_specs=hbm,
        scratch_shapes=[
            pltpu.VMEM(slot, x.dtype),   # landing slot
            pltpu.VMEM(slot, x.dtype),   # merge stage
            pltpu.SemaphoreType.DMA,
            pltpu.SemaphoreType.DMA,
        ] + [pltpu.SemaphoreType.REGULAR] * tables.num_classes,
    )
    out = pl.pallas_call(
        functools.partial(_rdma_kernel, tables, axis_name),
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct(x.shape, x.dtype),
        input_output_aliases={8: 0},
        compiler_params=pltpu.CompilerParams(
            has_side_effects=True, collective_id=0,
            vmem_limit_bytes=max(16 << 20,
                                 2 * _vmem_bytes(slot, x.dtype) + (4 << 20)),
        ),
    )(
        jnp.asarray(tables.send_start), jnp.asarray(tables.recv_start),
        jnp.asarray(tables.lo), jnp.asarray(tables.hi),
        jnp.asarray(tables.combine), jnp.asarray(dst_of), jnp.asarray(src_of),
        jnp.asarray(talks), x,
    )
    return out.reshape(K, wide)[:, :cols]


def inkernel_replay(lowered: LoweredSchedule, buf: jax.Array, axis_name: str,
                    *, interpret: bool | None = None) -> jax.Array:
    """Replay a lowered schedule on this rank's ``(K, cols)`` buffer with a
    single kernel launch. Must be called inside ``shard_map`` over
    ``axis_name``, like the other executors."""
    interpret = resolve_interpret(interpret)
    tables = pack_tables(lowered)
    if tables.num_rounds == 0 or tables.num_classes == 0:
        return buf
    if not interpret:
        if not on_tpu():
            raise ValueError(
                "inkernel_replay(interpret=False) needs a TPU backend; the "
                f"default backend is {jax.default_backend()!r}"
            )
        return _rdma_replay(tables, buf, axis_name)
    shared = lax.all_gather(buf, axis_name, axis=0)
    out = inkernel_replay_shared(lowered, shared, interpret=interpret)
    return lax.dynamic_index_in_dim(
        out, lax.axis_index(axis_name), 0, keepdims=False
    )
