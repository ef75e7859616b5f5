"""Mamba selective scan (Pallas, TPU target), forward and backward.

The recurrence, per batch row b, channel d and state n:

    h_t = exp(dt_t * A) * h_{t-1} + (dt_t * x_t) * B_t,   y_t = sum_n h_t * C_t

The state sequence never leaves VMEM. Each kernel walks the sequence in time
order through blocks of steps; the state of a di tile is an (N, tile) f32
value carried across blocks in a VMEM scratch, with di on the lanes and N on
the sublanes. Only dt, x, B, C, y and the state at each block boundary touch
HBM. B and C come in as (N, block) slices, time on the lanes; at a block's
first di tile they are spread into (block, N, 128) VMEM scratches (B_t
repeated over the lanes), which every tile of the block reads. Time steps go
in groups of 8 rows (one f32 sublane tile of dt, x and y).

Grid: (batch, time block, di tile). The di tile is innermost: a time
block's B and C are fetched and spread once for all tiles, and the backward
kernel sums each tile's dB and dC into VMEM, reducing them over the lanes
at the block's last tile. TPU grids run in order, so the per-tile state
scratch carries across time blocks.

``mamba_scan_bwd`` walks time blocks in reverse. For each block it recomputes
the states inside it from the saved boundary state into VMEM, then runs the
reverse recurrence for the state's cotangent. It emits d(dt), dx, dB, dC and
dh0 whole, and dA as a partial sum per time block, finished outside.

Any shape runs: :func:`mamba_scan` pads di to a multiple of 128 with zero
channels, and the sequence to whole blocks with steps of dt = 0, which leave
the state as it was (exp(0 * A) = 1, dt * x = 0). The padding is sliced off.

Validated against ref.py with interpret=True (CPU); compiles to the real
Mosaic pipeline on TPU.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from .interpret import resolve_interpret

__all__ = ["mamba_scan", "time_blocks"]

LANES = 128      # f32 lane width: di tiles are multiples of this
ROWS = 8         # f32 sublane tile: time blocks are multiples of this
MAX_TILE = 1024  # widest di tile: its state and A stay near the vreg file
# the backward kernel's recompute buffer (block x N x tile f32, 5.2 MB at
# hymba's widths) and double-buffered blocks pass the default scoped limit
VMEM_LIMIT = 64 * 1024 * 1024


def lane_tile(di: int) -> int:
    """Widest multiple of 128 that divides ``di`` and is at most MAX_TILE."""
    return max(t for t in range(LANES, min(di, MAX_TILE) + 1, LANES) if di % t == 0)


def _round_up(n: int, m: int) -> int:
    return -(-n // m) * m


def time_blocks(T: int, chunk: int) -> tuple[int, int]:
    """(block, padded T) for a sequence of T steps and blocks of at most
    about ``chunk`` steps. B and C put time on the lanes, so a block is the
    whole padded sequence (T rounded up to 8 steps) where that fits
    ``chunk`` rounded up to 128, and otherwise exactly that many steps."""
    block = _round_up(max(chunk, 1), LANES)
    rows = _round_up(T, ROWS)
    if rows <= block:
        return rows, rows
    return block, _round_up(T, block)


def _lanes(v, nl):
    """Repeat an (N, 128) value nl times along the lanes: (N, nl * 128)."""
    return jnp.concatenate([v] * nl, axis=1)


def _spread(src_ref, dst):
    """dst[t] = src[:, t] repeated over 128 lanes: (N, block) -> (block, N, 128)."""
    dst[...] = jnp.broadcast_to(src_ref[0].T[:, :, None], dst.shape)


def _gather(src, dst_ref):
    """dst[:, t] = the sum of src[t] over its lanes: (block, N, 128) -> (N, block)."""
    dst_ref[0] = jnp.sum(src[...], axis=2).T


def _fwd_kernel(dt_ref, x_ref, b_ref, c_ref, a_ref, h0_ref, y_ref, hb_ref,
                h_scr, b_scr, c_scr, *, nl, chunk):
    k = pl.program_id(1)
    j = pl.program_id(2)

    @pl.when(k == 0)
    def _():
        h_scr[j] = h0_ref[0]

    @pl.when(j == 0)
    def _():
        _spread(b_ref, b_scr)
        _spread(c_ref, c_scr)

    A = a_ref[...]
    row = lax.broadcasted_iota(jnp.int32, (ROWS, A.shape[1]), 0)

    def group(s, h):
        r0 = pl.multiple_of(s * ROWS, ROWS)
        dt8 = dt_ref[0, pl.ds(r0, ROWS), :]
        u8 = dt8 * x_ref[0, pl.ds(r0, ROWS), :]
        y8 = jnp.zeros_like(dt8)
        for i in range(ROWS):
            bt = _lanes(b_scr[r0 + i], nl)
            ct = _lanes(c_scr[r0 + i], nl)
            h = jnp.exp(dt8[i:i + 1] * A) * h + u8[i:i + 1] * bt
            y8 = jnp.where(row == i, jnp.sum(h * ct, axis=0, keepdims=True), y8)
        y_ref[0, pl.ds(r0, ROWS), :] = y8
        return h

    h = lax.fori_loop(0, chunk // ROWS, group, h_scr[j])
    h_scr[j] = h
    hb_ref[0, 0] = h


def _bwd_kernel(dt_ref, x_ref, b_ref, c_ref, a_ref, hs_ref, gy_ref, ghl_ref,
                ddt_ref, dx_ref, db_ref, dc_ref, da_ref, dh0_ref,
                g_scr, hp_scr, b_scr, c_scr, db_scr, dc_scr, *, nl, nj, chunk):
    k = pl.program_id(1)
    j = pl.program_id(2)
    ng = chunk // ROWS

    @pl.when(k == 0)
    def _():
        g_scr[j] = ghl_ref[0]

    @pl.when(j == 0)
    def _():
        _spread(b_ref, b_scr)
        _spread(c_ref, c_scr)
        db_scr[...] = jnp.zeros_like(db_scr)
        dc_scr[...] = jnp.zeros_like(dc_scr)

    A = a_ref[...]
    row = lax.broadcasted_iota(jnp.int32, (ROWS, A.shape[1]), 0)

    def lane_sum(v):
        """(N, nl * 128) -> (N, 128): the sum of the lane columns."""
        out = v[:, :LANES]
        for l in range(1, nl):
            out = out + v[:, l * LANES:(l + 1) * LANES]
        return out

    # recompute: the state before each step of the block, into VMEM
    def recompute(s, h):
        r0 = pl.multiple_of(s * ROWS, ROWS)
        dt8 = dt_ref[0, pl.ds(r0, ROWS), :]
        u8 = dt8 * x_ref[0, pl.ds(r0, ROWS), :]
        for i in range(ROWS):
            hp_scr[r0 + i] = h
            h = jnp.exp(dt8[i:i + 1] * A) * h + u8[i:i + 1] * _lanes(b_scr[r0 + i], nl)
        return h

    lax.fori_loop(0, ng, recompute, hs_ref[0, 0])

    # reverse walk: c is the cotangent reaching h_t from steps after t
    def group(s, carry):
        c, da = carry
        r0 = pl.multiple_of((ng - 1 - s) * ROWS, ROWS)
        dt8 = dt_ref[0, pl.ds(r0, ROWS), :]
        x8 = x_ref[0, pl.ds(r0, ROWS), :]
        gy8 = gy_ref[0, pl.ds(r0, ROWS), :]
        u8 = dt8 * x8
        ddt8 = jnp.zeros_like(dt8)
        dx8 = jnp.zeros_like(dt8)
        for i in reversed(range(ROWS)):
            t = r0 + i
            dt_i, u_i, gy_i = dt8[i:i + 1], u8[i:i + 1], gy8[i:i + 1]
            bt = _lanes(b_scr[t], nl)
            hp = hp_scr[t]
            a = jnp.exp(dt_i * A)
            g = gy_i * _lanes(c_scr[t], nl) + c
            dc_scr[t] = dc_scr[t] + lane_sum(gy_i * (a * hp + u_i * bt))
            db_scr[t] = db_scr[t] + lane_sum(g * u_i)
            gb = jnp.sum(g * bt, axis=0, keepdims=True)
            c = a * g
            gah = c * hp
            da = da + gah * dt_i
            ga = jnp.sum(gah * A, axis=0, keepdims=True)
            ddt8 = jnp.where(row == i, x8[i:i + 1] * gb + ga, ddt8)
            dx8 = jnp.where(row == i, dt_i * gb, dx8)
        ddt_ref[0, pl.ds(r0, ROWS), :] = ddt8
        dx_ref[0, pl.ds(r0, ROWS), :] = dx8
        return c, da

    c, da = lax.fori_loop(0, ng, group, (g_scr[j], jnp.zeros_like(A)))
    g_scr[j] = c
    # dh0's block is written at every time block; the last one written, after
    # the sequence's first block, holds the result
    dh0_ref[0] = c
    da_ref[0, 0] = da

    @pl.when(j == nj - 1)
    def _():
        _gather(db_scr, db_ref)
        _gather(dc_scr, dc_ref)


def _params():
    return pltpu.CompilerParams(
        dimension_semantics=("arbitrary", "arbitrary", "arbitrary"),
        vmem_limit_bytes=VMEM_LIMIT,
    )


def _fwd_call(dt, x, bT, cT, At, h0t, *, chunk, interpret):
    Bt, T, di = dt.shape
    N = bT.shape[1]
    tile = lane_tile(di)
    nl, nt, nj = tile // LANES, T // chunk, di // tile
    seq = pl.BlockSpec((1, chunk, tile), lambda b, k, j: (b, k, j))
    bc = pl.BlockSpec((1, N, chunk), lambda b, k, j: (b, 0, k))
    spread = pltpu.VMEM((chunk, N, LANES), jnp.float32)
    return pl.pallas_call(
        functools.partial(_fwd_kernel, nl=nl, chunk=chunk),
        grid=(Bt, nt, nj),
        in_specs=[
            seq, seq, bc, bc,
            pl.BlockSpec((N, tile), lambda b, k, j: (0, j)),
            pl.BlockSpec((1, N, tile), lambda b, k, j: (b, 0, j)),
        ],
        out_specs=[
            seq,
            pl.BlockSpec((1, 1, N, tile), lambda b, k, j: (b, k, 0, j)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((Bt, T, di), jnp.float32),
            jax.ShapeDtypeStruct((Bt, nt, N, di), jnp.float32),
        ],
        scratch_shapes=[pltpu.VMEM((nj, N, tile), jnp.float32), spread, spread],
        compiler_params=_params(),
        interpret=interpret,
        name="mamba_scan_fwd",
    )(dt, x, bT, cT, At, h0t)


def _bwd_call(dt, x, bT, cT, At, hs, gy, ghl, *, chunk, interpret):
    Bt, T, di = dt.shape
    N = bT.shape[1]
    tile = lane_tile(di)
    nl, nt, nj = tile // LANES, T // chunk, di // tile
    seq = pl.BlockSpec((1, chunk, tile), lambda b, k, j: (b, nt - 1 - k, j))
    bc = pl.BlockSpec((1, N, chunk), lambda b, k, j: (b, 0, nt - 1 - k))
    state = pl.BlockSpec((1, 1, N, tile), lambda b, k, j: (b, nt - 1 - k, 0, j))
    row = pl.BlockSpec((1, N, tile), lambda b, k, j: (b, 0, j))
    spread = pltpu.VMEM((chunk, N, LANES), jnp.float32)
    return pl.pallas_call(
        functools.partial(_bwd_kernel, nl=nl, nj=nj, chunk=chunk),
        grid=(Bt, nt, nj),
        in_specs=[
            seq, seq, bc, bc,
            pl.BlockSpec((N, tile), lambda b, k, j: (0, j)),
            state, seq, row,
        ],
        out_specs=[seq, seq, bc, bc, state, row],
        out_shape=[
            jax.ShapeDtypeStruct((Bt, T, di), jnp.float32),
            jax.ShapeDtypeStruct((Bt, T, di), jnp.float32),
            jax.ShapeDtypeStruct(bT.shape, jnp.float32),
            jax.ShapeDtypeStruct(bT.shape, jnp.float32),
            jax.ShapeDtypeStruct((Bt, nt, N, di), jnp.float32),
            jax.ShapeDtypeStruct((Bt, N, di), jnp.float32),
        ],
        scratch_shapes=[
            pltpu.VMEM((nj, N, tile), jnp.float32),
            pltpu.VMEM((chunk, N, tile), jnp.float32),
            spread, spread, spread, spread,
        ],
        compiler_params=_params(),
        interpret=interpret,
        name="mamba_scan_bwd",
    )(dt, x, bT, cT, At, hs, gy, ghl)


@functools.partial(jax.custom_vjp, nondiff_argnums=(6, 7))
def _scan(dt, x, bT, cT, At, h0t, chunk, interpret):
    return _scan_fwd(dt, x, bT, cT, At, h0t, chunk, interpret)[0]


def _scan_fwd(dt, x, bT, cT, At, h0t, chunk, interpret):
    """The kernel layout: B, C (Bt, N, T); A (N, di); states (Bt, N, di)."""
    y, hb = _fwd_call(dt, x, bT, cT, At, h0t, chunk=chunk, interpret=interpret)
    # the state entering each time block: h0, then every boundary but the last
    hs = jnp.concatenate([h0t[:, None], hb[:, :-1]], axis=1)
    return (y, hb[:, -1]), (dt, x, bT, cT, At, hs)


def _scan_bwd(chunk, interpret, res, cts):
    dt, x, bT, cT, At, hs = res
    gy, ghl = cts
    ddt, dx, dbT, dcT, da, dh0 = _bwd_call(
        dt, x, bT, cT, At, hs, gy, ghl, chunk=chunk, interpret=interpret)
    return ddt, dx, dbT, dcT, da.sum((0, 1)), dh0


_scan.defvjp(_scan_fwd, _scan_bwd)


def mamba_scan(dt, x, Bm, Cm, A, h0, *, chunk: int, interpret: bool | None = None):
    """dt, x: (Bt, T, di); Bm, Cm: (Bt, T, N); A: (di, N); h0: (Bt, di, N),
    computed in f32. Returns (y (Bt, T, di), h_last (Bt, di, N)), where
    y_t = sum_n h_t C_t (no skip term). Any T and di: time goes in blocks
    of :func:`time_blocks` (T, chunk), and the padding is sliced off.
    ``interpret=None`` follows the backend. Differentiable (custom VJP, both
    passes kernels)."""
    Bt, T, di = dt.shape
    block, Tp = time_blocks(T, chunk)
    dp = _round_up(di, LANES)
    f32 = jnp.float32
    seq = lambda v: jnp.pad(v.astype(f32), ((0, 0), (0, Tp - T), (0, dp - di)))
    # B and C with time on the lanes; the state with di on the lanes
    bc = lambda v: jnp.pad(jnp.swapaxes(v.astype(f32), 1, 2), ((0, 0), (0, 0), (0, Tp - T)))
    chan = lambda v: jnp.pad(jnp.swapaxes(v.astype(f32), -1, -2),
                             ((0, 0),) * (v.ndim - 1) + ((0, dp - di),))
    y, h_last = _scan(seq(dt), seq(x), bc(Bm), bc(Cm), chan(A), chan(h0), block,
                      resolve_interpret(interpret))
    return y[:, :T, :di], jnp.swapaxes(h_last[..., :di], 1, 2)
