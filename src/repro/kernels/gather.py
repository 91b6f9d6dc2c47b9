"""Pallas TPU kernels: double-buffered row gather + fused gather-compute.

The streaming ABA core touches its data one chunk at a time through an
index gather (``x[idx_chunk]``).  On TPU a plain gather serializes: HBM row
movement for chunk t+1 waits for chunk t's compute.  These kernels pipeline
it instead -- rows are pulled HBM -> VMEM with explicit ``make_async_copy``
DMAs into a 2-slot scratch ring, so while block ``j`` is being consumed the
copies for block ``j+1`` are already in flight (classic double buffering;
the scalar-prefetch index vector is available to the kernel before the grid
runs, which is what lets it compute source addresses ahead of time).

Three entry points, all sharing the same issue/wait ring:

- :func:`gather_rows_pallas` -- pure gather, ``x[idx]`` with overlapped DMA.
- :func:`bid_top2_gather_pallas` -- fused ``bid_top2(x[idx], c, prices)``:
  the gathered rows never round-trip to HBM; each row block is DMA'd once
  and reduced against every centroid tile while the next block streams in.
- :func:`cdist_gather_pallas` -- fused ``cdist(x[idx], c)`` (untiled D; the
  dispatcher composes gather + tiled cdist instead when D is too large for
  full rows in VMEM).

On CPU these run under ``interpret=True`` for parity tests only -- the
dispatcher (:func:`repro.kernels.ops.gather_rows`) uses the jnp take there,
because interpreting a per-row DMA loop in Python has no fidelity value.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels.bid_top2 import merge_top2, top2_operands, top2_out_shape
from repro.kernels.cdist import dot_t

_LANE = 128


def row_table(x: jnp.ndarray) -> jnp.ndarray:
    """(n, d) -> (n, 1, dp) float32 source table for the row DMAs.

    A TPU DMA moves whole tiles: a single row of a 2-D (8, 128)-tiled array
    is a slice the compiler refuses (the sublane dim is tiled by 8), and a
    row narrower than the 128-lane tile is refused too.  Giving every row
    its own unit sublane dim and zero-padding d up to a lane multiple ``dp``
    makes each row one aligned (1, dp) slab.  The zero lanes change no dot
    product or norm.
    """
    n, d = x.shape
    dp = _rup(d, _LANE)
    xf = x.astype(jnp.float32)
    if dp != d:
        xf = jnp.pad(xf, ((0, 0), (0, dp - d)))
    return xf.reshape(n, 1, dp)


def _copy(idx_ref, x_ref, rows, sems, slot, blk, bm, r):
    return pltpu.make_async_copy(x_ref.at[idx_ref[blk * bm + r]],
                                 rows.at[slot, r], sems.at[slot])


def _issue_block(idx_ref, x_ref, rows, sems, slot, blk, bm):
    """Start the per-row HBM->VMEM copies for row block ``blk`` into ``slot``."""

    def row(r, _):
        _copy(idx_ref, x_ref, rows, sems, slot, blk, bm, r).start()
        return 0

    jax.lax.fori_loop(0, bm, row, 0)


def _wait_block(idx_ref, x_ref, rows, sems, slot, blk, bm):
    """Block until every row of ``blk`` has landed in ``slot``."""

    def row(r, _):
        _copy(idx_ref, x_ref, rows, sems, slot, blk, bm, r).wait()
        return 0

    jax.lax.fori_loop(0, bm, row, 0)


def _landed(rows, slot):
    """The (bm, dp) row block held in ring ``slot``."""
    _, bm, _, dp = rows.shape
    return rows[slot].reshape(bm, dp)


def _ring(bm, dp):
    """The 2-slot VMEM ring of (1, dp) row slabs and one DMA semaphore per
    slot: every row copy of a block signals its slot's semaphore, and the
    wait loop takes one row's worth per copy.  (A semaphore per row would
    outgrow the chip's semaphore memory at bm = 256.)"""
    return [pltpu.VMEM((2, bm, 1, dp), jnp.float32),
            pltpu.SemaphoreType.DMA((2,))]


def _pad_idx(idx, n, mp):
    idx_p = jnp.clip(idx.astype(jnp.int32), 0, n - 1)
    m = idx.shape[0]
    if mp > m:
        idx_p = jnp.concatenate([idx_p, jnp.zeros((mp - m,), jnp.int32)])
    return idx_p


_HBM = pl.BlockSpec(memory_space=pltpu.HBM)


# ---------------------------------------------------------------------------
# Pure gather
# ---------------------------------------------------------------------------


def _gather_kernel(idx_ref, x_ref, o_ref, rows, sems, *, bm):
    """Grid = (M/bm,): copy-out slot j%2 while slot (j+1)%2 fills."""
    j = pl.program_id(0)
    nb = pl.num_programs(0)

    @pl.when(j == 0)
    def _prologue():
        _issue_block(idx_ref, x_ref, rows, sems, 0, 0, bm)

    @pl.when(j + 1 < nb)
    def _prefetch():
        _issue_block(idx_ref, x_ref, rows, sems, (j + 1) % 2, j + 1, bm)

    _wait_block(idx_ref, x_ref, rows, sems, j % 2, j, bm)
    o_ref[...] = _landed(rows, j % 2)


@functools.partial(jax.jit, static_argnames=("d", "bm", "interpret"))
def gather_rows_pallas(
    table: jnp.ndarray,
    idx: jnp.ndarray,
    *,
    d: int,
    bm: int = 256,
    interpret: bool = False,
) -> jnp.ndarray:
    """``x[idx]`` with double-buffered DMA: (n, 1, dp) :func:`row_table`,
    (m,) -> (m, d) float32.

    Takes the prepared table so a caller that gathers from the same ``x``
    many times (the streaming core, once per chunk) lays it out once.
    Out-of-range indices are clipped (the streaming core clamps sentinels
    itself and masks their values downstream).
    """
    n, _, dp = table.shape
    m = idx.shape[0]
    bm = min(bm, _rup(m, 8))
    mp = _rup(m, bm)

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=(mp // bm,),
        in_specs=[_HBM],
        out_specs=pl.BlockSpec((bm, dp), lambda j, idx_ref: (j, 0)),
        scratch_shapes=_ring(bm, dp),
    )
    out = pl.pallas_call(
        functools.partial(_gather_kernel, bm=bm),
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((mp, dp), jnp.float32),
        interpret=interpret,
    )(_pad_idx(idx, n, mp), table)
    return out[:m, :d]


# ---------------------------------------------------------------------------
# Fused gather + bid_top2
# ---------------------------------------------------------------------------


def _bid_gather_kernel(idx_ref, x_ref, c_ref, cn_ref, p_ref,
                       v1_ref, j1_ref, v2_ref, rows, sems, *, bm, bn):
    """Grid = (M/bm, K/bn), j innermost.  Row block i is DMA'd once into the
    2-slot ring at its first column step and reduced against every centroid
    tile; block i+1's copies are issued at the same point, so they overlap
    the whole inner loop over centroid tiles."""
    i = pl.program_id(0)
    j = pl.program_id(1)

    @pl.when(jnp.logical_and(i == 0, j == 0))
    def _prologue():
        _issue_block(idx_ref, x_ref, rows, sems, 0, 0, bm)

    @pl.when(j == 0)
    def _arrive():
        _wait_block(idx_ref, x_ref, rows, sems, i % 2, i, bm)

        @pl.when(i + 1 < pl.num_programs(0))
        def _prefetch():
            _issue_block(idx_ref, x_ref, rows, sems, (i + 1) % 2, i + 1, bm)

    merge_top2(dot_t(_landed(rows, i % 2), c_ref[...]), cn_ref, p_ref,
               v1_ref, j1_ref, v2_ref, j=j, bn=bn)


@functools.partial(jax.jit, static_argnames=("bm", "bn", "interpret"))
def bid_top2_gather_pallas(
    x: jnp.ndarray,
    idx: jnp.ndarray,
    c: jnp.ndarray,
    prices: jnp.ndarray,
    *,
    bm: int = 128,
    bn: int = 512,
    interpret: bool = False,
):
    """``bid_top2(x[idx], c, prices)`` without materializing ``x[idx]``:
    (n, d), (m,), (k, d), (k,) -> (v1, j1, v2) each (m,)."""
    n, d = x.shape
    m = idx.shape[0]
    k, d2 = c.shape
    assert d == d2, (x.shape, c.shape)
    bm, bn = min(bm, _rup(m, 8)), min(bn, _rup(k, 128))
    mp, kp = _rup(m, bm), _rup(k, bn)
    table = row_table(x)
    dp = table.shape[-1]
    cp, cn, pp = top2_operands(jnp.pad(c, ((0, 0), (0, dp - d))), prices, kp)

    row = pl.BlockSpec((bm, 1), lambda i, j, idx_ref: (i, 0))
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=(mp // bm, kp // bn),
        in_specs=[
            _HBM,
            pl.BlockSpec((bn, dp), lambda i, j, idx_ref: (j, 0)),
            pl.BlockSpec((1, bn), lambda i, j, idx_ref: (0, j)),
            pl.BlockSpec((1, bn), lambda i, j, idx_ref: (0, j)),
        ],
        out_specs=[row, row, row],
        scratch_shapes=_ring(bm, dp),
    )
    v1, j1, v2 = pl.pallas_call(
        functools.partial(_bid_gather_kernel, bm=bm, bn=bn),
        grid_spec=grid_spec,
        out_shape=top2_out_shape(mp),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary", "arbitrary")),
        interpret=interpret,
    )(_pad_idx(idx, n, mp), table, cp, cn, pp)
    return v1[:m, 0], j1[:m, 0], v2[:m, 0]


# ---------------------------------------------------------------------------
# Fused gather + cdist (untiled D)
# ---------------------------------------------------------------------------


def _cdist_gather_kernel(idx_ref, x_ref, c_ref, cn_ref, o_ref, rows, sems,
                         *, bm):
    """Grid = (M/bm, N/bn), j innermost; full rows in VMEM (no D tiling),
    so ``||x_i||^2`` is computed from the landed scratch rows directly."""
    i = pl.program_id(0)
    j = pl.program_id(1)

    @pl.when(jnp.logical_and(i == 0, j == 0))
    def _prologue():
        _issue_block(idx_ref, x_ref, rows, sems, 0, 0, bm)

    @pl.when(j == 0)
    def _arrive():
        _wait_block(idx_ref, x_ref, rows, sems, i % 2, i, bm)

        @pl.when(i + 1 < pl.num_programs(0))
        def _prefetch():
            _issue_block(idx_ref, x_ref, rows, sems, (i + 1) % 2, i + 1, bm)

    xb = _landed(rows, i % 2)
    dots = dot_t(xb, c_ref[...])
    xn = jnp.sum(xb * xb, axis=1, keepdims=True)
    o_ref[...] = (xn - 2.0 * dots + cn_ref[...]).astype(o_ref.dtype)


@functools.partial(
    jax.jit, static_argnames=("bm", "bn", "interpret", "out_dtype"))
def cdist_gather_pallas(
    x: jnp.ndarray,
    idx: jnp.ndarray,
    c: jnp.ndarray,
    *,
    bm: int = 128,
    bn: int = 128,
    interpret: bool = False,
    out_dtype=jnp.float32,
) -> jnp.ndarray:
    """``cdist(x[idx], c)`` without materializing ``x[idx]``:
    (n, d), (m,), (nc, d) -> (m, nc) squared distances."""
    n, d = x.shape
    m = idx.shape[0]
    nc, d2 = c.shape
    assert d == d2, (x.shape, c.shape)
    bm, bn = min(bm, _rup(m, 8)), min(bn, _rup(nc, 128))
    mp, ncp = _rup(m, bm), _rup(nc, bn)
    table = row_table(x)
    dp = table.shape[-1]
    cp = jnp.zeros((ncp, dp), jnp.float32).at[:nc, :d].set(
        c.astype(jnp.float32))
    cn = jnp.sum(cp * cp, axis=1)[None, :]

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=(mp // bm, ncp // bn),
        in_specs=[
            _HBM,
            pl.BlockSpec((bn, dp), lambda i, j, idx_ref: (j, 0)),
            pl.BlockSpec((1, bn), lambda i, j, idx_ref: (0, j)),
        ],
        out_specs=pl.BlockSpec((bm, bn), lambda i, j, idx_ref: (i, j)),
        scratch_shapes=_ring(bm, dp),
    )
    out = pl.pallas_call(
        functools.partial(_cdist_gather_kernel, bm=bm),
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((mp, ncp), out_dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary", "arbitrary")),
        interpret=interpret,
    )(_pad_idx(idx, n, mp), table, cp, cn)
    return out[:m, :nc]


def _rup(v: int, m: int) -> int:
    return -(-v // m) * m
