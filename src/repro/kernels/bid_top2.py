"""Pallas TPU kernel: fused auction bidding (ABA hot spot #2).

One auction round needs, per unassigned row i, the top-2 of
``value[i, j] = -2 x_i . mu_j + ||mu_j||^2 - price_j`` plus the argmax.  The
naive path materializes the (m, k) value matrix in HBM every round; this
kernel streams column tiles through VMEM and keeps only the running
(v1, j1, v2) per row -- O(m) HBM output instead of O(m*k), turning the
memory-bound bidding step into an MXU-bound one.

The row-constant ``||x_i||^2`` is dropped: v1 - v2 (the bid increment) and the
argmax are invariant to per-row constants.

The streaming core's chunk steps use the gather-fused twin of this kernel
(``repro.kernels.gather.bid_top2_gather_pallas``, dispatched through
``repro.kernels.ops.bid_top2(..., idx=)``): same tile loop and top-2 merge,
but the row block arrives through a double-buffered DMA ring indexed by a
prefetched ``idx`` vector, so the gathered copy never exists in HBM.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels.cdist import dot_t

_NEG = -1e30


def _bid_kernel(x_ref, c_ref, cn_ref, p_ref, v1_ref, j1_ref, v2_ref, *, bn):
    """Grid = (M/bm, K/bn); the column dim j is innermost (sequential merge)."""
    merge_top2(dot_t(x_ref[...], c_ref[...]), cn_ref, p_ref,
               v1_ref, j1_ref, v2_ref, j=pl.program_id(1), bn=bn)


def merge_top2(dots, cn_ref, p_ref, v1_ref, j1_ref, v2_ref, *, j, bn):
    """Fold column tile ``j`` of ``-2 x . mu + ||mu||^2 - price`` into the
    running per-row (v1, j1, v2) held in the (bm, 1) output blocks.

    ``dots`` is the (bm, bn) tile of ``x . mu``; ``cn_ref`` / ``p_ref`` are
    the tile's (1, bn) norm and price rows.  Shared with the gather-fused
    twin in ``repro.kernels.gather``.
    """

    @pl.when(j == 0)
    def _init():
        v1_ref[...] = jnp.full_like(v1_ref, _NEG)
        j1_ref[...] = jnp.zeros_like(j1_ref)
        v2_ref[...] = jnp.full_like(v2_ref, _NEG)

    vals = -2.0 * dots + (cn_ref[...] - p_ref[...])

    # tile top-2 (iota-based, TPU-safe); keepdims keeps rows on sublanes
    col = jax.lax.broadcasted_iota(jnp.int32, vals.shape, 1)
    t_v1 = jnp.max(vals, axis=1, keepdims=True)
    t_j1 = jnp.min(jnp.where(vals >= t_v1, col, bn), axis=1, keepdims=True)
    t_v2 = jnp.max(jnp.where(col == t_j1, _NEG, vals), axis=1, keepdims=True)
    t_j1 = t_j1 + j * bn

    # merge with running top-2: second best of two sorted pairs
    r_v1, r_j1, r_v2 = v1_ref[...], j1_ref[...], v2_ref[...]
    take = t_v1 > r_v1
    v1_ref[...] = jnp.where(take, t_v1, r_v1)
    j1_ref[...] = jnp.where(take, t_j1, r_j1)
    v2_ref[...] = jnp.maximum(jnp.minimum(t_v1, r_v1),
                              jnp.maximum(t_v2, r_v2))


def top2_operands(c, prices, kp):
    """(1, kp) norm and price rows, lane-dense; padded columns get price
    +inf so they never win.  1-D (bn,) blocks do not match the layout XLA
    gives a long f32 vector on TPU, so the kernels take 2-D rows."""
    k, d = c.shape
    cp = jnp.zeros((kp, d), jnp.float32).at[:k].set(c.astype(jnp.float32))
    cn = jnp.sum(cp * cp, axis=1)[None, :]
    pp = jnp.full((1, kp), -_NEG, jnp.float32).at[0, :k].set(
        prices.astype(jnp.float32))
    return cp, cn, pp


def top2_out_shape(mp):
    """Per-row (v1, j1, v2) as (mp, 1) columns: rows stay on sublanes."""
    return [jax.ShapeDtypeStruct((mp, 1), jnp.float32),
            jax.ShapeDtypeStruct((mp, 1), jnp.int32),
            jax.ShapeDtypeStruct((mp, 1), jnp.float32)]


@functools.partial(
    jax.jit, static_argnames=("bm", "bn", "interpret"))
def bid_top2_pallas(
    x: jnp.ndarray,
    c: jnp.ndarray,
    prices: jnp.ndarray,
    *,
    bm: int = 128,
    bn: int = 512,
    interpret: bool = False,
):
    """(m, d), (k, d), (k,) -> (v1, j1, v2) each (m,).

    v1/v2 are the best/second-best *reduced* values (row constant dropped);
    j1 is the argmax column.  Padded columns get price +inf so they never win.
    """
    m, d = x.shape
    k, d2 = c.shape
    assert d == d2
    bm, bn = min(bm, _rup(m, 8)), min(bn, _rup(k, 128))
    mp, kp = _rup(m, bm), _rup(k, bn)
    xp = jnp.zeros((mp, d), jnp.float32).at[:m].set(x.astype(jnp.float32))
    cp, cn, pp = top2_operands(c, prices, kp)

    row = pl.BlockSpec((bm, 1), lambda i, j: (i, 0))
    v1, j1, v2 = pl.pallas_call(
        functools.partial(_bid_kernel, bn=bn),
        grid=(mp // bm, kp // bn),
        in_specs=[
            pl.BlockSpec((bm, d), lambda i, j: (i, 0)),
            pl.BlockSpec((bn, d), lambda i, j: (j, 0)),
            pl.BlockSpec((1, bn), lambda i, j: (0, j)),
            pl.BlockSpec((1, bn), lambda i, j: (0, j)),
        ],
        out_specs=[row, row, row],
        out_shape=top2_out_shape(mp),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary")),
        interpret=interpret,
    )(xp, cp, cn, pp)
    return v1[:m, 0], j1[:m, 0], v2[:m, 0]


def _rup(v: int, m: int) -> int:
    return -(-v // m) * m
