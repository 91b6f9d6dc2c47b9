"""Pallas TPU kernel: tiled squared-Euclidean cost matrix (ABA hot spot #1).

Computes ``C[i, j] = ||x_i - mu_j||^2 = ||x_i||^2 - 2 x_i . mu_j + ||mu_j||^2``
so the dominant term is a matmul that runs on the MXU.  Blocks are 128-aligned
(MXU native tile) and accumulation is fp32 in VMEM scratch; norms are folded
in on the last reduction step, so the cost matrix is produced in one pass
over HBM with arithmetic intensity ~ bm*bn*D / ((bm+bn)*D) elements.

The ABA scan calls this once per batch with (K, D) x (K, D) -> (K, K); the
hierarchical/vmapped path calls it with a leading group dimension.  The
streaming core's chunk steps use the gather-fused twin
(``repro.kernels.gather.cdist_gather_pallas``, dispatched through
``repro.kernels.ops.cdist(..., idx=)``), whose row blocks stream HBM -> VMEM
through a double-buffered DMA ring instead of reading a pre-gathered copy.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


def dot_t(a, b):
    """``a @ b.T`` on the MXU at full f32 precision.

    Mosaic runs an f32 dot without a stated precision as one bf16 pass:
    on a v5e that put the bidding values about 5e-4 of their scale away
    from the f32 reference.  Every kernel's dot goes through here.
    """
    return jax.lax.dot_general(
        a, b, dimension_numbers=(((1,), (1,)), ((), ())),
        precision=jax.lax.Precision.HIGHEST,
        preferred_element_type=jnp.float32)


def _cdist_kernel(x_ref, c_ref, xn_ref, cn_ref, o_ref, acc_ref, *, k_steps):
    """Grid = (M/bm, N/bn, D/bk); k (reduction over D) is the innermost dim."""

    @pl.when(pl.program_id(2) == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    acc_ref[...] += dot_t(x_ref[...], c_ref[...])  # (bm, bk) x (bn, bk)^T

    @pl.when(pl.program_id(2) == k_steps - 1)
    def _finish():
        o_ref[...] = (xn_ref[...] - 2.0 * acc_ref[...] + cn_ref[...]
                      ).astype(o_ref.dtype)


@functools.partial(
    jax.jit,
    static_argnames=("bm", "bn", "bk", "interpret", "out_dtype"),
)
def cdist_pallas(
    x: jnp.ndarray,
    c: jnp.ndarray,
    *,
    bm: int = 128,
    bn: int = 128,
    bk: int = 512,
    interpret: bool = False,
    out_dtype=jnp.float32,
) -> jnp.ndarray:
    """(m, d), (n, d) -> (m, n) squared distances.  Pads to block multiples.

    Leading chunk dims are handled by the ``repro.kernels.ops.cdist``
    dispatcher (it flattens them into ``m``); already-aligned inputs are fed
    straight to the kernel so the streaming path's chunked calls do not pay
    an extra O(m*d) padded copy.
    """
    m, d = x.shape
    n, d2 = c.shape
    assert d == d2, (x.shape, c.shape)
    bm, bn, bk = min(bm, _rup(m, 8)), min(bn, _rup(n, 128)), min(bk, _rup(d, 128))
    mp, np_, dp = _rup(m, bm), _rup(n, bn), _rup(d, bk)
    xp = (x.astype(jnp.float32) if (mp, dp) == (m, d) else
          jnp.zeros((mp, dp), jnp.float32).at[:m, :d].set(
              x.astype(jnp.float32)))
    cp = (c.astype(jnp.float32) if (np_, dp) == (n, d) else
          jnp.zeros((np_, dp), jnp.float32).at[:n, :d].set(
              c.astype(jnp.float32)))
    # 2-D norm operands: a (bm, 1) column block and a lane-dense (1, bn)
    # row block.  1-D (bm,)/(bn,) blocks do not match the layout XLA gives a
    # long f32 vector on TPU, and Mosaic refuses them once m or n spans
    # more than one block.
    xn = jnp.sum(xp * xp, axis=1, keepdims=True)
    cn = jnp.sum(cp * cp, axis=1)[None, :]
    k_steps = dp // bk

    out = pl.pallas_call(
        functools.partial(_cdist_kernel, k_steps=k_steps),
        grid=(mp // bm, np_ // bn, k_steps),
        in_specs=[
            pl.BlockSpec((bm, bk), lambda i, j, k: (i, k)),
            pl.BlockSpec((bn, bk), lambda i, j, k: (j, k)),
            pl.BlockSpec((bm, 1), lambda i, j, k: (i, 0)),
            pl.BlockSpec((1, bn), lambda i, j, k: (0, j)),
        ],
        out_specs=pl.BlockSpec((bm, bn), lambda i, j, k: (i, j)),
        out_shape=jax.ShapeDtypeStruct((mp, np_), out_dtype),
        scratch_shapes=[pltpu.VMEM((bm, bn), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
        interpret=interpret,
    )(xp, cp, xn, cn)
    return out[:m, :n]


def _rup(v: int, m: int) -> int:
    return -(-v // m) * m
