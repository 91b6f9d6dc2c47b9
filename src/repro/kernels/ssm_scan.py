"""Pallas TPU kernel: fused selective-scan chunk (the S`Perf A structural fix).

The chunked jnp scan (mamba.py) still round-trips the SSM state through HBM
once per chunk and leaves the unrolled backward as ~60 small fusions (the
residual 1000s memory term in the falcon train cell).  This kernel computes a
whole chunk of the Mamba recurrence

    h_t = exp(dt_t * A) * h_{t-1} + (dt_t * x_t) B_t ;   y_t = <h_t, C_t>

with ``h`` resident in VMEM across all C timesteps: HBM traffic per chunk is
exactly inputs + outputs + one state save.  d_inner is the tiled/parallel
grid dim (TP shards it the same way), d_state rides along (16).

Forward-only (serving/prefill use; training integration would add a custom
VJP with the same chunk structure -- documented in EXPERIMENTS.md S`Perf A).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


def _ssm_chunk_kernel(dt_ref, b_ref, c_ref, x_ref, a_ref, h0_ref,
                      y_ref, h_ref, *, chunk):
    h = h0_ref[...]                       # (B, bdi, ds) fp32, stays in VMEM
    a = a_ref[...]                        # (bdi, ds)
    for t in range(chunk):                # unrolled: static small C
        dt_t = dt_ref[t]                  # (B, bdi)
        da = jnp.exp(dt_t[:, :, None] * a[None])
        h = h * da + (dt_t * x_ref[t])[:, :, None] * b_ref[t][:, None, :]
        y_ref[t] = jnp.sum(h * c_ref[t][:, None, :], axis=-1)
    h_ref[...] = h


@functools.partial(jax.jit, static_argnames=("bdi", "interpret"))
def ssm_scan_chunk_pallas(dt, b_in, c_out, x_in, a_mat, h0, *,
                          bdi: int = 512, interpret: bool = False):
    """One fused chunk of the selective scan.

    dt, x_in: (C, B, di)  fp32;  b_in, c_out: (C, B, ds)  fp32;
    a_mat: (di, ds);  h0: (B, di, ds).
    Returns (y (C, B, di), h_final (B, di, ds)).
    """
    c, bsz, di = dt.shape
    ds = a_mat.shape[1]
    bdi = min(bdi, di)
    assert di % bdi == 0, (di, bdi)
    grid = (di // bdi,)

    y, h = pl.pallas_call(
        functools.partial(_ssm_chunk_kernel, chunk=c),
        grid=grid,
        in_specs=[
            pl.BlockSpec((c, bsz, bdi), lambda i: (0, 0, i)),
            pl.BlockSpec((c, bsz, ds), lambda i: (0, 0, 0)),
            pl.BlockSpec((c, bsz, ds), lambda i: (0, 0, 0)),
            pl.BlockSpec((c, bsz, bdi), lambda i: (0, 0, i)),
            pl.BlockSpec((bdi, ds), lambda i: (i, 0)),
            pl.BlockSpec((bsz, bdi, ds), lambda i: (0, i, 0)),
        ],
        out_specs=[
            pl.BlockSpec((c, bsz, bdi), lambda i: (0, 0, i)),
            pl.BlockSpec((bsz, bdi, ds), lambda i: (0, i, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((c, bsz, di), jnp.float32),
            jax.ShapeDtypeStruct((bsz, di, ds), jnp.float32),
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel",)),
        interpret=interpret,
    )(dt.astype(jnp.float32), b_in.astype(jnp.float32),
      c_out.astype(jnp.float32), x_in.astype(jnp.float32),
      a_mat.astype(jnp.float32), h0.astype(jnp.float32))
    return y, h


def ssm_scan_pallas(dt, b_in, c_out, x_in, a_mat, *, chunk: int = 16,
                    bdi: int = 512, interpret: bool = False):
    """Full-sequence selective scan via fused chunks.

    dt, x_in: (B, S, di); b_in, c_out: (B, S, ds); a_mat (di, ds).
    Returns (y (B, S, di), h_final (B, di, ds)).
    """
    bsz, seq, di = dt.shape
    ds = a_mat.shape[1]
    chunk = chunk if seq % chunk == 0 else 1

    def to_xs(t):
        t = t.transpose(1, 0, 2)
        return t.reshape(seq // chunk, chunk, bsz, t.shape[-1])

    xs = (to_xs(dt), to_xs(b_in), to_xs(c_out), to_xs(x_in))
    h0 = jnp.zeros((bsz, di, ds), jnp.float32)

    def step(h, inp):
        d_c, b_c, c_c, x_c = inp
        y, h = ssm_scan_chunk_pallas(d_c, b_c, c_c, x_c, a_mat, h,
                                     bdi=min(bdi, di), interpret=interpret)
        return h, y

    h_fin, ys = jax.lax.scan(step, h0, xs)
    y = ys.reshape(seq, bsz, di).transpose(1, 0, 2)
    return y, h_fin
