"""Pallas TPU kernel: one epsilon phase of the dense auction, VMEM-resident.

The dense auction (``repro.core.assignment._auction_phase``) runs each
bidding round as a chain of XLA ops -- a top-2 over ``cost - prices``,
scatters for the per-object best bid and winner, gathers for the bid and the
outbid check -- and every op reads the (n, n) value matrix from HBM again.
A LAP of a few hundred columns fits in VMEM, so this kernel loads one
instance's cost block once and runs the phase's whole round loop on it:
one program per stack instance, the grid over the stack.  (Blocking
several small instances into one program measured slower on a v5e: a
block runs as many rounds as its slowest instance.)

Each round is the same arithmetic as ``_auction_phase``'s ``body_with``, in
f32 and in the same order; every scatter and gather of the XLA round becomes
a one-hot compare against a lane or sublane iota followed by a reduction
(max and min do not depend on the order of their terms, and ties still go to
the lowest row index), so assignment, prices and round count are bitwise
those of the XLA loop.  Per-row vectors are (rows, 1) columns, per-object
vectors (1, cols) rows.

Padding keeps shapes tile-aligned and changes nothing: padded columns cost
-inf (never a favourite; the runner-up already counts the masked favourite
as ``_NEG``), padded rows start "assigned" to a column that does not exist,
so they never bid, win or lose.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

_NEG = -1e30  # repro.core.assignment._NEG
_LANE = 128


def _phase_kernel(eps_ref, cost_ref, p_ref, a_ref, a_out, p_out, it_out, *,
                  max_rounds, min_rounds):
    R, L = cost_ref.shape
    eps = eps_ref[:, :1]
    col = jax.lax.broadcasted_iota(jnp.int32, (R, L), 1)
    row = jax.lax.broadcasted_iota(jnp.int32, (R, L), 0)

    def cond(state):
        a, _p, it = state
        return jnp.logical_or(
            it < min_rounds,
            jnp.logical_and(jnp.min(a) < 0, it < max_rounds))

    def body(state):
        a, p, it = state
        unassigned = a < 0
        vals = cost_ref[...] - p
        # top-2: v1, the lowest column reaching it, the max of the rest
        v1 = jnp.max(vals, axis=1, keepdims=True)
        j1 = jnp.min(jnp.where(vals == v1, col, L), axis=1, keepdims=True)
        fav = col == j1
        v2 = jnp.max(jnp.where(fav, _NEG, vals), axis=1, keepdims=True)
        p_j1 = jnp.max(jnp.where(fav, p, -jnp.inf), axis=1, keepdims=True)
        bid = jnp.where(unassigned, v1 + p_j1 - v2 + eps, _NEG)
        # per-object best bid and winning row: the lowest unassigned row
        # whose bid on its favourite reaches that object's best
        best = jnp.maximum(
            jnp.max(jnp.where(fav, bid, _NEG), axis=0, keepdims=True), _NEG)
        wins = jnp.logical_and(jnp.logical_and(fav, unassigned), bid >= best)
        winner = jnp.min(jnp.where(wins, row, R), axis=0, keepdims=True)
        got_bid = winner < R
        # Winners take their objects; a row whose object got a bid loses
        # it (it held the object, so it did not bid and cannot be the
        # winner), so an assigned row keeps its object only where no bid
        # came.  The two sets are disjoint: one reduction gives both.
        keep = jnp.logical_and(col == a, jnp.logical_not(got_bid))
        took = jnp.max(jnp.where(jnp.logical_or(winner == row, keep), col, -1),
                       axis=1, keepdims=True)
        a = jnp.where(a == L, a, took)  # padded rows stay out
        return a, jnp.where(got_bid, best, p), it + 1

    a, p, it = jax.lax.while_loop(cond, body,
                                  (a_ref[...], p_ref[...], jnp.int32(0)))
    a_out[...] = a
    p_out[...] = p
    it_out[...] = jnp.full(it_out.shape, it, jnp.int32)


def padded_cost(cost: jnp.ndarray) -> jnp.ndarray:
    """(B, n, n) -> (B, R, L) float32, R and L the sublane and lane tiles'
    multiples; padded entries are -inf (see module doc)."""
    B, n, _ = cost.shape
    R, L = _rup(n, 8), _rup(n, _LANE)
    cost = cost.astype(jnp.float32)
    if (R, L) == (n, n):
        return cost
    return jnp.full((B, R, L), -jnp.inf, jnp.float32).at[:, :n, :n].set(cost)


def block_bytes(n: int) -> int:
    """Bytes of one instance's padded cost block."""
    return _rup(n, 8) * _rup(n, _LANE) * 4


@functools.partial(jax.jit, static_argnames=(
    "n", "max_rounds", "min_rounds", "vmem_limit_bytes", "interpret"))
def auction_phase_pallas(cost, prices, eps, assign0, *, n, max_rounds,
                         vmem_limit_bytes, min_rounds=0, interpret=False):
    """One eps phase on a :func:`padded_cost` stack (see module doc).

    ``prices`` (B, n) f32, ``eps`` (B,) f32, ``assign0`` (B, n) int32 (-1
    unassigned, a column for a pre-assigned row).  An instance's rounds run
    until none of its rows is unassigned or ``max_rounds`` have run; the
    first ``min_rounds`` run in any case (a no-op but for the count).
    Returns the assignment (B, n), prices (B, n) and the rounds each
    instance ran (B,).
    """
    B, R, L = cost.shape
    # per-instance scalars ride in one lane tile: (1, 128) rows
    ep = jnp.broadcast_to(eps.astype(jnp.float32)[:, None, None],
                          (B, 1, _LANE))
    pp = jnp.zeros((B, 1, L), jnp.float32).at[:, 0, :n].set(prices)
    ap = jnp.full((B, R, 1), L, jnp.int32).at[:, :n, 0].set(assign0)
    blk = lambda *s: pl.BlockSpec((None,) + s, lambda b: (b, 0, 0))
    a, p, it = pl.pallas_call(
        functools.partial(_phase_kernel, max_rounds=max_rounds,
                          min_rounds=min_rounds),
        grid=(B,),
        in_specs=[blk(1, _LANE), blk(R, L), blk(1, L), blk(R, 1)],
        out_specs=[blk(R, 1), blk(1, L), blk(1, _LANE)],
        out_shape=[jax.ShapeDtypeStruct((B, R, 1), jnp.int32),
                   jax.ShapeDtypeStruct((B, 1, L), jnp.float32),
                   jax.ShapeDtypeStruct((B, 1, _LANE), jnp.int32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel",),
            vmem_limit_bytes=vmem_limit_bytes),
        interpret=interpret,
        name="resident_auction",
    )(ep, cost, pp, ap)
    return a[:, :n, 0], p[:, 0, :n], it[:, 0, 0]


def _rup(v: int, m: int) -> int:
    return -(-v // m) * m
