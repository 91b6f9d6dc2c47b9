"""Linear assignment solvers for ABA.

The paper's reference implementation uses LAPJV (Jonker-Volgenant), a
branch-heavy serial algorithm that maps poorly onto TPU vector/matrix units.
Following the paper's own future-work pointer (Bertsekas' auction algorithm,
Section 6), we implement a fully vectorized **Jacobi auction** with
epsilon-scaling: every round is a dense top-2 reduction over the cost matrix
plus scatter-max bidding -- VPU/MXU friendly, `vmap`-able, and usable inside
`lax.scan`/`shard_map`.

The engine is **batched-native**: a ``(B, k, k)`` cost stack is solved in one
fused round loop with per-instance convergence masking (a converged instance
is a fixed point of the round update), not a ``vmap`` over scalar solves.
Hierarchical ABA feeds every level's padded group batch through this path as
a single solver call.

All solvers MAXIMIZE total cost (anticlustering assigns batches to the
*farthest* centroids).

Solvers
-------
- ``auction_solve``           eps-optimal, jit/vmap-safe, accepts (k, k) or a
                              stacked (B, k, k); the production solver.
- ``auction_solve_factored``  matrix-free auction on ``cost = -2 x.c^T +
                              ||c||^2``; the bidding top-2 streams through the
                              fused Pallas ``bid_top2`` kernel (TPU) so the
                              value matrix is never re-materialized per round.
- ``greedy_solve``            O(n^3) vectorized greedy, cheap lower-quality.
- ``scipy_solve``             exact Hungarian via scipy (host-side oracle).

The **solver registry** (``register_solver`` / ``get_solver``) is how the ABA
core finds its LAP backend: every entry is a :class:`Solver` whose ``solve``
accepts a ``(B, n, n)`` stack (or ``(n, n)``) plus an optional warm-start
``prices`` vector and returns ``(assignment, prices)``, maximizing total
cost, with an optional matrix-free ``factored`` path.  The price vector is
the auction's dual state: :class:`repro.anticluster.AnticlusterEngine`
carries it across repeated same-shape solves (``repartition``) so each epoch
warm-starts the epsilon-scaling schedule instead of re-discovering the
equilibrium from zero.  Price-less backends (greedy, Hungarian) pass the
incoming prices through unchanged.  ``auction``, ``auction_fused``,
``greedy`` and ``scipy`` are registered by default; benchmarks and users add
LAP backends with one ``register_solver`` call instead of editing the core.
Backends registered with the legacy price-less signature
``solve(cost, config)`` are wrapped in a pass-through shim (with a
``DeprecationWarning``) so third-party registrations keep working.
"""

from __future__ import annotations

import functools
import inspect
import warnings
from typing import Callable, NamedTuple

import jax
import jax.numpy as jnp
import numpy as np

_NEG = -1e30  # sentinel "minus infinity" that survives f32 arithmetic

# Warm re-entry slack: the probe's max contested value gap overestimates the
# eps scale a warm solve must re-enter at, because only the few contested
# objects need price movement and the final phase's while-loop absorbs that
# in a handful of rounds.  Measured on the epoch bench (2048x16x8 CPU smoke):
# steady-state warm batches probe at 1.5-25x eps_lo yet the final phase alone
# converges faster than any added phase, so only gaps beyond this slack times
# the phase eps re-enter mid-schedule (prices carried across genuinely
# different problems probe at O(span), far past it).
_REENTRY_SLACK = 32.0


class AuctionConfig(NamedTuple):
    """Epsilon-scaling schedule for the auction solver.

    eps runs ``n_phases`` geometric steps from ``span/eps_start_div`` down to
    ``span/(eps_end_mul * n)``.  An eps-optimal assignment is within
    ``n * eps`` of the optimum; the default schedule gives objective parity
    with the Hungarian oracle to ~1e-6 relative on random instances.

    ``fixed_rounds > 0`` replaces the convergence while-loop with a
    fixed-length scan (the round update is a no-op at the converged fixed
    point).  Used by the dry-run so XLA knows every trip count, and on TPU it
    avoids host round-trips for the loop predicate.

    ``adaptive_reentry`` controls where a *warm-started* solve re-enters the
    schedule: ``True`` (default) measures the carried prices' dual
    infeasibility and runs every phase whose eps is at or below it (near-
    equilibrium prices still take only the final phase; drifted prices get
    the mid-schedule phases they actually need); ``False`` keeps the fixed
    legacy behaviour of always jumping straight to the final small-eps phase.
    Cold (all-zero-price) instances always run the full ramp either way.
    """

    n_phases: int = 4
    eps_start_div: float = 8.0
    eps_end_mul: float = 4.0
    max_rounds: int = 0  # 0 -> auto (50 * n + 1000)
    fixed_rounds: int = 0
    adaptive_reentry: bool = True


# ---------------------------------------------------------------------------
# Batched engine
# ---------------------------------------------------------------------------

def _top2_batched(values: jnp.ndarray):
    """Last-axis (best value, best index, second value) of a (..., n) array."""
    j1 = jnp.argmax(values, axis=-1).astype(jnp.int32)
    v1 = jnp.take_along_axis(values, j1[..., None], axis=-1)[..., 0]
    col = jax.lax.broadcasted_iota(jnp.int32, values.shape, values.ndim - 1)
    v2 = jnp.max(jnp.where(col == j1[..., None], _NEG, values), axis=-1)
    return v1, j1, v2


def _auction_phase(top2_fn, prices: jnp.ndarray, eps: jnp.ndarray,
                   max_rounds: int, fixed_rounds: int = 0,
                   skip: jnp.ndarray | None = None,
                   seed_top2=None, resident=None,
                   ) -> tuple[jnp.ndarray, jnp.ndarray, jnp.ndarray]:
    """One epsilon phase of batched Jacobi forward auction (maximization).

    ``top2_fn(prices)`` returns the per-row ``(v1, j1, v2)`` of the reduced
    value matrix ``value[b, i, j] = cost[b, i, j] - prices[b, j]``, each
    (B, n) -- the *bidding round reduction*, pluggable so the dense path and
    the fused matrix-free kernel path share one engine.  Prices/eps are
    (B, n) / (B,).  Returns (row_to_col, prices, rounds): ``rounds`` is the
    phase's executed round count (the ``it`` counter the loop carries; the
    solver telemetry source).  All rows start unassigned; prices persist
    across phases (standard eps-scaling).  A fully assigned instance places
    no bids, so the round update is a no-op for it while the rest of the
    batch keeps iterating (per-instance convergence masking).

    ``skip`` ((B,) bool) marks instances that sit this phase out entirely:
    their rows start pre-assigned (identity), so by the masking above they
    never bid and their prices pass through untouched -- the warm-start path
    uses this to run only the phases at or below its measured re-entry eps
    per warm instance while cold instances in the same stack keep the full
    ramp.

    ``seed_top2`` optionally supplies the first round's ``(v1, j1, v2)``
    reduction, precomputed at the *incoming* prices -- the warm path's
    infeasibility probe is exactly that reduction, so threading it here
    makes the probe free (it becomes round one).  The values are what the
    round would compute itself, so results are unchanged.

    ``resident`` (from :func:`repro.kernels.ops.resident_auction`) runs the
    round loop in one Pallas kernel on the VMEM-resident cost block instead;
    the round is the same arithmetic, so the results and the round count
    are bitwise those of the loop below (a seeded first round runs there
    even where every row is pre-assigned, as it does here).
    """
    B, n = prices.shape
    cols = jnp.broadcast_to(jnp.arange(n, dtype=jnp.int32), (B, n))
    assign0 = jnp.full((B, n), -1, jnp.int32)
    if skip is not None:
        # pre-assigned identity: no bids, a fixed point of the round update
        assign0 = jnp.where(skip[:, None], cols, assign0)
    if resident is not None:
        return resident(prices, eps, assign0, max_rounds,
                        int(seed_top2 is not None))
    rows = cols
    barange = jnp.arange(B)[:, None]

    def cond(state):
        assign, _prices, it = state
        return jnp.logical_and(jnp.any(assign < 0), it < max_rounds)

    def body_with(state, top2):
        assign, prices, it = state
        unassigned = assign < 0
        v1, j1, v2 = top2
        # Bid: raise the price of the favourite object past the point of
        # indifference with the runner-up, plus eps.  Using the identity
        # cost[b, i, j1] = v1 + prices[b, j1] keeps the phase matrix-free.
        bids = v1 + jnp.take_along_axis(prices, j1, axis=1) - v2 + eps[:, None]
        bid_val = jnp.where(unassigned, bids, _NEG)
        # Per-object best bid (scatter-max) and winning row (min row index
        # among rows achieving the best bid -- deterministic tie-break).
        best = jnp.full((B, n), _NEG, bids.dtype).at[barange, j1].max(bid_val)
        is_best = jnp.logical_and(
            unassigned, bid_val >= jnp.take_along_axis(best, j1, axis=1))
        cand = jnp.where(is_best, rows, n)
        winner = jnp.full((B, n), n, jnp.int32).at[barange, j1].min(cand)
        got_bid = winner < n
        # Rows whose object was just outbid become unassigned.  (They were
        # assigned, hence did not bid, hence cannot also be winners.)
        safe_assign = jnp.where(assign >= 0, assign, 0)
        lost = jnp.logical_and(
            assign >= 0,
            jnp.logical_and(
                jnp.take_along_axis(got_bid, safe_assign, axis=1),
                jnp.take_along_axis(winner, safe_assign, axis=1) != rows))
        assign = jnp.where(lost, -1, assign)
        # Winners take their objects at the winning bid.
        winner_safe = jnp.where(got_bid, winner, n)
        assign = assign.at[barange, winner_safe].set(cols, mode="drop")
        prices = jnp.where(got_bid, best, prices)
        return assign, prices, it + 1

    def body(state):
        return body_with(state, top2_fn(state[1]))

    state0 = (assign0, prices, jnp.int32(0))
    rounds = fixed_rounds
    if seed_top2 is not None:
        # round one, with the caller's precomputed reduction (same values
        # the round would compute; identical results, one reduction saved)
        state0 = body_with(state0, seed_top2)
        rounds = max(fixed_rounds - 1, 0)
    if fixed_rounds:
        # converged state is a fixed point of body (no bids -> no updates)
        def scan_body(state, _):
            return body(state), None
        state, _ = jax.lax.scan(scan_body, state0, None, length=rounds)
        return state
    return jax.lax.while_loop(cond, body, state0)


def _eps_schedule(span: jnp.ndarray, n: int, config: AuctionConfig):
    """(B,) span -> (n_phases, B) geometric epsilon schedule."""
    eps_hi = span / config.eps_start_div
    eps_lo = span / (config.eps_end_mul * n)
    n_phases = max(int(config.n_phases), 1)
    if n_phases > 1:
        ratio = (eps_lo / eps_hi) ** (1.0 / (n_phases - 1))
        steps = jnp.arange(n_phases, dtype=jnp.float32)
        return eps_hi[None, :] * ratio[None, :] ** steps[:, None]
    return eps_lo[None, :]


def _run_phases(top2_fn, eps_sched: jnp.ndarray, n: int,
                config: AuctionConfig,
                prices0: jnp.ndarray | None = None,
                return_stats: bool = False, resident=None,
                ) -> tuple[jnp.ndarray, jnp.ndarray]:
    """Run the eps-scaling schedule; returns (assignment, final prices).

    ``resident`` is the phase implementation on the VMEM-resident kernel
    (see :func:`_auction_phase`); ``None`` runs the XLA round loop.

    ``return_stats=True`` appends a solver telemetry pytree -- the numbers
    the loops already compute, surfaced instead of discarded, so the stats
    path costs no extra traced work beyond stacking them:

    * ``rounds``  (n_phases,) int32 -- executed bidding rounds per phase
      (the phase while-loop's own counter; a skipped warm phase exits on
      its first predicate check and reports 0/1 rounds).
    * ``eps``     (n_phases, B)     -- the geometric epsilon schedule.
    * ``warm``    (B,) bool         -- instances that entered with carried
      (nonzero) prices.
    * ``reentry`` (B,) float32      -- the measured re-entry epsilon per
      instance (-inf on the legacy fixed shortcut; 0 on the cold path).
    * ``skipped`` (n_phases, B) bool -- which phases each instance sat out.

    ``prices0`` warm-starts the solve ((B, n); ``None`` or all-zeros is the
    cold path).  Epsilon scaling exists to tame the round count from
    *uninformed* prices -- its early large-eps phases actively re-scramble
    an already-converged price equilibrium (measured: warm-starting the full
    schedule saves nothing, and re-running *every* phase at the final small
    epsilon costs almost as much as the cold ramp).  So the price-carrying
    path skips phases **per instance**: an instance whose incoming prices
    are all zero (the engine's cold-start sentinel) runs the full ramp,
    bit-identical to ``prices0=None``; an instance with carried (nonzero)
    duals *re-enters the schedule adaptively* -- one probe bidding round at
    the carried prices measures its dual infeasibility (the largest
    value gap a row stands to lose where several rows contest the same
    object; zero at a clean equilibrium), and the instance sits out every
    phase whose eps exceeds that measured infeasibility (rows start
    pre-assigned, placing no bids -- the same per-instance convergence
    masking that lets converged instances free-wheel).  Near-equilibrium
    prices therefore still run only the final small-eps phase (the fixed
    legacy shortcut, ``config.adaptive_reentry=False`` forces it), while
    prices carried across drifted data re-enter mid-schedule and converge
    in far fewer rounds than the final phase alone would need from that
    distance.  The last phase always runs, so the ``n * eps_lo`` optimality
    bound of the full schedule is kept either way.  The final prices are the
    dual state a repeated caller threads into its next same-shape solve.
    """
    B = eps_sched.shape[1]
    n_phases = eps_sched.shape[0]
    max_rounds = config.max_rounds or (50 * n + 1000)

    def run_phase(prices, eps, skip=None, seed_top2=None):
        return _auction_phase(top2_fn, prices, eps, max_rounds,
                              config.fixed_rounds, skip=skip,
                              seed_top2=seed_top2, resident=resident)

    def done(assign, prices, stats):
        # Safety net: if the round cap was hit, columns may be unassigned;
        # patch them greedily so the result is always a permutation.
        assign = _repair_permutation(assign)
        return (assign, prices, stats) if return_stats else (assign, prices)

    if prices0 is None:
        def phase(prices, eps):
            assign, prices, it = run_phase(prices, eps)
            return prices, (assign, it)

        prices, (assigns, rounds) = jax.lax.scan(
            phase, jnp.zeros((B, n), jnp.float32), eps_sched)
        stats = {"rounds": rounds.astype(jnp.int32), "eps": eps_sched,
                 "warm": jnp.zeros((B,), bool),
                 "reentry": jnp.zeros((B,), jnp.float32),
                 "skipped": jnp.zeros((n_phases, B), bool)}
        return done(assigns[-1], prices, stats)

    prices0 = prices0.astype(jnp.float32)
    is_warm = jnp.any(prices0 != 0.0, axis=1)          # (B,) per instance
    is_last = jnp.arange(n_phases) == n_phases - 1
    if config.adaptive_reentry:
        # Probe reduction at the carried prices: rows whose favourite object
        # is contested (demanded by >1 rows) must either outbid or fall back
        # to their runner-up, so max contested (v1 - v2) tracks the price
        # movement still needed -- the eps scale worth re-entering at.  The
        # reduction is fed back in as the first executed round's top-2
        # (seed_top2), so the probe costs nothing extra.
        probe = top2_fn(prices0)
        v1, j1, v2 = probe
        barange = jnp.arange(B)[:, None]
        demand = jnp.zeros((B, n), jnp.float32).at[barange, j1].add(1.0)
        contested = jnp.take_along_axis(demand, j1, axis=1) > 1.0
        infeas = jnp.max(jnp.where(contested, v1 - v2, 0.0), axis=1)
        reentry = jnp.clip(infeas / _REENTRY_SLACK, eps_sched[-1],
                           eps_sched[0])
    else:
        # legacy fixed shortcut: warm instances skip all but the last phase
        probe = None
        reentry = jnp.full((B,), -jnp.inf)
    skipped = jnp.logical_and(
        is_warm[None, :],
        jnp.logical_and(jnp.logical_not(is_last)[:, None],
                        eps_sched > reentry[None, :]))

    def phase_p(prices, inp):
        eps, skip = inp
        assign, prices, it = run_phase(prices, eps, skip=skip)
        return prices, (assign, it)

    # Phase 1 unrolled so it can consume the probe reduction (every instance
    # still holds the incoming prices there); the remaining phases scan.  A
    # skipped phase's while-loop exits on its first predicate check (all
    # rows pre-assigned), so the steady-state engine case -- every instance
    # warm at equilibrium, only the final phase live -- costs the same as
    # the old jump-straight-to-the-last-phase shortcut (measured slightly
    # less: a branchless scan of empty phases beats a lax.cond dispatch).
    assign, prices, it0 = run_phase(prices0, eps_sched[0], skip=skipped[0],
                                    seed_top2=probe)
    rounds = it0[None]
    if n_phases > 1:
        prices, (assigns, its) = jax.lax.scan(
            phase_p, prices, (eps_sched[1:], skipped[1:]))
        assign = assigns[-1]
        rounds = jnp.concatenate([rounds, its])
    stats = {"rounds": rounds.astype(jnp.int32), "eps": eps_sched,
             "warm": is_warm, "reentry": reentry, "skipped": skipped}
    return done(assign, prices, stats)


def _solve_stack(cost: jnp.ndarray, config: AuctionConfig,
                 prices0: jnp.ndarray | None = None,
                 return_stats: bool = False,
                 ) -> tuple[jnp.ndarray, jnp.ndarray]:
    """(B, n, n) -> ((B, n) assignment, (B, n) prices); the dense engine."""
    B, n, _ = cost.shape
    finite = jnp.where(cost <= _NEG / 2, 0.0, cost)
    span = jnp.maximum(jnp.max(finite, axis=(1, 2))
                       - jnp.min(finite, axis=(1, 2)), 1e-6)

    def top2_fn(prices):
        return _top2_batched(cost - prices[:, None, :])

    resident = None
    if not config.fixed_rounds:  # the dry-run's scan keeps the XLA loop
        from repro.kernels import ops
        resident = ops.resident_auction(cost)
    return _run_phases(top2_fn, _eps_schedule(span, n, config), n, config,
                       prices0, return_stats=return_stats,
                       resident=resident)


def _zero_stats(B: int, config: AuctionConfig) -> dict:
    """The telemetry pytree for solves that run no phases (n == 1)."""
    p = max(int(config.n_phases), 1)
    return {"rounds": jnp.zeros((p,), jnp.int32),
            "eps": jnp.zeros((p, B), jnp.float32),
            "warm": jnp.zeros((B,), bool),
            "reentry": jnp.zeros((B,), jnp.float32),
            "skipped": jnp.zeros((p, B), bool)}


def _squeeze_stats(stats: dict) -> dict:
    """Drop the B axis for single-instance (squeezed) solves."""
    return {"rounds": stats["rounds"], "eps": stats["eps"][:, 0],
            "warm": stats["warm"][0], "reentry": stats["reentry"][0],
            "skipped": stats["skipped"][:, 0]}


@functools.partial(jax.jit,
                   static_argnames=("config", "return_prices",
                                    "return_stats"))
def auction_solve(cost: jnp.ndarray,
                  config: AuctionConfig = AuctionConfig(), *,
                  prices: jnp.ndarray | None = None,
                  return_prices: bool = False,
                  return_stats: bool = False) -> jnp.ndarray:
    """eps-optimal max-cost assignment; single matrix or batched stack.

    ``(n, n)`` input returns ``row_to_col`` (n,) int32; a stacked
    ``(B, n, n)`` input returns (B, n), solved in ONE fused round loop with
    per-instance convergence masking -- instance b's result is identical to
    ``auction_solve(cost[b])``.  Safe under ``vmap`` and inside ``lax.scan``.
    Rectangular problems must be padded by the caller (constant-cost dummy
    rows are neutral: any column suits them; a padded instance converges
    early and free-wheels at its fixed point while the rest finish).

    ``prices`` warm-starts the epsilon schedule from a carried price vector
    ((n,) / (B, n); ``None`` = zeros, the cold path -- bit-identical to the
    pre-warm-start behaviour).  ``return_prices=True`` additionally returns
    the final prices (the shape of the assignment), which is what the
    registry's price-carrying ``solve`` signature exposes.
    ``return_stats=True`` returns ``(assignment, prices, stats)`` where
    ``stats`` is the solver telemetry pytree of :func:`_run_phases` (rounds
    per eps phase, the eps schedule, warm re-entry decisions); the
    assignment and prices are identical to the plain call.
    """
    cost = cost.astype(jnp.float32)
    in_shape = cost.shape
    if cost.ndim not in (2, 3):
        raise ValueError(f"cost must be (n, n) or (B, n, n), got {in_shape}")
    squeeze = cost.ndim == 2
    if squeeze:
        cost = cost[None]
        prices = None if prices is None else prices[None]
    B, n, n2 = cost.shape
    if n != n2:
        raise ValueError(f"cost must be square, got {in_shape}")
    stats = None
    if n == 1:
        out = jnp.zeros((B, 1), jnp.int32)
        p_out = (jnp.zeros((B, 1), jnp.float32) if prices is None
                 else prices.astype(jnp.float32))
        if return_stats:
            stats = _zero_stats(B, config)
    elif return_stats:
        out, p_out, stats = _solve_stack(cost, config, prices,
                                         return_stats=True)
    else:
        out, p_out = _solve_stack(cost, config, prices)
    if return_stats:
        if squeeze:
            return out[0], p_out[0], _squeeze_stats(stats)
        return out, p_out, stats
    if return_prices:
        return (out[0], p_out[0]) if squeeze else (out, p_out)
    return out[0] if squeeze else out


@functools.partial(jax.jit,
                   static_argnames=("config", "force", "return_prices",
                                    "return_stats"))
def auction_solve_factored(x: jnp.ndarray, c: jnp.ndarray, *,
                           is_real: jnp.ndarray | None = None,
                           config: AuctionConfig = AuctionConfig(),
                           force: str | None = None,
                           prices: jnp.ndarray | None = None,
                           return_prices: bool = False,
                           return_stats: bool = False) -> jnp.ndarray:
    """Matrix-free auction on ``cost[i, j] = -2 x_i . c_j + ||c_j||^2``.

    This is the ABA batch-to-centroid LAP with the row-constant ``||x||^2``
    dropped.  Each bidding round's top-2 reduction runs through the fused
    ``kernels.ops.bid_top2`` dispatch -- the Pallas kernel on TPU (column
    tiles streamed through VMEM, O(k) output), ``interpret=True`` on CPU --
    so the (k, k) value matrix is never re-materialized per round.  Only the
    one-off span estimate for the eps schedule touches a dense product.

    A first-class registry backend (``"auction_fused"``'s ``factored``
    path): it takes a single ``(k, d) x (k, d)`` problem OR the ABA core's
    stacked ``(G, k, d) x (G, k, d)`` batch (per-group centroids; the
    bidding reduction vmaps the kernel, which on TPU is one extra grid dim).
    ``is_real`` marks dummy rows whose cost is the neutral constant 0,
    matching the dense masked path in :func:`repro.core.aba.aba_core`.
    ``prices`` / ``return_prices`` carry the auction's dual state exactly as
    in :func:`auction_solve` (warm start in, final prices out);
    ``return_stats`` appends the solver telemetry pytree, also as there.
    Returns ``row_to_col`` (k,) / (G, k) int32.
    """
    from repro.kernels.ops import bid_top2

    if x.shape[-2] != c.shape[-2]:
        raise ValueError(
            f"LAP must be square: {x.shape[-2]} != {c.shape[-2]}")
    squeeze = x.ndim == 2
    if squeeze:
        x, c = x[None], c[None]
        is_real = None if is_real is None else is_real[None]
        prices = None if prices is None else prices[None]
    G, n, _ = x.shape
    if n == 1:
        out = jnp.zeros((G, 1), jnp.int32)
        if return_prices or return_stats:
            p_out = (jnp.zeros((G, 1), jnp.float32) if prices is None
                     else prices.astype(jnp.float32))
            if return_stats:
                stats = _zero_stats(G, config)
                if squeeze:
                    return out[0], p_out[0], _squeeze_stats(stats)
                return out, p_out, stats
            return (out[0], p_out[0]) if squeeze else (out, p_out)
        return out[0] if squeeze else out
    x = x.astype(jnp.float32)
    c = c.astype(jnp.float32)
    cn = jnp.sum(c * c, axis=-1)  # (G, n)

    # one-off span for the eps schedule (fused per-row extrema: the max is
    # bid_top2 at zero prices; the min is the max of the negated values,
    # reachable with prices = 2 * ||c||^2 and x -> -x)
    hi_v1, _, _ = bid_top2(x, c, jnp.zeros((G, n), jnp.float32), force=force)
    lo_v1, _, _ = bid_top2(-x, c, 2.0 * cn, force=force)
    if is_real is not None:
        any_dummy = jnp.any(~is_real, axis=1)
        hi = jnp.max(jnp.where(is_real, hi_v1, _NEG), axis=1)
        lo = -jnp.max(jnp.where(is_real, lo_v1, _NEG), axis=1)
        hi = jnp.where(any_dummy, jnp.maximum(hi, 0.0), hi)
        lo = jnp.where(any_dummy, jnp.minimum(lo, 0.0), lo)
    else:
        hi = jnp.max(hi_v1, axis=1)
        lo = -jnp.max(lo_v1, axis=1)
    span = jnp.maximum(hi - lo, 1e-6)  # (G,)

    def top2_fn(prices):
        v1, j1, v2 = bid_top2(x, c, prices, force=force)
        if is_real is not None:
            # dummy rows see the constant-0 cost row: value = -prices, the
            # same vector for every dummy row of a group, so the per-group
            # (G,) top-2 broadcasts across the row axis
            dv1, dj1, dv2 = _top2_batched(-prices)
            v1 = jnp.where(is_real, v1, dv1[:, None])
            j1 = jnp.where(is_real, j1, dj1[:, None])
            v2 = jnp.where(is_real, v2, dv2[:, None])
        return v1, j1, v2

    if return_stats:
        out, p_out, stats = _run_phases(
            top2_fn, _eps_schedule(span, n, config), n, config, prices,
            return_stats=True)
        if squeeze:
            return out[0], p_out[0], _squeeze_stats(stats)
        return out, p_out, stats
    out, p_out = _run_phases(top2_fn, _eps_schedule(span, n, config), n,
                             config, prices)
    if return_prices:
        return (out[0], p_out[0]) if squeeze else (out, p_out)
    return out[0] if squeeze else out


def solve_restricted_slots(cost: jnp.ndarray, mandatory: jnp.ndarray, *,
                           solver: str = "auction",
                           config: AuctionConfig = AuctionConfig(),
                           prices: jnp.ndarray | None = None,
                           ) -> tuple[jnp.ndarray, jnp.ndarray]:
    """Frozen-price restricted assignment of m arriving rows over T slots.

    The delta-update subsystem's LAP (``repro.incremental``): ``cost`` is
    the (m, T) value of placing each arriving row into each open capacity
    slot (m <= T), ``mandatory`` ((T,) bool) marks slots that MUST take a
    real row (clusters below the balance floor).  The problem is squared
    with ``T - m`` neutral dummy rows (constant cost 0, the ``aba_core``
    dummy convention) barred from mandatory slots by a penalty scaled to
    the real cost span: with ``pen = -(4 * span + 1)`` an exchange argument
    against the schedule's ``T * eps_lo <= span_solver / 4`` optimality
    slack shows an eps-optimal assignment never takes a penalized pair when
    a feasible completion exists (the categorical ``_MASK_COST = -1e9``
    would instead blow up the span-derived epsilon schedule and with it the
    placement quality).

    ``prices`` ((T,) float32) warm-starts the solve from carried per-slot
    duals; nonzero prices engage ``_run_phases``' adaptive re-entry probe,
    so near-equilibrium slots sit out all but the final epsilon phase while
    contested slots re-enter mid-schedule -- "all other prices frozen" falls
    out of the probe rather than an explicit mask.

    Returns ``(slots, slot_prices)``: each real row's slot ((m,) int32) and
    the final duals ((T,) float32).  Jit/scan-safe for auction backends.
    """
    cost = jnp.asarray(cost, jnp.float32)
    if cost.ndim != 2:
        raise ValueError(f"cost must be (m, T), got {cost.shape}")
    m, T = cost.shape
    if m > T:
        raise ValueError(f"m={m} arriving rows exceed T={T} open slots")
    solver_obj = get_solver(solver)
    if m == T:
        square = cost
    else:
        # dummy rows see cost 0, so the span must cover 0 like the factored
        # path's any_dummy branch does
        hi = jnp.maximum(jnp.max(cost), 0.0)
        lo = jnp.minimum(jnp.min(cost), 0.0)
        pen = -(4.0 * jnp.maximum(hi - lo, 1e-6) + 1.0)
        dummy = jnp.where(jnp.asarray(mandatory, jnp.bool_), pen, 0.0)
        square = jnp.concatenate(
            [cost, jnp.broadcast_to(dummy, (T - m, T))], axis=0)
    assign, p_out = solver_obj.solve(square, config, prices)
    return assign[:m].astype(jnp.int32), p_out


def _repair_permutation(assign: jnp.ndarray) -> jnp.ndarray:
    """Fill any ``-1`` rows with the unused columns (order-preserving)."""
    B, n = assign.shape
    barange = jnp.arange(B)[:, None]
    safe = jnp.where(assign >= 0, assign, 0)
    used = jnp.zeros((B, n), jnp.int32).at[barange, safe].add(
        (assign >= 0).astype(jnp.int32)) > 0
    free_cols = jnp.argsort(used, axis=1, stable=True)  # unused columns first
    need = assign < 0
    slot = jnp.cumsum(need, axis=1) - 1  # index into free_cols per needy row
    fill = jnp.take_along_axis(free_cols, jnp.maximum(slot, 0), axis=1)
    return jnp.where(need, fill, assign).astype(jnp.int32)


@jax.jit
def greedy_solve(cost: jnp.ndarray) -> jnp.ndarray:
    """Vectorized global-greedy max assignment: n rounds of masked argmax."""
    n = cost.shape[0]
    def body(_i, state):
        c, assign = state
        flat = jnp.argmax(c)
        r, col = flat // n, flat % n
        assign = assign.at[r].set(col.astype(jnp.int32))
        c = c.at[r, :].set(_NEG).at[:, col].set(_NEG)
        return c, assign
    _c, assign = jax.lax.fori_loop(
        0, n, body, (cost.astype(jnp.float32), jnp.full((n,), -1, jnp.int32)))
    return assign


def scipy_solve(cost: np.ndarray) -> np.ndarray:
    """Exact max-cost assignment (Hungarian) -- host-side oracle."""
    from scipy.optimize import linear_sum_assignment

    rows, cols = linear_sum_assignment(np.asarray(cost), maximize=True)
    out = np.empty(cost.shape[0], dtype=np.int32)
    out[rows] = cols
    return out


def assignment_value(cost: np.ndarray, row_to_col: np.ndarray) -> float:
    return float(np.asarray(cost)[np.arange(len(row_to_col)), row_to_col].sum())


# ---------------------------------------------------------------------------
# Solver registry
# ---------------------------------------------------------------------------

class Solver(NamedTuple):
    """A registered LAP backend for the ABA core.

    ``solve(cost, config, prices=None)`` takes a ``(B, n, n)`` stack (or a
    single ``(n, n)`` matrix) plus an optional warm-start price vector
    ((B, n) / (n,)) and returns ``(row_to_col, prices)`` of shapes
    ``(B, n)`` / ``(n,)``, MAXIMIZING total cost; it must be jit/scan-safe
    (host solvers wrap themselves in ``jax.pure_callback``).  ``prices=None``
    is the cold start; backends without a price concept (greedy, Hungarian)
    return the incoming prices unchanged (zeros when cold) so the engine's
    state threading stays a no-op for them.  ``factored`` is the optional
    matrix-free path ``factored(x, c, is_real=..., config=..., prices=...)``
    -> ``(row_to_col, prices)`` used by the ABA core whenever the cost
    factors as ``-2 x.c^T + ||c||^2`` (no categorical mask); it must accept
    both ``(n, d)`` and the core's stacked ``(G, n, d)`` inputs (the
    fused-kernel auction does).

    Backends registered with the legacy price-less signature
    ``solve(cost, config) -> row_to_col`` are auto-wrapped in a pass-through
    shim by :func:`register_solver` (with a ``DeprecationWarning``).

    ``host_callback`` marks backends that round-trip to the host from inside
    the traced computation (``jax.pure_callback`` -- e.g. the scipy
    Hungarian).  Such a solve occupies the host thread while it "runs on
    device", so dispatching it asynchronously buys no overlap and the
    engine's non-blocking path (``AnticlusterEngine.dispatch_repartition``,
    ``repro.train.pipeline``) refuses it up front and falls back to the
    synchronous route.

    ``solve_stats`` / ``factored_stats`` are the optional telemetry twins:
    the same signatures as ``solve`` / ``factored`` but returning
    ``(row_to_col, prices, stats)`` where ``stats`` is the auction telemetry
    pytree (see ``_run_phases``).  Backends without internals worth
    reporting leave them ``None`` and the engine's
    ``AnticlusterSpec(telemetry=True)`` path statically degrades to no
    telemetry for them -- never a traced-op cost on anyone's default path.
    """

    solve: Callable
    factored: Callable | None = None
    host_callback: bool = False
    solve_stats: Callable | None = None
    factored_stats: Callable | None = None


_REGISTRY: dict[str, Solver] = {}


def _accepts_prices(fn: Callable) -> bool:
    try:
        return "prices" in inspect.signature(fn).parameters
    except (TypeError, ValueError):  # C callables etc.: assume legacy
        return False


def _prices_or_zeros(shape_src: jnp.ndarray, prices):
    """Pass-through prices for price-less backends ((..., n) from (..., n, n))."""
    if prices is not None:
        return jnp.asarray(prices, jnp.float32)
    return jnp.zeros(shape_src.shape[:-1], jnp.float32)


def _legacy_solve_shim(solve: Callable) -> Callable:
    @functools.wraps(solve)
    def shim(cost, config=AuctionConfig(), prices=None):
        return solve(cost, config), _prices_or_zeros(cost, prices)
    return shim


def _legacy_factored_shim(factored: Callable) -> Callable:
    @functools.wraps(factored)
    def shim(x, c, *, is_real=None, config=AuctionConfig(), prices=None):
        out = factored(x, c, is_real=is_real, config=config)
        if prices is None:
            prices = jnp.zeros(c.shape[:-1], jnp.float32)  # (G, n) / (n,)
        return out, jnp.asarray(prices, jnp.float32)
    return shim


def register_solver(name: str, solve: Callable, *,
                    factored: Callable | None = None,
                    host_callback: bool = False,
                    solve_stats: Callable | None = None,
                    factored_stats: Callable | None = None,
                    overwrite: bool = False) -> Solver:
    """Register a LAP backend under ``name`` (see :class:`Solver`).

    The canonical signature is price-carrying:
    ``solve(cost, config, prices=None) -> (row_to_col, prices)``.  A solver
    whose signature has no ``prices`` parameter is treated as the legacy
    price-less form ``solve(cost, config) -> row_to_col`` and wrapped in a
    pass-through shim (incoming prices are returned unchanged, zeros when
    cold) with a ``DeprecationWarning`` -- warm starts are a no-op for such
    backends but everything else keeps working.

    Pass ``host_callback=True`` for backends that execute on the host via
    ``jax.pure_callback``: the engine's async dispatch path refuses them
    (there is nothing to overlap with -- the "device" work IS host work).

    The ABA core resolves ``name`` at *trace* time (solver names are static
    jit arguments), so ``overwrite=True`` does not reach already-compiled
    core traces -- re-registering an existing name changes future traces
    only.  Register under a fresh name (or clear jax caches) when comparing
    backends within one process.
    """
    if not overwrite and name in _REGISTRY:
        raise ValueError(f"solver {name!r} already registered "
                         f"(pass overwrite=True to replace it)")
    if not _accepts_prices(solve):
        warnings.warn(
            f"solver {name!r} uses the deprecated price-less signature "
            "solve(cost, config); wrapping it in a pass-through shim. "
            "Migrate to solve(cost, config, prices=None) -> "
            "(assignment, prices) to participate in warm starts.",
            DeprecationWarning, stacklevel=2)
        solve = _legacy_solve_shim(solve)
    if factored is not None and not _accepts_prices(factored):
        warnings.warn(
            f"solver {name!r}: factored path uses the deprecated price-less "
            "signature; wrapping it in a pass-through shim.",
            DeprecationWarning, stacklevel=2)
        factored = _legacy_factored_shim(factored)
    solver = Solver(solve=solve, factored=factored,
                    host_callback=host_callback,
                    solve_stats=solve_stats,
                    factored_stats=factored_stats)
    _REGISTRY[name] = solver
    return solver


def get_solver(name: str) -> Solver:
    if name not in _REGISTRY:
        raise KeyError(f"unknown solver {name!r}; registered: "
                       f"{available_solvers()}")
    return _REGISTRY[name]


def available_solvers() -> tuple[str, ...]:
    """Sorted names of every registered LAP backend.

    Every listed backend satisfies the price-carrying :class:`Solver`
    contract (legacy registrations are shimmed at registration time), so
    each is usable both by one-shot ``anticluster()`` calls and as the
    warm-started engine inside ``AnticlusterEngine.repartition``.
    """
    return tuple(sorted(_REGISTRY))


def _auction_solve_p(cost: jnp.ndarray,
                     config: AuctionConfig = AuctionConfig(),
                     prices: jnp.ndarray | None = None):
    """Registry entry: price-carrying wrapper over ``auction_solve``."""
    return auction_solve(cost, config, prices=prices, return_prices=True)


def _auction_factored_p(x: jnp.ndarray, c: jnp.ndarray, *,
                        is_real: jnp.ndarray | None = None,
                        config: AuctionConfig = AuctionConfig(),
                        prices: jnp.ndarray | None = None):
    """Registry entry: price-carrying wrapper over the matrix-free auction."""
    return auction_solve_factored(x, c, is_real=is_real, config=config,
                                  prices=prices, return_prices=True)


def _auction_solve_stats(cost: jnp.ndarray,
                         config: AuctionConfig = AuctionConfig(),
                         prices: jnp.ndarray | None = None):
    """Registry entry: telemetry twin of ``_auction_solve_p``."""
    return auction_solve(cost, config, prices=prices, return_stats=True)


def _auction_factored_stats(x: jnp.ndarray, c: jnp.ndarray, *,
                            is_real: jnp.ndarray | None = None,
                            config: AuctionConfig = AuctionConfig(),
                            prices: jnp.ndarray | None = None):
    """Registry entry: telemetry twin of ``_auction_factored_p``."""
    return auction_solve_factored(x, c, is_real=is_real, config=config,
                                  prices=prices, return_stats=True)


def _greedy_stack(cost: jnp.ndarray,
                  config: AuctionConfig = AuctionConfig(),
                  prices: jnp.ndarray | None = None):
    del config  # greedy has no tuning knobs
    if cost.ndim == 3:
        out = jax.vmap(greedy_solve)(cost)
    else:
        out = greedy_solve(cost)
    return out, _prices_or_zeros(cost, prices)  # price-less: pass-through


def _scipy_host_stack(cost: np.ndarray) -> np.ndarray:
    return np.stack([scipy_solve(c) for c in cost])


def scipy_solve_jax(cost: jnp.ndarray,
                    config: AuctionConfig = AuctionConfig(),
                    prices: jnp.ndarray | None = None):
    """Exact Hungarian as a jit/scan-safe backend via ``pure_callback``.

    The oracle solver, usable anywhere ``auction_solve`` is: each stack
    instance round-trips to the host, so it is CPU-speed by construction --
    the registry entry exists for exactness checks and tiny problems.
    Hungarian has no dual price state worth carrying, so the warm-start
    ``prices`` are passed through unchanged (zeros when cold).
    """
    del config
    cost = jnp.asarray(cost, jnp.float32)
    squeeze = cost.ndim == 2
    stack = cost[None] if squeeze else cost
    out = jax.pure_callback(
        _scipy_host_stack,
        jax.ShapeDtypeStruct(stack.shape[:2], jnp.int32),
        stack, vmap_method="sequential")
    return out[0] if squeeze else out, _prices_or_zeros(cost, prices)


register_solver("auction", _auction_solve_p,
                solve_stats=_auction_solve_stats)
register_solver("auction_fused", _auction_solve_p,
                factored=_auction_factored_p,
                solve_stats=_auction_solve_stats,
                factored_stats=_auction_factored_stats)
register_solver("greedy", _greedy_stack)
register_solver("scipy", scipy_solve_jax, host_callback=True)
