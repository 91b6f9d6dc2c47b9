"""The Assignment-Based Anticlustering algorithm (paper Section 4).

JAX implementation notes
------------------------
* ONE rank-polymorphic masked core (:func:`aba_core`) carries every regime:
  it takes a ``(G, M, D)`` stack of padded subproblems and the flat case is
  simply the ``G = 1`` specialization.  The centrality sort, the Section
  4.2/4.3 rearrangements, the pad-to-full-batches step and the Algorithm 1
  scan therefore exist exactly once; ``aba`` / ``aba_batched`` are thin
  deprecated shims over it (use :func:`repro.anticluster.anticluster`).
* The batch loop (Algorithm 1) is a ``lax.scan`` carrying the anticluster
  centroids and per-cluster counts.  It is inherently sequential -- each LAP
  depends on the centroids updated by the previous batch -- so parallelism
  comes from (a) the dense vectorized work inside one step (cost matrix +
  auction rounds, batched across the G subproblems) and (b) the hierarchical
  decomposition (Section 4.4), which feeds group stacks through this same
  core.
* The LAP input drops the row-constant ``||x_j||^2`` term: adding a constant
  per row never changes the optimal assignment, so the cost matrix is just
  ``-2 x . mu^T + ||mu||^2`` -- one matmul (MXU) plus a bias.
* The LAP backend comes from the solver registry
  (:func:`repro.core.assignment.get_solver`); every backend solves the whole
  ``(G, k, k)`` stack per scan step in one call.
* The Section 4.2 interleave rearrangement is a *static* permutation of sorted
  positions (depends only on M, K) and is precomputed in numpy at trace time.
* The Section 4.3 categorical rearrangement depends on data; it is expressed
  as a single lexicographic sort key so it stays jit/vmap-compatible, and it
  is batched over the group axis (hierarchical levels keep stratifying).
* ``valid_mask`` supports padded subproblems (hierarchical level >= 2 gathers
  groups whose sizes differ by one into a fixed-shape batch).
"""

from __future__ import annotations

import functools
import warnings
from typing import Literal

import jax
import jax.numpy as jnp
import numpy as np

from repro.core.assignment import AuctionConfig, get_solver
from repro.kernels.ops import row_gatherer

_MASK_COST = -1e9  # categorical upper-bound mask (paper 4.3)

Variant = Literal["auto", "base", "interleave"]


def _deprecated(old: str, new: str):
    warnings.warn(
        f"repro.core.{old} is deprecated; use {new} "
        "(labels are guaranteed identical)",
        DeprecationWarning, stacklevel=3)


# ---------------------------------------------------------------------------
# Static rearrangements
# ---------------------------------------------------------------------------

def interleave_permutation(n: int, k: int) -> np.ndarray:
    """Section 4.2 rearrangement of *positions* 0..n-1 of the sorted list.

    Splits the sorted list into k sublists (short ones first when k does not
    divide n) and round-robins through them; the n - floor(n/k)*k leftovers
    (one per long sublist, nearest the global centroid) go to the end.
    """
    q, r = divmod(n, k)
    if q == 0:
        return np.arange(n)
    n_short = k - r  # sublists of length q; the remaining r have length q+1
    lengths = np.array([q] * n_short + [q + 1] * r)
    starts = np.concatenate([[0], np.cumsum(lengths)[:-1]])
    rounds = starts[None, :] + np.arange(q)[:, None]  # (q, k) round-robin
    perm = rounds.reshape(-1)
    if r:
        leftovers = starts[n_short:] + q
        perm = np.concatenate([perm, leftovers])
    return perm.astype(np.int32)


def categorical_sort_order(categories: jnp.ndarray, rank_in_cat: jnp.ndarray,
                           cat_counts: jnp.ndarray, k: int) -> jnp.ndarray:
    """Section 4.3: lexicographic order by (incomplete, block, category, pos).

    All inputs carry a leading group axis: ``categories`` / ``rank_in_cat``
    are (G, M) in centrality-sorted order (``rank_in_cat`` is each object's
    0-based position among objects of its category), ``cat_counts`` is
    (G, n_categories).  The returned (G, M) permutation yields the rearranged
    list per group: full K-blocks alternate across categories by block index;
    incomplete tail blocks come last in the same alternating order.
    """
    block = rank_in_cat // k
    pos = rank_in_cat % k
    n_g = jnp.take_along_axis(cat_counts, categories, axis=1)
    incomplete = ((block + 1) * k > n_g).astype(jnp.int32)
    # lexsort: last key is primary; sorts each group row independently
    return jnp.lexsort((pos, categories, block, incomplete), axis=-1)


# ---------------------------------------------------------------------------
# The shared Algorithm-1 batch step
# ---------------------------------------------------------------------------

def _assign_batch(solver_obj, fused, auction_config, cents, counts,
                  cat_counts, xb, is_real, cb=None, ub=None, prices=None,
                  stats_fn=None):
    """One Algorithm-1 batch on a (G, k, ...) stack: solve the LAP against
    the current centroids and fold the assigned rows into the running
    moments.  The ONE copy of the batch update -- the dense core's scan and
    the streaming core's chunked scan both call it, which is what makes the
    ``chunk_size >= n`` parity guarantee hold bit-for-bit.

    ``cb`` carries each row's quota codes as a (G, k, A) stack -- A = 1 with
    plain ``categories`` (the code IS the category), A > 1 for multi-attribute
    fairness (one offset code per attribute into a shared ``ub`` axis).  A
    cluster is closed for a row when ANY of the row's codes is at its
    ``ub`` quota, which with A = 1 degenerates exactly to constraint (5).

    ``prices`` warm-starts the batch LAP from a carried (G, k) price vector
    (``None`` = zeros: the cold path, unchanged); the solver's final prices
    are returned so a stateful caller can carry them into its next run.

    ``stats_fn`` (the solver's registered telemetry twin, resolved by the
    caller) swaps the solve for its ``(assign, prices, stats)`` variant;
    the trailing return slot then carries the per-batch telemetry pytree
    (``None`` on the default path, which stays byte-identical).
    """
    garange = jnp.arange(cents.shape[0])[:, None]
    stats = None
    if fused:
        # matrix-free bidding: the (k, k) value matrix is never built;
        # each auction round is one fused bid_top2 kernel call.
        if stats_fn is not None:
            assign, p_out, stats = stats_fn(xb, cents, is_real=is_real,
                                            config=auction_config,
                                            prices=prices)
        else:
            assign, p_out = solver_obj.factored(xb, cents, is_real=is_real,
                                                config=auction_config,
                                                prices=prices)
    else:
        # reduced cost: row-constant ||x||^2 dropped (LAP-invariant)
        cost = (-2.0 * jnp.einsum("gid,gjd->gij", xb, cents)
                + jnp.sum(cents * cents, axis=-1)[:, None, :])
        cost = jnp.where(is_real[..., None], cost, 0.0)  # neutral dummies
        if ub is not None:
            # cnt[g, i, j, a] = cat_counts[g, j, cb[g, i, a]]: how many of
            # row i's code-a peers cluster j already holds
            cnt = jnp.take_along_axis(
                cat_counts[:, None], cb[:, :, None, :], axis=3)
            quota = jnp.take_along_axis(ub[:, None], cb, axis=2)
            full = jnp.any(cnt >= quota[:, :, None, :], axis=-1)
            cost = jnp.where(jnp.logical_and(full, is_real[..., None]),
                             _MASK_COST, cost)
        if stats_fn is not None:
            assign, p_out, stats = stats_fn(cost, auction_config, prices)
        else:
            assign, p_out = solver_obj.solve(cost, auction_config,
                                             prices)  # (G, k) batched
    # centroid running mean: mu_k += (x - mu_k) / new_count  (Algorithm 1)
    new_counts = counts.at[garange, assign].add(is_real.astype(jnp.int32))
    delta = xb - jnp.take_along_axis(cents, assign[..., None], axis=1)
    upd = jnp.zeros_like(cents).at[garange, assign].add(
        jnp.where(is_real[..., None], delta, 0.0))
    cents = cents + upd / jnp.maximum(
        new_counts, 1)[..., None].astype(jnp.float32)
    if ub is not None:
        cat_counts = cat_counts.at[
            garange[..., None], assign[..., None], cb].add(
            is_real[..., None].astype(jnp.int32))
    return cents, new_counts, cat_counts, assign, p_out, stats


# ---------------------------------------------------------------------------
# The rank-polymorphic masked core
# ---------------------------------------------------------------------------

@functools.partial(
    jax.jit,
    static_argnames=("k", "variant", "n_categories", "n_fair_codes",
                     "solver", "auction_config", "return_state",
                     "telemetry"),
)
def aba_core(
    x: jnp.ndarray,
    k: int,
    valid_mask: jnp.ndarray | None = None,
    *,
    variant: Variant = "base",
    categories: jnp.ndarray | None = None,
    n_categories: int = 0,
    fair_codes: jnp.ndarray | None = None,
    n_fair_codes: int = 0,
    solver: str = "auction",
    auction_config: AuctionConfig = AuctionConfig(),
    prices: jnp.ndarray | None = None,
    return_state: bool = False,
    telemetry: bool = False,
) -> jnp.ndarray:
    """Assignment-Based Anticlustering on a ``(G, M, D)`` stack of problems.

    This is THE implementation of Algorithm 1 + variants 4.2/4.3: the flat
    case is ``G = 1``, hierarchical levels and sharded shards pass their
    padded group stacks directly.  Each scan step solves the whole
    ``(G, k, k)`` LAP stack with ONE batched solver call.

    Args:
      x: (G, M, D) float features, groups padded to a common M.
      k: number of anticlusters per group (static).
      valid_mask: optional (G, M) bool; False rows are padding -- they never
        influence real rows, but their returned labels are arbitrary in
        [0, k): callers must mask them out.  ``None`` means all rows valid
        (required for the static interleave rearrangement).
      variant: "base", "interleave" (Section 4.2), or "auto" (interleave when
        anticlusters are small, M/k <= 8, matching the paper's guidance).
        Interleave needs the true row count to be static, so it is skipped
        when ``valid_mask`` is given.
      categories: optional (G, M) int32 in [0, n_categories) -- Section 4.3,
        applied independently per group (stratification composes across
        hierarchical levels).
      n_categories: static number of categories (required with categories).
      fair_codes: optional (G, M, A) int32 multi-attribute quota codes --
        the proportional-fairness generalization of constraint (5).  The
        rearrangement still follows ``categories`` (the front door passes
        the joint attribute cell there), but the quota upper bounds are
        enforced per *code*: each of a row's A codes indexes a shared
        ``n_fair_codes``-wide count axis (attributes occupy disjoint offset
        ranges) and a cluster is closed once any code hits
        ``ceil(count(code)/k)``.  ``None`` (with categories) is exactly the
        single-attribute case: codes = categories, A = 1, bit-identical to
        the pre-fairness behaviour.
      n_fair_codes: static total code count (required with fair_codes).
      solver: registry name (see ``repro.core.assignment.register_solver``);
        defaults: "auction" | "auction_fused" | "greedy" | "scipy".  A solver
        with a matrix-free ``factored`` path (e.g. "auction_fused", whose
        bidding top-2 streams through the Pallas ``bid_top2`` kernel) uses it
        for category-free problems at any G (the stacked bidding vmaps the
        kernel) and falls back to its dense ``solve`` when categories are in
        play (the categorical upper-bound mask cannot be factored).
      prices: optional (G, k) float32 warm-start prices: every batch LAP in
        this run starts its epsilon schedule from this carried vector
        instead of zeros.  ``None`` (or zeros) is the cold path and is
        bit-for-bit identical to the pre-warm-start behaviour -- the
        assignment is eps-optimal either way, warm prices only cut rounds.
      return_state: also return the run's carried state as a dict with
        ``"prices"`` ((G, k) final prices of the last batch, the warm start
        for a repeated same-shape run) and ``"mu"`` ((G, d) per-group
        centrality centroid, the running moment of the sort phase).
      telemetry: (requires ``return_state``) the state dict additionally
        carries ``"telemetry"``: the solver's per-batch stats pytree stacked
        over the scan (auction rounds per eps phase, eps schedule, warm
        re-entry decisions; leading axis ``n_batches - 1``), or ``None``
        when the resolved solve path registers no telemetry twin or no
        batch LAP runs (``n_batches == 1``).  The labels and prices are
        bit-identical to the ``telemetry=False`` call; the flag is static,
        so the default executable is untouched.

    Returns:
      (G, M) int32 labels in [0, k); with ``return_state`` a
      ``(labels, state)`` tuple.
    """
    G, M, D = x.shape
    if k > M:
        raise ValueError(f"k={k} > M={M}")
    if telemetry and not return_state:
        raise ValueError("telemetry=True requires return_state=True (the "
                         "stats pytree rides the state dict)")
    solver_obj = get_solver(solver)
    xf = x.astype(jnp.float32)
    garange = jnp.arange(G)[:, None]

    # --- per-group centrality sort (descending distance to centroid) -------
    if valid_mask is None:
        mu = jnp.mean(xf, axis=1)
        dist = jnp.sum((xf - mu[:, None, :]) ** 2, axis=-1)
    else:
        w = valid_mask.astype(jnp.float32)
        mu = jnp.sum(xf * w[..., None], axis=1) / jnp.maximum(
            jnp.sum(w, axis=1), 1.0)[:, None]
        dist = jnp.where(valid_mask,
                         jnp.sum((xf - mu[:, None, :]) ** 2, axis=-1),
                         -jnp.inf)  # padding sorts to the end
    order = jnp.argsort(-dist, axis=1, stable=True).astype(jnp.int32)

    # --- rearrangement ------------------------------------------------------
    use_interleave = variant == "interleave" or (
        variant == "auto" and M // k <= 8)
    if categories is not None:
        if n_categories <= 0:
            raise ValueError("n_categories must be set with categories")
        cat_i = categories.astype(jnp.int32)
        cat_sorted = jnp.take_along_axis(cat_i, order, axis=1)
        if valid_mask is not None:
            # padding gets a virtual category that sorts last
            cat_sorted = jnp.where(
                jnp.take_along_axis(valid_mask, order, axis=1),
                cat_sorted, n_categories - 1)
        onehot = jax.nn.one_hot(cat_sorted, n_categories, dtype=jnp.int32)
        rank_in_cat = jnp.take_along_axis(
            jnp.cumsum(onehot, axis=1) - onehot,
            cat_sorted[..., None], axis=2)[..., 0]
        cat_counts = jnp.sum(onehot, axis=1)
        order = jnp.take_along_axis(
            order, categorical_sort_order(cat_sorted, rank_in_cat,
                                          cat_counts, k), axis=1)
    elif use_interleave and valid_mask is None:
        order = order[:, jnp.asarray(interleave_permutation(M, k))]
    # (interleave + valid_mask: the true row count is dynamic, so the static
    #  rearrangement is unavailable; fall back to base order.)

    # --- pad to full batches -------------------------------------------------
    n_batches = -(-M // k)
    pad = n_batches * k - M
    order_p = (jnp.concatenate([order, jnp.full((G, pad), M, jnp.int32)], 1)
               if pad else order)
    real = order_p < M
    if valid_mask is not None:
        vm_ext = jnp.concatenate([valid_mask, jnp.zeros((G, 1), jnp.bool_)], 1)
        real = jnp.logical_and(
            real, jnp.take_along_axis(vm_ext, jnp.minimum(order_p, M), axis=1))
    batches = order_p.reshape(G, n_batches, k)
    real = real.reshape(G, n_batches, k)

    x_ext = jnp.concatenate([xf, jnp.zeros((G, 1, D), jnp.float32)], 1)
    if fair_codes is not None and categories is None:
        raise ValueError("fair_codes requires categories (the joint "
                         "attribute cell drives the 4.3 rearrangement)")
    if categories is not None:
        # quota codes: A=1 plain categories (code IS the category) or the
        # (G, M, A) multi-attribute fairness codes sharing one count axis
        if fair_codes is not None:
            if n_fair_codes <= 0:
                raise ValueError("n_fair_codes must be set with fair_codes")
            codes_i = fair_codes.astype(jnp.int32)
            n_codes = n_fair_codes
        else:
            codes_i = cat_i[..., None]
            n_codes = n_categories
        codes_ext = jnp.concatenate(
            [codes_i, jnp.zeros((G, 1, codes_i.shape[-1]), jnp.int32)], 1)

    # --- batch 1 initializes centroids ---------------------------------------
    first_idx = jnp.minimum(batches[:, 0], M)
    centroids0 = jnp.take_along_axis(x_ext, first_idx[..., None], axis=1)
    counts0 = real[:, 0].astype(jnp.int32)
    labels0 = jnp.broadcast_to(jnp.arange(k, dtype=jnp.int32), (G, k))
    if categories is not None:
        valid_i = (jnp.ones((G, M), jnp.int32) if valid_mask is None
                   else valid_mask.astype(jnp.int32))
        ub = -(-jnp.maximum(
            jnp.zeros((G, n_codes), jnp.int32).at[
                garange[..., None], codes_i].add(valid_i[..., None]),
            0) // k)  # (G, n_codes): ceil(|N_code| / k) per group
        cat_counts0 = (
            jnp.zeros((G, k, n_codes), jnp.int32)
            .at[garange[..., None], labels0[..., None],
                jnp.take_along_axis(codes_ext, first_idx[..., None], axis=1)]
            .add(real[:, 0].astype(jnp.int32)[..., None]))
    else:
        ub = None
        cat_counts0 = jnp.zeros((G, k, 1), jnp.int32)

    prices_in = (None if prices is None
                 else jnp.asarray(prices, jnp.float32))
    if n_batches == 1:
        out = jnp.zeros((G, M + 1), jnp.int32).at[
            garange, first_idx].set(labels0, mode="drop")
        if return_state:
            p_out = (jnp.zeros((G, k), jnp.float32) if prices_in is None
                     else prices_in)
            state = {"prices": p_out, "mu": mu}
            if telemetry:
                state["telemetry"] = None  # no batch LAP ran
            return out[:, :M], state
        return out[:, :M]

    # --- scan over remaining batches: one (G, k, k) LAP stack per step -----
    fused = (solver_obj.factored is not None and ub is None)
    # telemetry statically downgrades to None when the resolved solve path
    # has no stats twin (greedy/scipy/custom backends)
    stats_fn = None
    if telemetry:
        stats_fn = (solver_obj.factored_stats if fused
                    else solver_obj.solve_stats)
    p_init = (jnp.zeros((G, k), jnp.float32) if prices_in is None
              else prices_in)

    def step(carry, inp):
        cents, counts, cat_counts, _p_last = carry
        idx, is_real = inp  # (G, k) each
        xb = jnp.take_along_axis(x_ext, jnp.minimum(idx, M)[..., None], axis=1)
        cb = (jnp.take_along_axis(codes_ext, jnp.minimum(idx, M)[..., None],
                                  axis=1)
              if ub is not None else None)
        # every batch warm-starts from the SAME carried epoch prices (not the
        # previous batch's): the cold path (prices=None -> per-batch zeros)
        # stays bit-identical, and warm prices never compound across batches
        cents, new_counts, cat_counts, assign, p_out, stats = _assign_batch(
            solver_obj, fused, auction_config, cents, counts, cat_counts,
            xb, is_real, cb=cb, ub=ub, prices=prices_in, stats_fn=stats_fn)
        if stats_fn is None:
            return (cents, new_counts, cat_counts, p_out), assign
        return (cents, new_counts, cat_counts, p_out), (assign, stats)

    tele = None
    if stats_fn is None:
        (_, _, _, prices_f), assigns = jax.lax.scan(
            step, (centroids0, counts0, cat_counts0, p_init),
            (batches[:, 1:].swapaxes(0, 1), real[:, 1:].swapaxes(0, 1)))
    else:
        (_, _, _, prices_f), (assigns, tele) = jax.lax.scan(
            step, (centroids0, counts0, cat_counts0, p_init),
            (batches[:, 1:].swapaxes(0, 1), real[:, 1:].swapaxes(0, 1)))

    labels_all = jnp.concatenate(
        [labels0[:, None], assigns.swapaxes(0, 1)], axis=1)  # (G, B, k)
    out = jnp.zeros((G, M + 1), jnp.int32).at[
        garange, jnp.minimum(order_p, M)
    ].set(labels_all.reshape(G, -1), mode="drop")
    # padding rows of the *input* keep whatever label they drew (callers mask)
    if return_state:
        state = {"prices": prices_f, "mu": mu}
        if telemetry:
            state["telemetry"] = tele
        return out[:, :M], state
    return out[:, :M]


# ---------------------------------------------------------------------------
# The streaming (chunked, matrix-free) core
# ---------------------------------------------------------------------------

@functools.partial(
    jax.jit,
    static_argnames=("k", "chunk_size", "variant", "n_categories",
                     "n_fair_codes", "solver", "auction_config",
                     "return_state", "telemetry"),
)
def aba_stream(
    x: jnp.ndarray,
    k: int,
    chunk_size: int,
    *,
    variant: Variant = "base",
    categories: jnp.ndarray | None = None,
    n_categories: int = 0,
    fair_codes: jnp.ndarray | None = None,
    n_fair_codes: int = 0,
    valid_mask: jnp.ndarray | None = None,
    solver: str = "auction",
    auction_config: AuctionConfig = AuctionConfig(),
    prices: jnp.ndarray | None = None,
    return_state: bool = False,
    telemetry: bool = False,
) -> jnp.ndarray:
    """Streaming ABA on flat ``(n, d)`` features: Algorithm 1 in fixed-size
    chunks, for n far beyond what the dense core's working set allows.

    The dense core materializes a permuted copy of the whole dataset (its
    ``x_ext`` gather is O(n*d)); here the centrality pass uses running
    moments (one scan for the global centroid, one chunked distance pass),
    and the assignment phase is a two-level scan -- outer over chunks of
    ``chunk_size`` rows (ONE (chunk, d) gather each), inner over the chunk's
    n/k batches -- so peak live memory beyond the input is
    O(chunk_size * d + k * d) in the feature dimension (plus the O(n)
    scalar dist/order/label vectors every path needs), not O(n * d): there
    is no concatenated/permuted dataset copy anywhere (chunks are dynamic
    slices; sentinel rows are clamped gathers masked by ``is_real``).  On
    TPU the per-chunk gather runs through the double-buffered DMA kernel
    (``repro.kernels.ops.row_gatherer``) so the next chunk's row movement
    overlaps the current chunk's batch solves.  With a ``factored`` solver
    (e.g. "auction_fused") each batch's LAP is matrix-free on top: the
    (k, k) value matrix is never built either (`bid_top2` streams column
    tiles through VMEM on TPU).

    ``categories`` / ``fair_codes`` / ``valid_mask`` stream too (the bans
    lifted): the Section 4.3 rearrangement becomes a single pass over the
    centrality-sorted category stream -- an outer scan carries per-category
    running counts while each chunk ranks its rows locally with one
    (chunk, C) one-hot cumsum -- and the assignment scan carries the
    (k, n_codes) per-cluster quota counts, so the categorical working set is
    O(chunk * C + k * C) and never the dense (n, C) one-hot.  The rank pass
    is integer-exact, so the rearranged order is bit-identical to the dense
    categorical path at ANY chunk size; quota masking runs through the same
    ``_assign_batch`` as the dense core.

    Every batch runs through the same ``_assign_batch`` step as the dense
    core, so with ``chunk_size >= n`` the labels are bit-for-bit identical
    to ``aba_core(x[None], k)[0]`` with the same
    solver/variant/categories/fairness/mask (the parity contract tested in
    tests/test_anticluster.py and tests/test_stream_categorical.py).
    Larger chunks only change *memory*, never assignment order; smaller
    chunks are exactly equivalent too except that the global centroid is
    accumulated chunk by chunk (same sum, same result up to float summation
    order -- the permutation and all LAPs see identical inputs).

    Args:
      x: (n, d) float features.
      k: number of anticlusters (static).
      chunk_size: rows processed per outer step (static); rounded down to a
        multiple of k (at least one k-batch).
      variant: "base" | "interleave" | "auto" (same rule as ``aba_core``;
        categories take precedence, and the static interleave is skipped
        under ``valid_mask`` exactly like the dense core).
      categories: optional (n,) int32 in [0, n_categories) -- Section 4.3.
      n_categories: static category count (required with categories).
      fair_codes: optional (n, A) int32 multi-attribute quota codes (see
        ``aba_core``); requires ``categories`` (the joint attribute cell).
      n_fair_codes: static total code count (required with fair_codes).
      valid_mask: optional (n,) bool; False rows are padding (arbitrary
        labels, masked out of moments/quotas), same contract as the dense
        core.
      solver / auction_config: LAP backend (registry name) and schedule.
      prices: optional (1, k) float32 warm-start prices, same contract as
        ``aba_core`` (every batch LAP starts from this carried vector; None
        is the bit-identical cold path).
      return_state: also return ``{"prices": (1, k), "mu": (d,)}`` -- the
        final batch's prices and the running-moment global centroid.
      telemetry: (requires ``return_state``) the state dict additionally
        carries ``"telemetry"``: the solver's per-batch stats pytree with
        leading axis ``n_batches - 1`` (the chunk structure flattened back
        out and the sentinel pad batches dropped, so the layout matches the
        dense core's), or ``None`` when the resolved solve path has no
        telemetry twin or only one batch runs.  Labels/prices stay
        bit-identical; the flag is static (default executable untouched).

    Returns:
      (n,) int32 labels in [0, k); with ``return_state`` a
      ``(labels, state)`` tuple.
    """
    n, d = x.shape
    if k > n:
        raise ValueError(f"k={k} > n={n}")
    if telemetry and not return_state:
        raise ValueError("telemetry=True requires return_state=True (the "
                         "stats pytree rides the state dict)")
    solver_obj = get_solver(solver)
    xf = x.astype(jnp.float32)
    cpb = max(1, int(chunk_size) // k)  # batches per chunk
    chunk = cpb * k
    vm = None if valid_mask is None else valid_mask.astype(jnp.bool_)
    if fair_codes is not None and categories is None:
        raise ValueError("fair_codes requires categories (the joint "
                         "attribute cell drives the 4.3 rearrangement)")
    if categories is not None:
        if n_categories <= 0:
            raise ValueError("n_categories must be set with categories")
        cat_i = categories.astype(jnp.int32)
        if fair_codes is not None:
            if n_fair_codes <= 0:
                raise ValueError("n_fair_codes must be set with fair_codes")
            codes_i = fair_codes.astype(jnp.int32)   # (n, A)
            n_codes = n_fair_codes
        else:
            codes_i = cat_i[:, None]                 # A = 1: code IS the cat
            n_codes = n_categories

    # --- centrality: running moments + chunked distance pass ---------------
    # No padded O(n*d) copy: chunks are dynamic slices of the input.  The
    # tail chunk is clamped to the last `chunk` rows and masks its overlap
    # with the previous chunk (overlapping *distances* recompute to the same
    # values, so the update-slice reassembly is idempotent there).
    n_chunks = -(-n // chunk)
    if int(chunk_size) >= n or n_chunks == 1:
        # One covering chunk: identical ops to the dense core.  Keyed on the
        # *requested* chunk_size, not the k-rounded chunk, so the bit-parity
        # contract "chunk_size >= n == dense labels" holds structurally
        # (rounding down to a k-multiple must not switch the float reduction
        # order of the centrality mean).
        if vm is None:
            mu = jnp.mean(xf, axis=0)
            dist = jnp.sum((xf - mu[None, :]) ** 2, axis=-1)
        else:
            w = vm.astype(jnp.float32)
            mu = jnp.sum(xf * w[:, None], axis=0) / jnp.maximum(
                jnp.sum(w), 1.0)
            dist = jnp.where(vm,
                             jnp.sum((xf - mu[None, :]) ** 2, axis=-1),
                             -jnp.inf)  # padding sorts to the end
    else:
        starts = jnp.minimum(
            jnp.arange(n_chunks, dtype=jnp.int32) * chunk, n - chunk)
        offs = jnp.arange(n_chunks, dtype=jnp.int32) * chunk - starts
        crange = jnp.arange(chunk, dtype=jnp.int32)

        if vm is None:
            def moment_step(acc, inp):
                s, off = inp
                xc = jax.lax.dynamic_slice(xf, (s, 0), (chunk, d))
                w = (crange >= off).astype(jnp.float32)[:, None]
                return acc + jnp.sum(xc * w, axis=0), None

            total, _ = jax.lax.scan(
                moment_step, jnp.zeros((d,), jnp.float32), (starts, offs))
            mu = total / n
        else:
            def moment_step(acc, inp):
                s, off = inp
                xc = jax.lax.dynamic_slice(xf, (s, 0), (chunk, d))
                wc = jnp.logical_and(
                    crange >= off,
                    jax.lax.dynamic_slice(vm, (s,), (chunk,)))
                wf = wc.astype(jnp.float32)
                tot, cnt = acc
                return (tot + jnp.sum(xc * wf[:, None], axis=0),
                        cnt + jnp.sum(wf)), None

            (total, cnt), _ = jax.lax.scan(
                moment_step,
                (jnp.zeros((d,), jnp.float32), jnp.zeros((), jnp.float32)),
                (starts, offs))
            mu = total / jnp.maximum(cnt, 1.0)

        def dist_step(buf, inp):
            s, _off = inp
            xc = jax.lax.dynamic_slice(xf, (s, 0), (chunk, d))
            dc = jnp.sum((xc - mu[None, :]) ** 2, axis=-1)
            return jax.lax.dynamic_update_slice(buf, dc, (s,)), None

        dist, _ = jax.lax.scan(
            dist_step, jnp.zeros((n,), jnp.float32), (starts, offs))
        if vm is not None:
            dist = jnp.where(vm, dist, -jnp.inf)
    order = jnp.argsort(-dist, stable=True).astype(jnp.int32)

    # --- rearrangement (same rules as the dense core) -----------------------
    if categories is not None:
        cat_sorted = cat_i[order]
        if vm is not None:
            # padding gets a virtual category that sorts last (dense rule)
            cat_sorted = jnp.where(vm[order], cat_sorted, n_categories - 1)
        # Single-pass rank-in-category over the sorted category stream: the
        # outer scan carries the (C,) per-category running counts, each
        # chunk ranks its rows locally with one (chunk, C) one-hot cumsum --
        # the dense (n, C) one-hot never materializes.  Integer-exact, so
        # the rearranged order is bit-identical to the dense categorical
        # path at ANY chunk size.
        rpad = n_chunks * chunk - n
        cs_p = (jnp.concatenate([cat_sorted, jnp.zeros((rpad,), jnp.int32)])
                if rpad else cat_sorted)
        in_rng = jnp.arange(n_chunks * chunk, dtype=jnp.int32) < n

        def rank_step(run, inp):
            cat_c, ok_c = inp
            oh = (jax.nn.one_hot(cat_c, n_categories, dtype=jnp.int32)
                  * ok_c.astype(jnp.int32)[:, None])
            local = jnp.cumsum(oh, axis=0) - oh
            r = run[cat_c] + jnp.take_along_axis(
                local, cat_c[:, None], axis=1)[:, 0]
            return run + jnp.sum(oh, axis=0), r

        cat_counts, ranks = jax.lax.scan(
            rank_step, jnp.zeros((n_categories,), jnp.int32),
            (cs_p.reshape(n_chunks, chunk), in_rng.reshape(n_chunks, chunk)))
        rank_in_cat = ranks.reshape(-1)[:n]
        order = jnp.take_along_axis(
            order[None],
            categorical_sort_order(cat_sorted[None], rank_in_cat[None],
                                   cat_counts[None], k), axis=1)[0]
    elif (variant == "interleave" or (variant == "auto" and n // k <= 8)) \
            and vm is None:
        order = order[jnp.asarray(interleave_permutation(n, k))]
    # (interleave + valid_mask: same dense-core rule -- fall back to base)

    # --- pad to full batches, then to full chunks ---------------------------
    n_batches = -(-n // k)
    order_p = (jnp.concatenate([order, jnp.full((n_batches * k - n,), n,
                                                jnp.int32)])
               if n_batches * k > n else order)
    real = order_p < n
    if vm is not None:
        real = jnp.logical_and(real, vm[jnp.minimum(order_p, n - 1)])
    batches = order_p.reshape(n_batches, k)
    real_b = real.reshape(n_batches, k)

    # Sentinel indices (== n) clamp to the last row instead of indexing a
    # concatenated zero-row copy: a clamped gather avoids the dense core's
    # O(n*d) ``x_ext`` duplicate, and every consumer of a dummy row's values
    # masks them with ``is_real`` (cost neutralized, centroid delta zeroed,
    # quota add zeroed), so the clamped garbage never leaks -- labels stay
    # bit-identical.

    # --- batch 1 initializes centroids ---------------------------------------
    first_idx = jnp.minimum(batches[0], n - 1)
    centroids0 = xf[first_idx][None]              # (1, k, d)
    counts0 = real_b[0].astype(jnp.int32)[None]   # (1, k)
    labels0 = jnp.arange(k, dtype=jnp.int32)
    if categories is not None:
        valid_i = (jnp.ones((n,), jnp.int32) if vm is None
                   else vm.astype(jnp.int32))
        # ceil(|N_code| / k) quota bounds over valid rows -- (1, n_codes)
        ub = -(-jnp.maximum(
            jnp.zeros((n_codes,), jnp.int32).at[codes_i].add(
                valid_i[:, None]), 0) // k)[None]
        cat0 = (jnp.zeros((k, n_codes), jnp.int32)
                .at[jnp.arange(k)[:, None], codes_i[first_idx]]
                .add(real_b[0].astype(jnp.int32)[:, None]))[None]
    else:
        ub = None
        cat0 = jnp.zeros((1, k, 1), jnp.int32)
    prices_in = (None if prices is None
                 else jnp.asarray(prices, jnp.float32))
    if n_batches == 1:
        out1 = jnp.zeros((n + 1,), jnp.int32).at[first_idx].set(
            labels0, mode="drop")[:n]
        if return_state:
            p_out = (jnp.zeros((1, k), jnp.float32) if prices_in is None
                     else prices_in)
            state = {"prices": p_out, "mu": mu}
            if telemetry:
                state["telemetry"] = None  # no batch LAP ran
            return out1, state
        return out1

    # --- stream the remaining batches in chunks of cpb ----------------------
    rem = n_batches - 1
    n_bchunks = -(-rem // cpb)
    bpad = n_bchunks * cpb - rem
    idx_rest = batches[1:]
    real_rest = real_b[1:]
    if bpad:  # sentinel batches: all-dummy rows, a no-op for _assign_batch
        idx_rest = jnp.concatenate(
            [idx_rest, jnp.full((bpad, k), n, jnp.int32)])
        real_rest = jnp.concatenate(
            [real_rest, jnp.zeros((bpad, k), jnp.bool_)])
    idx_rest = idx_rest.reshape(n_bchunks, cpb, k)
    real_rest = real_rest.reshape(n_bchunks, cpb, k)

    # same rule as the dense core: the categorical quota mask cannot be
    # factored, so a factored solver falls back to its dense solve under it
    fused = solver_obj.factored is not None and categories is None
    # telemetry statically downgrades to None when the resolved solve path
    # has no stats twin (greedy/scipy/custom backends)
    stats_fn = None
    if telemetry:
        stats_fn = (solver_obj.factored_stats if fused
                    else solver_obj.solve_stats)
    p_init = (jnp.zeros((1, k), jnp.float32) if prices_in is None
              else prices_in)

    take_rows = row_gatherer(xf)  # lays x out for the gather once

    def chunk_step(carry, inp):
        cents, counts, ccat, p_last = carry
        idx_c, real_c = inp                      # (cpb, k)
        idx_g = jnp.minimum(idx_c, n - 1)
        # ONE (chunk, d) gather; double-buffered DMA kernel on TPU
        xc = take_rows(idx_g.reshape(-1)).reshape(cpb, k, d)
        if categories is not None:
            xs = (xc, real_c, codes_i[idx_g])    # + (cpb, k, A) code gather
        else:
            xs = (xc, real_c)

        def batch_step(bcarry, binp):
            bcents, bcounts, bcat, _bp = bcarry
            if categories is not None:
                xb, is_real, cb = binp           # (k, d), (k,), (k, A)
            else:
                (xb, is_real), cb = binp, None
            # same epoch-carried warm start per batch as the dense core
            bcents, bcounts, bcat, assign, p_out, stats = _assign_batch(
                solver_obj, fused, auction_config, bcents, bcounts, bcat,
                xb[None], is_real[None],
                cb=None if cb is None else cb[None], ub=ub,
                prices=prices_in, stats_fn=stats_fn)
            if stats_fn is None:
                return (bcents, bcounts, bcat, p_out), assign[0]
            return (bcents, bcounts, bcat, p_out), (assign[0], stats)

        (cents, counts, ccat, p_last), ys = jax.lax.scan(
            batch_step, (cents, counts, ccat, p_last), xs)
        return (cents, counts, ccat, p_last), ys  # assigns (cpb, k) [+stats]

    tele = None
    if stats_fn is None:
        (_, _, _, prices_f), assigns = jax.lax.scan(
            chunk_step, (centroids0, counts0, cat0, p_init),
            (idx_rest, real_rest))
    else:
        (_, _, _, prices_f), (assigns, tele_ck) = jax.lax.scan(
            chunk_step, (centroids0, counts0, cat0, p_init),
            (idx_rest, real_rest))
        # (n_bchunks, cpb, ...) -> (n_batches - 1, ...): flatten the chunk
        # structure and drop the sentinel pad batches, matching aba_core's
        # per-batch layout
        tele = jax.tree_util.tree_map(
            lambda a: a.reshape((n_bchunks * cpb,) + a.shape[2:])[:rem],
            tele_ck)

    labels_all = jnp.concatenate(
        [labels0, assigns.reshape(-1)[:rem * k]])
    out = jnp.zeros((n + 1,), jnp.int32).at[jnp.minimum(order_p, n)].set(
        labels_all, mode="drop")
    if return_state:
        state = {"prices": prices_f, "mu": mu}
        if telemetry:
            state["telemetry"] = tele
        return out[:n], state
    return out[:n]


def delta_moments(moment_sum: jnp.ndarray, moment_count: jnp.ndarray,
                  added: jnp.ndarray | None = None,
                  removed: jnp.ndarray | None = None,
                  ) -> tuple[jnp.ndarray, jnp.ndarray]:
    """Merge arrivals/departures into carried centrality moments.

    ``moment_sum`` ((d,) feature sum over valid rows) and ``moment_count``
    (() valid-row count) are the running moments :class:`ABAState` carries
    behind the level-1 centrality sort -- the same mergeable pair
    ``aba_stream`` accumulates chunk by chunk.  ``added`` / ``removed`` are
    the delta's row blocks ((m, d) / (r, d)); the update is exact: the
    returned moments equal the from-scratch moments of the post-delta
    dataset up to float summation order.
    """
    moment_sum = jnp.asarray(moment_sum, jnp.float32)
    moment_count = jnp.asarray(moment_count, jnp.float32)
    if removed is not None and removed.shape[0]:
        moment_sum = moment_sum - jnp.sum(
            jnp.asarray(removed, jnp.float32), axis=0)
        moment_count = moment_count - float(removed.shape[0])
    if added is not None and added.shape[0]:
        moment_sum = moment_sum + jnp.sum(
            jnp.asarray(added, jnp.float32), axis=0)
        moment_count = moment_count + float(added.shape[0])
    return moment_sum, moment_count


# ---------------------------------------------------------------------------
# Deprecated shims (exact-parity wrappers over aba_core)
# ---------------------------------------------------------------------------

def aba(
    x: jnp.ndarray,
    k: int,
    *,
    variant: Variant = "auto",
    categories: jnp.ndarray | None = None,
    n_categories: int = 0,
    valid_mask: jnp.ndarray | None = None,
    solver: str = "auction",
    auction_config: AuctionConfig = AuctionConfig(),
) -> jnp.ndarray:
    """Deprecated: flat ABA on (n, d).  Use ``repro.anticluster.anticluster``.

    Exactly ``aba_core`` with a leading group axis of size 1; labels are
    bit-for-bit identical to ``anticluster(x, AnticlusterSpec(k=k, ...))``.
    """
    _deprecated("aba", "repro.anticluster.anticluster(x, spec)")
    return aba_core(
        x[None], k,
        None if valid_mask is None else valid_mask[None],
        variant=variant,
        categories=None if categories is None else categories[None],
        n_categories=n_categories, solver=solver,
        auction_config=auction_config)[0]


def aba_batched(
    x: jnp.ndarray,
    k: int,
    valid_mask: jnp.ndarray,
    *,
    solver: str = "auction",
    auction_config: AuctionConfig = AuctionConfig(),
) -> jnp.ndarray:
    """Deprecated: base-variant ABA on a (G, M, D) stack.  Use
    ``repro.anticluster.anticluster`` (it accepts the stacked rank directly).

    This IS ``aba_core`` -- the legacy name solved the stack with a dense
    batched engine, so a factored solver falls back to its dense path here.
    """
    _deprecated("aba_batched",
                "repro.anticluster.anticluster(x, spec) on a (G, M, D) stack")
    solver = "auction" if solver == "auction_fused" else solver
    return aba_core(x, k, valid_mask, variant="base", solver=solver,
                    auction_config=auction_config)


# ---------------------------------------------------------------------------
# Reference implementation (Algorithm 1 verbatim, numpy + exact Hungarian)
# ---------------------------------------------------------------------------

def aba_reference(x: np.ndarray, k: int, *, variant: Variant = "base",
                  categories: np.ndarray | None = None) -> np.ndarray:
    """Direct transcription of Algorithm 1 with an exact LAP solver.

    Used as the oracle in tests and to quantify the auction solver's
    eps-optimality gap.  O(N K^2) like the paper's C code, but in numpy.
    """
    from scipy.optimize import linear_sum_assignment

    x = np.asarray(x, np.float64)
    n = x.shape[0]
    mu = x.mean(axis=0)
    dist = ((x - mu) ** 2).sum(axis=1)
    order = np.argsort(-dist, kind="stable")

    if categories is not None:
        categories = np.asarray(categories)
        g_count = np.bincount(categories)
        ub = -(-g_count // k)
        pieces_full, pieces_tail = [], []
        per_cat = {g: order[categories[order] == g] for g in range(len(g_count))}
        max_blocks = max((len(v) + k - 1) // k for v in per_cat.values())
        for b in range(max_blocks):
            for g, idxs in per_cat.items():
                blk = idxs[b * k:(b + 1) * k]
                (pieces_full if len(blk) == k else pieces_tail).append(blk)
        order = np.concatenate([p for p in pieces_full + pieces_tail if len(p)])
    elif variant == "interleave" or (variant == "auto" and n // k <= 8):
        order = order[interleave_permutation(n, k)]

    labels = np.full(n, -1, np.int64)
    labels[order[:k]] = np.arange(min(k, n))
    cents = x[order[:k]].copy()
    counts = np.ones(min(k, n), np.int64)
    cat_counts = None
    if categories is not None:
        cat_counts = np.zeros((k, len(g_count)), np.int64)
        np.add.at(cat_counts, (labels[order[:k]], categories[order[:k]]), 1)

    b = 1
    while b * k < n:
        idx = order[b * k:(b + 1) * k]
        xb = x[idx]
        cost = ((xb[:, None, :] - cents[None, :, :]) ** 2).sum(-1)
        if categories is not None:
            cb = categories[idx]
            full = cat_counts[:, cb].T >= ub[cb][:, None]
            cost[full] = _MASK_COST
        rows, cols = linear_sum_assignment(cost, maximize=True)
        for r, c in zip(rows, cols):
            counts[c] += 1
            cents[c] += (xb[r] - cents[c]) / counts[c]
            labels[idx[r]] = c
            if cat_counts is not None:
                cat_counts[c, categories[idx[r]]] += 1
        b += 1
    return labels.astype(np.int32)
