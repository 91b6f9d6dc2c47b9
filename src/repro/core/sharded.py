"""Distributed ABA across the device mesh (multi-host / multi-pod).

Maps the paper's "subproblems can be solved in parallel" (Section 4.4) onto
``shard_map``: the data-parallel sharding of the dataset IS the first level of
the hierarchical decomposition.  Each data-parallel shard runs the local ABA
core on its local rows and produces ``K / n_shards`` local anticlusters;
global label = shard_offset + local label.

This is exactly the paper's multi-level scheme with a size-balanced (but not
distance-sorted) top level -- the quality impact is measured in
``benchmarks/fig7_hierarchical.py`` and is in line with the paper's Figure 7
observation that the decomposition barely moves the objective.

The mesh is an *orthogonal placement axis* of the same engine API, not a
special one-shot mode: everything the shard-local cores support composes with
the sharding --

* **streaming** (``chunk_size``): each shard runs ``repro.core.aba.aba_stream``
  over its local rows (per-shard working set O(chunk*d + k_local*d));
* **categories / valid_mask**: each shard stratifies / masks its local rows
  through the same ``aba_core`` machinery (stratification is then exact *per
  shard*; the shard level itself splits by data placement, not category);
* **warm starts** (``prices`` / ``return_state``): per-shard, per-level
  auction price stacks -- leading shard axis, laid out with
  ``jax.sharding`` -- thread through every local solve, which is what
  :class:`repro.anticluster.AnticlusterEngine` carries in its
  :class:`repro.anticluster.ShardedABAState` across ``repartition`` calls.

Used by ``repro.data`` to build diverse mini-batches for each data-parallel
group without any cross-host traffic (the collective-free fast path), by
``repro.serve`` for sharded warm lanes, and by ``launch/dryrun.py`` to lower
the ABA step on the production mesh.
"""

from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax import shard_map
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from repro.core.assignment import AuctionConfig
from repro.core.hierarchical import (default_plan, hierarchical_core,
                                     plan_price_shapes)
from repro.core.aba import aba_core, aba_stream
from repro.sharding.specs import resolve_data_axes


def sharded_price_shapes(plan: tuple[int, ...],
                         n_shards: int) -> tuple[tuple[int, ...], ...]:
    """Per-level price-stack shapes carried by a sharded session.

    Each level's per-shard shape (:func:`plan_price_shapes`) gains a leading
    shard axis: level l is ``(n_shards, prod(plan[:l-1]), plan[l-1])``.
    """
    return tuple((n_shards,) + s for s in plan_price_shapes(plan))


def sharded_core(
    x: jnp.ndarray,
    k: int,
    mesh: Mesh,
    *,
    data_axes="auto",
    max_k: int = 512,
    variant: str = "auto",
    solver: str = "auction",
    auction_config: AuctionConfig = AuctionConfig(),
    batched: bool = True,
    chunk_size: int | None = None,
    categories: jnp.ndarray | None = None,
    n_categories: int = 0,
    fair_codes: jnp.ndarray | None = None,
    n_fair_codes: int = 0,
    valid_mask: jnp.ndarray | None = None,
    prices: tuple[jnp.ndarray, ...] | None = None,
    return_state: bool = False,
):
    """Partition sharded ``x`` (n, d) into k anticlusters; returns (n,) labels.

    ``k`` must be divisible by the total data-parallel shard count, and ``n``
    by the shard count (pad the dataset and pass ``valid_mask`` if needed);
    each shard owns n/n_shards rows.  ``data_axes`` follows
    :func:`repro.sharding.specs.resolve_data_axes` -- ``"auto"`` takes
    whichever of ('pod', 'data') the mesh has, an explicit tuple is validated
    strictly (absent axes raise, naming the offenders).  ``batched`` routes
    each shard's hierarchical levels through the single-call batched auction
    engine (see ``hierarchical_core``).  ``chunk_size`` streams each shard's
    *local* full-data level through ``repro.core.aba.aba_stream`` (per-shard
    working set O(chunk_size*d + k_local*d)); the shard level itself is
    already collective-free, so streaming composes with it.

    ``categories`` (with static ``n_categories``) stratifies each shard's
    local rows exactly (Section 4.3 per shard); ``fair_codes`` /
    ``n_fair_codes`` thread the multi-attribute fairness quota codes (see
    ``aba_core``) per shard; ``valid_mask`` marks padding rows (flat
    per-shard plans only -- the hierarchy's regrouping does not carry
    masks).  All are (n,) / (n, A) vectors sharded alongside ``x``, and all
    of them *stream* when ``chunk_size`` is set (the per-shard local level
    runs the chunked categorical ``aba_stream``).

    ``prices`` warm-starts every shard's per-level auctions from a carried
    per-shard price stack (level shapes from :func:`sharded_price_shapes`;
    ``None`` -- or all-zero stacks -- is the bit-identical cold path).
    ``return_state`` additionally returns ``{"prices": per-level (S, G_l,
    k_l) tuple, "moment_sum": (S, d) per-shard feature sums over valid rows,
    "moment_count": (S,)}`` -- the carried state of a distributed session.
    """
    axes = resolve_data_axes(mesh, data_axes)
    n_shards = math.prod(mesh.shape[a] for a in axes)
    if k % n_shards:
        raise ValueError(f"k={k} must be divisible by shard count {n_shards}")
    n, d = x.shape
    if n % n_shards:
        raise ValueError(
            f"n={n} rows must be divisible by shard count {n_shards} "
            "(pad the dataset and mark the padding with valid_mask)")
    k_local = k // n_shards
    plan = default_plan(k_local, max_k=max_k)
    if valid_mask is not None and len(plan) > 1:
        raise NotImplementedError(
            f"valid_mask needs a flat per-shard plan (k/n_shards={k_local} "
            f"resolved to {plan}); raise max_k or drop the padding rows")
    if categories is not None and n_categories <= 0:
        raise ValueError("n_categories must be set with categories")
    if (not batched) and (return_state or prices is not None):
        raise NotImplementedError(
            "price/state threading requires batched=True levels")
    kw = dict(variant=variant, solver=solver, auction_config=auction_config)

    has_cats = categories is not None
    has_codes = fair_codes is not None
    has_vm = valid_mask is not None
    has_prices = prices is not None
    n_levels = len(plan)

    operands = [x]
    in_specs = [P(axes, None)]
    if has_cats:
        operands.append(jnp.asarray(categories, jnp.int32))
        in_specs.append(P(axes))
    if has_codes:
        operands.append(jnp.asarray(fair_codes, jnp.int32))
        in_specs.append(P(axes, None))
    if has_vm:
        operands.append(jnp.asarray(valid_mask, jnp.bool_))
        in_specs.append(P(axes))
    if has_prices:
        if len(prices) != n_levels:
            raise ValueError(
                f"prices carries {len(prices)} levels for a {n_levels}-level "
                f"per-shard plan {plan}")
        operands.extend(jnp.asarray(p, jnp.float32) for p in prices)
        in_specs.extend(P(axes, None, None) for _ in prices)

    def local_fn(*args):
        it = iter(args)
        x_local = next(it)
        xs = x_local.reshape((-1, x_local.shape[-1]))
        cl = next(it).reshape(-1) if has_cats else None
        fl = (next(it).reshape(-1, fair_codes.shape[-1]) if has_codes
              else None)
        vl = next(it).reshape(-1) if has_vm else None
        p_local = tuple(p[0] for p in it) if has_prices else None

        p0 = None if p_local is None else p_local[0]
        if n_levels == 1 and chunk_size is not None:
            # each shard streams its local rows -- categories / fair codes /
            # mask included (the chunked rank-in-category rearrangement
            # keeps per-shard labels bit-identical to the dense local core
            # at chunk >= n_local)
            local, st = aba_stream(xs, k_local, chunk_size,
                                   categories=cl, n_categories=n_categories,
                                   fair_codes=fl, n_fair_codes=n_fair_codes,
                                   valid_mask=vl, prices=p0,
                                   return_state=True, **kw)
            p_out, mu = (st["prices"],), st["mu"]
        elif n_levels == 1:
            local, st = aba_core(
                xs[None], k_local,
                None if vl is None else vl[None],
                categories=None if cl is None else cl[None],
                n_categories=n_categories,
                fair_codes=None if fl is None else fl[None],
                n_fair_codes=n_fair_codes, prices=p0,
                return_state=True, **kw)
            local = local[0]
            p_out, mu = (st["prices"],), st["mu"][0]
        elif batched:
            local, st = hierarchical_core(
                xs, plan, categories=cl, n_categories=n_categories,
                fair_codes=fl, n_fair_codes=n_fair_codes,
                batched=True, chunk_size=chunk_size,
                prices=p_local, return_state=True, **kw)
            p_out, mu = st["prices"], st["mu"]
        else:
            # legacy vmap-per-group levels: no state threading (benchmarks)
            local = hierarchical_core(
                xs, plan, categories=cl, n_categories=n_categories,
                batched=False, chunk_size=chunk_size, **kw)
            p_out = tuple(jnp.zeros(s, jnp.float32)
                          for s in plan_price_shapes(plan))
            mu = jnp.mean(xs, axis=0)

        offset = jnp.int32(0)
        for a in axes:
            offset = offset * mesh.shape[a] + jax.lax.axis_index(a)
        labels = (offset * k_local + local).reshape(x_local.shape[:-1])
        cnt = (jnp.asarray(float(xs.shape[0]), jnp.float32) if vl is None
               else jnp.sum(vl, dtype=jnp.float32))
        outs = (labels, tuple(p[None] for p in p_out),
                (mu * cnt)[None], cnt[None])
        return outs

    out_specs = (P(axes), tuple(P(axes, None, None) for _ in range(n_levels)),
                 P(axes, None), P(axes))
    fn = shard_map(local_fn, mesh=mesh, in_specs=tuple(in_specs),
                   out_specs=out_specs, check_vma=False)
    labels, p_out, msum, mcnt = fn(*operands)
    if return_state:
        return labels, {"prices": p_out, "moment_sum": msum,
                        "moment_count": mcnt}
    return labels


def sharded_aba(x: jnp.ndarray, k: int, mesh: Mesh, **kw):
    """Deprecated: use ``repro.anticluster.anticluster`` with ``spec.mesh``
    (one-shot) or ``repro.anticluster.AnticlusterEngine`` with a mesh spec
    (warm-startable sessions); ``sharded_core`` stays the raw jit-able
    labels."""
    from repro.core.aba import _deprecated
    _deprecated("sharded_aba",
                "repro.anticluster.anticluster(x, spec) with spec.mesh")
    return sharded_core(x, k, mesh, **kw)


def sharded_aba_lowerable(mesh: Mesh, n: int, d: int, k: int,
                          **kw):
    """(jitted fn, arg specs) for dry-run lowering of the ABA data step."""
    fn = functools.partial(sharded_core, k=k, mesh=mesh, **kw)
    axes = resolve_data_axes(mesh, kw.get("data_axes", "auto"))
    jitted = jax.jit(
        fn,
        in_shardings=NamedSharding(mesh, P(axes, None)),
    )
    spec = jax.ShapeDtypeStruct((n, d), jnp.float32)
    return jitted, spec
