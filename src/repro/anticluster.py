"""The spec-driven front door for Euclidean anticlustering.

Two public surfaces over one rank-polymorphic core:

* :func:`anticluster` -- the one-shot call.  ``anticluster(x, spec)`` is
  semantically ``AnticlusterEngine(spec).partition(x)[0]`` (a parity test
  pins the two bit-for-bit) but dispatches straight to the module-level
  jitted cores, so repeated one-shot calls share the global compile cache.

    from repro.anticluster import AnticlusterSpec, anticluster

    res = anticluster(x, AnticlusterSpec(k=500))          # flat or auto-plan
    res = anticluster(x, k=500, plan=(10, 50))            # explicit hierarchy
    res = anticluster(x, k=5, categories=y)               # stratified (4.3)
    res = anticluster(x, k=512, mesh=mesh)                # shard_map across mesh
    res.labels, res.plan, res.cluster_sizes, res.balanced # result pytree

* :class:`AnticlusterEngine` -- the session API for the paper's *repeated*
  workloads (a fresh mini-batch partition every training epoch,
  representative K-fold CV, request serving).  The engine compiles one
  shape-keyed executable per input signature (state buffers donated) and
  carries an explicit :class:`ABAState` pytree -- the auction's dual prices
  per hierarchy level, the centrality running moments, and the previous
  labels -- so ``engine.repartition(x, state)`` warm-starts every
  epsilon-scaling auction instead of re-discovering the price equilibrium
  from zero:

    engine = AnticlusterEngine(AnticlusterSpec(k=64))
    res, state = engine.partition(x)            # compiles once for x.shape
    for epoch in range(E):
        x = embed(data)                         # same shape, drifted values
        res, state = engine.repartition(x, state)   # zero retrace, warm solve

``anticluster`` routes flat -> streaming -> hierarchical -> sharded
execution from the spec alone; every regime runs on the ONE rank-polymorphic
masked core (``repro.core.aba.aba_core``) so there is exactly one
implementation of the centrality sort / padding / Algorithm-1 scan.  At
million-object scale (``chunk_size="auto"`` or an explicit int) the flat
level runs through the chunked matrix-free twin ``repro.core.aba.aba_stream``
(same per-batch step, O(chunk*d + k*d) working set, bit-identical labels
when ``chunk_size >= n``).  The LAP backend is looked up
in the solver registry (``register_solver`` / ``get_solver``), so new
backends are a registry entry, not a seventh entry point.

``anticluster`` itself is a host-level convenience (it builds the result
statistics eagerly); inside ``jit``/``scan``/``shard_map`` call the cores
directly (``aba_core`` / ``hierarchical_core`` / ``sharded_core``).
"""

from __future__ import annotations

import dataclasses
import math
import warnings
from typing import Any

import jax
import jax.numpy as jnp
import numpy as np

from repro import obs
from repro.core.aba import aba_core, aba_stream
from repro.core.assignment import (AuctionConfig, available_solvers,
                                   get_solver, register_solver)
from repro.core.hierarchical import (default_plan, hierarchical_core,
                                     plan_price_shapes)
from repro.core.kplus import kplus_augment
from repro.sharding.specs import resolve_data_axes, shard_leading

__all__ = [
    "AnticlusterSpec", "AnticlusterResult", "anticluster",
    "AnticlusterEngine", "ABAState", "ShardedABAState",
    "PendingRepartition",
    "register_solver", "get_solver", "available_solvers",
]

# Streaming auto-selection thresholds: below _AUTO_STREAM_MIN rows the dense
# core's one-shot gather is cheap and ``chunk_size="auto"`` stays flat; at or
# above it the streaming core engages with ~_AUTO_CHUNK_ROWS rows per chunk
# (rounded to a multiple of k inside ``aba_stream``), keeping the working
# set O(chunk*d + k*d) regardless of n.
_AUTO_STREAM_MIN = 1 << 16   # 65536 rows
_AUTO_CHUNK_ROWS = 1 << 13   # 8192 rows per chunk


@dataclasses.dataclass(frozen=True, eq=False)
class AnticlusterSpec:
    """Frozen configuration for :func:`anticluster`.

    Attributes:
      k: number of anticlusters (required).
      variant: "auto" | "base" | "interleave" (paper Section 4.2; "auto"
        interleaves when anticlusters are small, n/k <= 8).
      categories: optional (n,) int category labels -- Section 4.3 exact
        stratification.  Composes with hierarchy: every level stratifies
        within its groups, and the global constraint (5) still holds exactly
        (ceil/floor compose across levels, see ``repro.core.hierarchical``).
      n_categories: static category count; 0 infers it from ``categories``.
      fairness: proportional fairness over one or more protected attributes
        -- the multi-attribute generalization of constraint (5).  Takes a
        single int attribute array (exactly the ``categories=`` constraint,
        bit-for-bit), a dict / list / tuple of several, or a stacked
        ``(n, A)`` int array (last axis = attributes).  With several
        attributes the *joint* attribute cell drives the Section 4.3
        rearrangement and every cluster is capped at
        ``ceil(|N_av| / k)`` members of each attribute value ``av``
        independently, so each cluster's attribute marginals track the
        population's proportions.  Multi-attribute caps are best-effort
        where attribute transversals conflict (the LAP must place k rows in
        distinct clusters per batch; an infeasible quota combination
        overflows by at most the conflicting rows -- single-attribute
        fairness is exact).  Mutually exclusive with ``categories=``;
        streams, shards and composes everywhere categories do.
      solver: LAP backend name in the solver registry ("auction",
        "auction_fused", "greedy", "scipy", or anything you
        ``register_solver``-ed).
      auction_config: epsilon-scaling schedule for the auction backends.
      plan: hierarchy plan (Section 4.4).  ``"auto"`` factorizes k with
        ``default_plan`` (every factor <= ``max_k``); a tuple is used as-is
        (must multiply to k); ``None`` forces the flat single-level path.
      chunk_size: streaming execution (million-scale path).  ``None`` keeps
        the dense one-shot core; an int streams the centrality-sorted object
        list through ``repro.core.aba.aba_stream`` in chunks of that many
        rows (peak live memory O(chunk_size*d + k*d) beyond the input);
        ``"auto"`` streams only at scale (n >= 65536 rows, ~8192-row chunks)
        and additionally upgrades the default "auction" solver to
        "auction_fused" so each batch LAP is matrix-free (the (k, k) value
        matrix is never built -- the paper's Tables 8/10 operating range).
        Applies to the flat path, the first (full-data) hierarchical level,
        and each shard's local solve under ``mesh``.  Categories, fairness
        and valid_mask all stream (the Section 4.3 rearrangement runs as a
        single chunked rank-in-category pass, the quota counts ride the
        assignment scan); only stacked (G, M, D) input stays dense -- an
        explicit int raises there, ``"auto"`` falls back with a
        ``RuntimeWarning`` (once per route) naming the reason.  With
        ``chunk_size >= n`` labels are bit-for-bit identical to the dense
        path.
      max_k: largest admissible LAP size for the auto plan.
      mesh: optional ``jax.sharding.Mesh`` -- an orthogonal *placement* axis
        of the same API, not a separate mode: execution routes through
        ``shard_map`` (the data sharding becomes the first hierarchy level),
        composing with streaming (each shard runs ``aba_stream`` on its
        local rows), categories / valid_mask (each shard stratifies / masks
        its local rows; the mask needs a flat per-shard plan), and the
        engine's warm starts (:class:`ShardedABAState`).  ``k`` and ``n``
        must be divisible by the shard count of ``data_axes``.
      data_axes: mesh axes that shard the data.  ``"auto"`` (default) takes
        whichever of ('pod', 'data') exist on the mesh; an explicit tuple is
        validated strictly -- naming an axis the mesh does not have raises
        with the offending names instead of silently dropping them.
      valid_mask: optional bool mask marking padding rows (shape of labels);
        masked rows get arbitrary labels in [0, k).
      kplus_moments: >= 2 augments features with standardized centered
        moments (k-plus, Section 3.3) before clustering; flat unmasked
        (n, d) input only.
      dtype: feature dtype fed to the core (the core computes in float32).
      batched: False switches hierarchical levels to the legacy vmap of
        per-group solves (identical labels; exists for benchmarking).
      stats: False skips the diversity statistics (sd/range report 0) so
        timed benchmark windows measure only the solve + cluster sizes.
        ``stats=True`` additionally surfaces the auction duals as an
        optimality-gap certificate (``AnticlusterResult.dual_bound`` /
        ``gap``; meshless modes only, computed outside any timed path).
      update_threshold: largest delta fraction ``(added + removed) / n_new``
        that :meth:`AnticlusterEngine.update` absorbs incrementally via the
        restricted frozen-price auction; a larger delta falls back -- loudly,
        with a ``RuntimeWarning`` -- to a full warm ``repartition``
        (bit-for-bit identical to calling ``repartition`` on the post-delta
        data with the carried prices).
      telemetry: surface the auction solver's internals (rounds per eps
        phase, the eps schedule, warm re-entry decisions) from the compiled
        path: the engine's result carries the stacked per-batch stats
        pytree (``AnticlusterEngine.last_telemetry``; converted to NumPy at
        ``wait()``, outside any timed window) and a traced run
        (``repro.obs``) records per-phase ``solver/phase`` events.  Flat,
        stream, and stacked routes report; hierarchical and mesh routes
        report ``None`` (their per-level/per-shard solves are not
        stitchable into one batch axis).  Solvers without a registered
        stats twin (greedy, scipy) report ``None`` as well.  The flag is a
        static part of the compiled signature: ``telemetry=False`` (the
        default) leaves every executable byte-identical -- observability
        never taxes the default path.
    """

    k: int
    variant: str = "auto"
    categories: Any = None
    n_categories: int = 0
    fairness: Any = None
    solver: str = "auction"
    auction_config: AuctionConfig = AuctionConfig()
    plan: Any = "auto"
    chunk_size: Any = None
    max_k: int = 512
    mesh: Any = None
    data_axes: Any = "auto"
    valid_mask: Any = None
    kplus_moments: int = 1
    dtype: Any = jnp.float32
    batched: bool = True
    stats: bool = True
    update_threshold: float = 0.25
    telemetry: bool = False

    def __post_init__(self):
        if self.k < 1:
            raise ValueError(f"k={self.k} must be >= 1")
        if not 0.0 <= self.update_threshold <= 1.0:
            raise ValueError(
                f"update_threshold={self.update_threshold} must be in "
                "[0, 1] (the delta fraction above which update() falls "
                "back to a full repartition)")
        if isinstance(self.plan, tuple) and math.prod(self.plan) != self.k:
            raise ValueError(
                f"prod(plan)={math.prod(self.plan)} != k={self.k}")
        if self.plan is not None and not isinstance(self.plan, tuple) \
                and self.plan != "auto":
            raise ValueError(f'plan must be "auto", a tuple, or None; '
                             f"got {self.plan!r}")
        if self.chunk_size is not None and self.chunk_size != "auto" and \
                (not isinstance(self.chunk_size, int)
                 or self.chunk_size < 1):
            raise ValueError(f'chunk_size must be None, "auto", or a '
                             f"positive int; got {self.chunk_size!r}")
        if self.fairness is not None:
            if self.categories is not None:
                raise ValueError(
                    "categories= and fairness= are mutually exclusive "
                    "(single-attribute fairness IS the categories= "
                    "constraint -- pass just one of them)")
            _fairness_attrs(self.fairness)  # validate shape/dtype up front

    def evolve(self, **changes) -> "AnticlusterSpec":
        """A new spec with ``changes`` applied -- the supported public
        alternative to raw ``dataclasses.replace``.

        Validates the *field names* up front (an unknown name raises
        ``TypeError`` listing the valid fields, instead of
        ``dataclasses.replace``'s bare complaint) and re-runs the frozen
        spec's ``__post_init__`` checks (k/plan consistency, chunk_size
        domain) on the evolved value.  Every keyword-``overrides`` surface
        in the repo (``anticluster(x, spec, **ov)``,
        ``AnticlusterEngine(spec, **ov)``, the serving tier, the
        folds/minibatch spec derivation) routes through here, so "spec +
        overrides" means exactly one thing everywhere.
        """
        if not changes:
            return self
        valid = {f.name for f in dataclasses.fields(self)}
        unknown = sorted(set(changes) - valid)
        if unknown:
            raise TypeError(
                f"unknown AnticlusterSpec field(s) {unknown}; valid fields "
                f"are {sorted(valid)}")
        return dataclasses.replace(self, **changes)

    def replace(self, **overrides) -> "AnticlusterSpec":
        """Back-compat alias of :meth:`evolve` (same validation)."""
        return self.evolve(**overrides)

    def resolve_plan(self) -> tuple[int, ...]:
        """The concrete per-device hierarchy plan this spec dispatches to."""
        if self.plan is None:
            return (self.k,)
        if isinstance(self.plan, tuple):
            return self.plan
        k = self.k
        if self.mesh is not None:
            n_shards = _mesh_shards(self)
            if k % n_shards:
                raise ValueError(
                    f"k={k} must be divisible by shard count {n_shards}")
            k = k // n_shards
        return default_plan(k, max_k=self.max_k)

    def resolve_chunk(self, n: int, k: int) -> int | None:
        """Concrete per-level chunk size for ``n`` rows, or None (dense).

        ``k`` is the level's anticluster count (the chunk is rounded to a
        multiple of it inside ``aba_stream``); "auto" engages only when the
        level is large enough for chunking to pay for itself.
        """
        if self.chunk_size is None:
            return None
        if self.chunk_size == "auto":
            if n < _AUTO_STREAM_MIN:
                return None
            return max(k, _AUTO_CHUNK_ROWS)
        return int(self.chunk_size)


@dataclasses.dataclass(frozen=True)
class AnticlusterResult:
    """Labels plus the resolved execution plan and quality statistics.

    A pytree: ``labels`` / ``cluster_sizes`` / ``diversity_sd`` /
    ``diversity_range`` / ``dual_bound`` / ``gap`` are leaves, the resolved
    ``plan`` and the spec echoes (``k``, ``solver``, ``variant``) plus the
    ``updated`` provenance flag are static metadata.  For stacked (G, M, D)
    inputs every field carries the leading group axis.

    ``dual_bound`` / ``gap`` (``spec.stats=True``, meshless modes) are the
    LP-dual optimality certificate built from the auction's carried duals
    (see :func:`repro.core.objective.dual_certificate`): ``dual_bound``
    upper-bounds the best assignment objective at the realized centroids and
    ``gap >= 0`` is its relative distance from the achieved objective --
    near-zero certifies the assignment step converged.  ``None`` when stats
    are off, under a mesh, or for zero-price (non-auction) solves where only
    the trivial bound is available (still reported -- it is valid for any
    prices, just loose).

    ``updated`` is True only for results produced by the incremental path of
    :meth:`AnticlusterEngine.update` (the restricted frozen-price auction);
    full solves -- including update()'s loud over-threshold fallback --
    report False.
    """

    labels: jnp.ndarray          # (n,) or (G, M) int32 in [0, k)
    cluster_sizes: jnp.ndarray   # (k,) or (G, k) int32 (valid rows only)
    diversity_sd: jnp.ndarray    # () or (G,) std of per-cluster diversity
    diversity_range: jnp.ndarray  # () or (G,) max - min of the same
    k: int = 1
    plan: tuple[int, ...] = ()
    solver: str = "auction"
    variant: str = "auto"
    dual_bound: Any = None       # () or (G,) LP-dual bound (stats=True)
    gap: Any = None              # () or (G,) relative optimality gap
    updated: bool = False        # True only for incremental update() results

    @property
    def n_valid(self):
        """Number of non-padding rows (per group for stacked inputs)."""
        return np.asarray(self.cluster_sizes).sum(axis=-1)

    @property
    def balanced(self) -> bool:
        """Constraint (2): all sizes in {floor(n/k), ceil(n/k)} (Prop. 1)."""
        sizes = np.asarray(self.cluster_sizes)
        n = sizes.sum(axis=-1, keepdims=True)
        return bool(np.all(sizes >= n // self.k)
                    and np.all(sizes <= -(-n // self.k)))


jax.tree_util.register_dataclass(
    AnticlusterResult,
    data_fields=["labels", "cluster_sizes", "diversity_sd",
                 "diversity_range", "dual_bound", "gap"],
    meta_fields=["k", "plan", "solver", "variant", "updated"])


@dataclasses.dataclass(frozen=True)
class ABAState:
    """The carried solver state of one anticlustering session.

    A pure-array pytree (jit/``device_put``/pickle-safe; every field is a
    leaf, there is no static metadata), produced by
    ``AnticlusterEngine.partition`` / ``repartition`` and consumed by
    ``repartition`` to warm-start the next same-shape solve:

    * ``prices`` -- the auction's dual price vectors, one per hierarchy
      level (level l is ``(prod(plan[:l-1]), plan[l-1])`` float32; flat,
      streamed and stacked runs carry a 1-tuple).  These are shift-invariant
      (the engine re-centers them per group), and a zeroed tuple is exactly
      the cold start: ``repartition`` with ``init_state``'s zeros is
      bit-identical to ``partition``.
    * ``moment_sum`` / ``moment_count`` -- the running centrality moments
      (per-group feature sums and valid-row counts) behind the level-1
      centrality sort; mergeable across sessions the way ``aba_stream``
      merges its chunk moments.
    * ``prev_labels`` -- the previous assignment ((n,) or (G, M) int32;
      ``-1`` before the first partition).
    """

    prices: tuple[jnp.ndarray, ...]
    moment_sum: jnp.ndarray
    moment_count: jnp.ndarray
    prev_labels: jnp.ndarray


jax.tree_util.register_dataclass(
    ABAState,
    data_fields=["prices", "moment_sum", "moment_count", "prev_labels"],
    meta_fields=[])


@dataclasses.dataclass(frozen=True)
class ShardedABAState:
    """The carried state of a *distributed* anticlustering session.

    The mesh twin of :class:`ABAState` -- same role, per-shard layout.  A
    pure-array pytree produced/consumed by an :class:`AnticlusterEngine`
    whose spec carries a ``mesh``; every leaf shards its **leading axis**
    across the spec's data axes (``jax.sharding.NamedSharding``, see
    ``AnticlusterEngine.state_shardings``), so ``repartition`` threads it
    straight through one ``shard_map`` executable with zero resharding:

    * ``prices`` -- per-shard, per-level auction dual price stacks: level l
      of the per-shard plan is ``(n_shards, prod(plan[:l-1]), plan[l-1])``
      float32.  A zeroed tuple is exactly the cold start (bit-identical to
      the one-shot ``anticluster(x, spec)`` mesh path).
    * ``moment_sum`` / ``moment_count`` -- (n_shards, d) per-shard feature
      sums over valid rows and (n_shards,) valid-row counts (the shard-local
      centrality moments; summing over the shard axis gives the global
      moments an :class:`ABAState` would carry).
    * ``prev_labels`` -- the previous global assignment ((n,) int32,
      row-sharded; ``-1`` before the first partition).
    """

    prices: tuple[jnp.ndarray, ...]
    moment_sum: jnp.ndarray
    moment_count: jnp.ndarray
    prev_labels: jnp.ndarray


jax.tree_util.register_dataclass(
    ShardedABAState,
    data_fields=["prices", "moment_sum", "moment_count", "prev_labels"],
    meta_fields=[])


def _resolve_spec(spec: "AnticlusterSpec | None",
                  overrides: dict) -> "AnticlusterSpec":
    """The one "spec or keyword overrides" rule every front door shares.

    ``None`` builds a fresh spec from the overrides; an existing spec is
    evolved through the validated :meth:`AnticlusterSpec.evolve`.
    """
    if spec is None:
        return AnticlusterSpec(**overrides)
    return spec.evolve(**overrides)


def _mesh_shards(spec: "AnticlusterSpec") -> int:
    """Total data-parallel shard count for the spec's mesh (1 if no mesh).

    Validates ``spec.data_axes`` against the mesh: explicit axes absent from
    the mesh raise (with the offending names) instead of being dropped.
    """
    if spec.mesh is None:
        return 1
    axes = resolve_data_axes(spec.mesh, spec.data_axes)
    return math.prod(spec.mesh.shape[a] for a in axes)


def _fairness_attrs(fairness) -> list:
    """Normalize ``AnticlusterSpec.fairness`` to a list of integer attribute
    arrays (one per protected attribute), validating as it goes.

    Accepted forms: a dict (attribute name -> codes; insertion order), a
    list/tuple of arrays, a single 1-D array/sequence, or a stacked 2-D
    ``(n, A)`` array whose last axis is the attribute axis.  (For stacked
    (G, M, D) inputs pass a list/dict of (G, M) arrays -- a bare 2-D array
    is always read as (n, A).)
    """
    if isinstance(fairness, dict):
        items = list(fairness.values())
    elif isinstance(fairness, (list, tuple)):
        items = list(fairness)
        if items and np.ndim(items[0]) == 0:
            items = [fairness]  # one attribute given as a plain sequence
    else:
        arr = np.asarray(fairness)
        items = ([arr[..., a] for a in range(arr.shape[-1])]
                 if arr.ndim == 2 else [arr])
    if not items:
        raise ValueError("fairness= needs at least one attribute")
    attrs = []
    for a, item in enumerate(items):
        arr = np.asarray(item)
        if not np.issubdtype(arr.dtype, np.integer):
            raise ValueError(
                f"fairness attribute {a} must be integer-coded, got dtype "
                f"{arr.dtype} (encode the levels as 0..C-1)")
        if arr.size and int(arr.min()) < 0:
            raise ValueError(f"fairness attribute {a} has negative codes")
        if attrs and arr.shape != attrs[0].shape:
            raise ValueError(
                f"fairness attributes disagree on shape: {arr.shape} vs "
                f"{attrs[0].shape}")
        attrs.append(arr)
    return attrs


def _resolve_constraints(spec: "AnticlusterSpec"):
    """``(categories, n_categories, fair_codes, n_fair_codes)`` as the cores
    take them, from either ``spec.categories`` or ``spec.fairness``.

    One attribute (or plain ``categories=``) resolves to the exact
    constraint-(5) path (``fair_codes`` stays None -- bit-for-bit the
    categorical core).  Several attributes resolve to the *joint* mixed-radix
    cell as the rearrangement category plus per-attribute offset codes into
    one shared ``sum(C_a)``-wide quota axis (see ``aba_core``'s
    ``fair_codes``).
    """
    if spec.fairness is None:
        cats = spec.categories
        n_categories = spec.n_categories
        if cats is not None:
            cats = jnp.asarray(cats, jnp.int32)
            if n_categories <= 0:
                n_categories = int(np.asarray(cats).max()) + 1
        return cats, n_categories, None, 0
    attrs = _fairness_attrs(spec.fairness)
    sizes = [int(a.max()) + 1 if a.size else 1 for a in attrs]
    if len(attrs) == 1:
        # one attribute degenerates to the exact categories= constraint
        return jnp.asarray(attrs[0], jnp.int32), sizes[0], None, 0
    joint = np.zeros(attrs[0].shape, np.int64)
    for a, s in zip(attrs, sizes):
        joint = joint * s + a
    offs = np.concatenate([[0], np.cumsum(sizes)[:-1]])
    codes = np.stack([a + o for a, o in zip(attrs, offs)], axis=-1)
    return (jnp.asarray(joint, jnp.int32), int(np.prod(sizes)),
            jnp.asarray(codes, jnp.int32), int(sum(sizes)))


_WARNED_FALLBACKS: set = set()


def _warn_dense_fallback(key, msg: str) -> None:
    """RuntimeWarning (once per route key) for a silent-degradation point.

    Streaming fallbacks change *memory*, not labels, so they warn instead of
    raising -- but only once per distinct route, so a per-epoch engine loop
    does not spam.  docs/ARCHITECTURE.md's fallback matrix lists every
    caller.
    """
    if key in _WARNED_FALLBACKS:
        return
    _WARNED_FALLBACKS.add(key)
    warnings.warn(msg, RuntimeWarning, stacklevel=3)


def _route(spec: AnticlusterSpec, shape: tuple[int, ...],
           has_categories: bool, has_valid_mask: bool):
    """Static dispatch decisions shared by ``anticluster()`` and the engine.

    Returns ``(mode, plan, solver, chunk)``: ``mode`` in ``"mesh"`` |
    ``"stacked"`` | ``"hier"`` | ``"stream"`` | ``"flat"``; ``solver`` the
    resolved registry name (the at-scale auto upgrade applied); ``chunk``
    the concrete per-level row count or None.  One function, so the engine
    and the one-shot wrapper can never disagree on the execution route.
    """
    if len(shape) not in (2, 3):
        raise ValueError(f"x must be (n, d) or (G, M, D), got {shape}")
    plan = spec.resolve_plan()
    streamable = len(shape) == 2  # categories/fairness/valid_mask all stream
    if spec.chunk_size is not None and not streamable \
            and spec.chunk_size != "auto":
        raise NotImplementedError(
            "chunk_size streaming needs flat (n, d) input; stacked "
            '(G, M, D) batches stay dense (chunk_size="auto" falls back '
            "loudly) -- split the groups into flat calls to stream them")
    if spec.chunk_size is not None and len(shape) == 3 \
            and shape[1] >= _AUTO_STREAM_MIN:
        _warn_dense_fallback(
            ("stacked", shape[1]),
            f"chunk_size streaming does not apply to stacked (G, M, D) "
            f"input; running the dense core on {shape} (split the groups "
            "into flat anticluster() calls to stream them)")

    def chunk_for(n_level: int, k_level: int) -> int | None:
        return spec.resolve_chunk(n_level, k_level) if streamable else None

    n = shape[0]
    solver = spec.solver
    if spec.chunk_size == "auto" and solver == "auction" and streamable \
            and not has_categories:
        # (with categories the quota mask can't be factored -- _assign_batch
        # would fall back to the fused solver's dense solve anyway, so the
        # plain auction stays the stratified default)
        n_level = n // max(_mesh_shards(spec), 1)
        if chunk_for(n_level, plan[0]) is not None:
            # at scale the matrix-free factored auction is the default engine
            solver = "auction_fused"

    if spec.mesh is not None:
        if len(shape) != 2:
            raise NotImplementedError(
                "mesh execution takes flat (n, d) data (shards are the "
                "first hierarchy level); stack the groups yourself or drop "
                "the mesh")
        if spec.plan != "auto":
            raise NotImplementedError(
                'mesh execution resolves its per-shard plan from max_k; '
                'use plan="auto"')
        n_shards = _mesh_shards(spec)
        if n % max(n_shards, 1):
            raise ValueError(
                f"n={n} rows must be divisible by the mesh shard count "
                f"{n_shards} (pad the dataset and mark the padding with "
                "valid_mask)")
        if has_valid_mask and len(plan) > 1:
            raise NotImplementedError(
                f"valid_mask under a mesh needs a flat per-shard plan (got "
                f"{plan}); raise max_k or drop the padding rows")
        return "mesh", plan, solver, chunk_for(n // max(n_shards, 1), plan[0])
    if len(shape) == 3:
        if len(plan) > 1:
            raise NotImplementedError(
                "stacked (G, M, D) input requires a flat plan "
                f"(got plan={plan}); hierarchy nests via repeated calls")
        return "stacked", plan, solver, None
    if len(plan) > 1:
        if has_valid_mask:
            raise NotImplementedError(
                "hierarchical plans do not support valid_mask; drop the "
                "padding rows instead")
        return "hier", plan, solver, chunk_for(n, plan[0])
    chunk = chunk_for(n, spec.k)
    return ("stream" if chunk is not None else "flat"), plan, solver, chunk


def _call_core(x, spec: AnticlusterSpec, mode: str, plan, solver: str,
               chunk, cats, n_categories: int, vm, codes=None,
               n_codes: int = 0, prices=None, return_state: bool = False,
               telemetry: bool = False):
    """Dispatch one solve to the right core (shared engine/one-shot path).

    ``prices`` is the per-level tuple from :class:`ABAState` (flat /
    streamed / stacked runs use a 1-tuple) or, in mesh mode, the per-shard
    stacks from :class:`ShardedABAState`; ``None`` is the cold path and is
    bit-identical.  ``codes`` / ``n_codes`` are the multi-attribute fairness
    quota codes from :func:`_resolve_constraints` (None for plain categories
    / single-attribute fairness).  With ``return_state`` the return is
    ``(labels, state)`` where ``state["prices"]`` is the per-level tuple and
    ``state["mu"]`` the level-1 centrality centroid ((d,); (G, d) for
    stacked input) -- except in mesh mode, where the state carries the
    per-shard moments directly (``"moment_sum"`` (S, d) /
    ``"moment_count"`` (S,)).

    ``telemetry`` (static, requires ``return_state``) adds a ``"telemetry"``
    key to the state dict: the solver's per-batch stats pytree for the
    flat / stream / stacked routes, ``None`` for hier / mesh (their
    per-level / per-shard solves have no single batch axis) and for
    solvers without a stats twin.
    """
    kw = dict(variant=spec.variant, solver=solver,
              auction_config=spec.auction_config)
    if mode == "mesh":
        from repro.core.sharded import sharded_core
        out = sharded_core(
            x, spec.k, spec.mesh, data_axes=spec.data_axes,
            max_k=spec.max_k, batched=spec.batched, chunk_size=chunk,
            categories=cats, n_categories=n_categories,
            fair_codes=codes, n_fair_codes=n_codes, valid_mask=vm,
            prices=prices, return_state=return_state, **kw)
        if return_state and telemetry:
            out[1]["telemetry"] = None  # per-shard solves: no batch axis
        return out
    p0 = None if prices is None else prices[0]
    if mode == "stacked":
        out = aba_core(x, spec.k, vm, categories=cats,
                       n_categories=n_categories, fair_codes=codes,
                       n_fair_codes=n_codes, prices=p0,
                       return_state=return_state, telemetry=telemetry, **kw)
        if not return_state:
            return out
        labels, st = out
        state = {"prices": (st["prices"],), "mu": st["mu"]}
        if telemetry:
            state["telemetry"] = st["telemetry"]
        return labels, state
    if mode == "hier":
        out = hierarchical_core(x, plan, categories=cats,
                                n_categories=n_categories,
                                fair_codes=codes, n_fair_codes=n_codes,
                                batched=spec.batched, chunk_size=chunk,
                                prices=prices, return_state=return_state,
                                **kw)
        if return_state and telemetry:
            out[1]["telemetry"] = None  # per-level solves: no batch axis
        return out
    if mode == "stream":
        out = aba_stream(x, spec.k, chunk, categories=cats,
                         n_categories=n_categories, fair_codes=codes,
                         n_fair_codes=n_codes, valid_mask=vm, prices=p0,
                         return_state=return_state, telemetry=telemetry,
                         **kw)
        if not return_state:
            return out
        labels, st = out
        state = {"prices": (st["prices"],), "mu": st["mu"]}
        if telemetry:
            state["telemetry"] = st["telemetry"]
        return labels, state
    # flat: the G=1 specialization of the stacked core
    out = aba_core(x[None], spec.k, None if vm is None else vm[None],
                   categories=None if cats is None else cats[None],
                   n_categories=n_categories,
                   fair_codes=None if codes is None else codes[None],
                   n_fair_codes=n_codes, prices=p0,
                   return_state=return_state, telemetry=telemetry, **kw)
    if not return_state:
        return out[0]
    labels, st = out
    state = {"prices": (st["prices"],), "mu": st["mu"][0]}
    if telemetry:
        state["telemetry"] = st["telemetry"]
    return labels[0], state


def _result_stats(x, labels, k, valid_mask, diversity=True):
    """Masked per-group (sizes, diversity sd, diversity range).

    The masked/grouped generalization of ``repro.core.objective``'s
    ``cluster_sizes`` / ``diversity_stats`` (which stay the flat fast path);
    a drift guard in tests/test_anticluster.py pins the two to each other.
    """
    squeeze = x.ndim == 2
    if squeeze:
        x, labels = x[None], labels[None]
        valid_mask = None if valid_mask is None else valid_mask[None]
    G, M, D = x.shape
    w = (jnp.ones((G, M), jnp.float32) if valid_mask is None
         else valid_mask.astype(jnp.float32))
    seg = labels + k * jnp.arange(G, dtype=labels.dtype)[:, None]
    seg = jnp.where(w > 0, seg, G * k)  # padding rows -> dump segment
    sizes = jax.ops.segment_sum(
        w.reshape(-1), seg.reshape(-1), num_segments=G * k + 1
    )[:G * k].reshape(G, k).astype(jnp.int32)
    if not diversity:
        zero = jnp.zeros((G,), jnp.float32)
        return (sizes[0], zero[0], zero[0]) if squeeze else (sizes, zero,
                                                             zero)
    sums = jax.ops.segment_sum(
        (x * w[..., None]).reshape(-1, D), seg.reshape(-1),
        num_segments=G * k + 1)[:G * k].reshape(G, k, D)
    mu = sums / jnp.maximum(sizes, 1).astype(jnp.float32)[..., None]
    sq = jnp.sum((x - jnp.take_along_axis(
        mu, labels[..., None], axis=1)) ** 2, axis=-1) * w
    div = jax.ops.segment_sum(
        sq.reshape(-1), seg.reshape(-1), num_segments=G * k + 1
    )[:G * k].reshape(G, k)
    sd = jnp.std(div, axis=1)
    rng = jnp.max(div, axis=1) - jnp.min(div, axis=1)
    if squeeze:
        return sizes[0], sd[0], rng[0]
    return sizes, sd, rng


def _cluster_prices(prices: tuple, mode: str):
    """Per-global-cluster duals from a carried per-level price tuple.

    Flat/streamed runs carry a ``(1, k)`` 1-tuple; hierarchical runs a
    per-level tuple whose *last* level is ``(prod(plan[:-1]), k_last)`` --
    global labels compose as ``g * k_last + sub`` (see
    ``repro.core.hierarchical``), so a row-major reshape is exactly
    global-cluster order.  Stacked runs keep their ``(G, k)`` group axis.
    Prices are re-centered per group first (idempotent for engine states,
    which are already re-centered; the duals are shift-invariant).
    """
    last = prices[-1]
    last = last - jnp.max(last, axis=-1, keepdims=True)
    return last if mode == "stacked" else last.reshape(-1)


def _certificate(x, labels, prices: tuple, mode: str, k: int, vm):
    """(dual_bound, gap) from the carried duals, or (None, None) under mesh.

    The mesh path's per-shard price stacks index shard-local clusters; the
    global gather is a follow-up -- every other mode reports the
    certificate (see ``repro.core.objective.dual_certificate``).
    """
    if mode == "mesh" or prices is None:
        return None, None
    from repro.core.objective import dual_certificate
    return dual_certificate(x, labels, _cluster_prices(prices, mode), k,
                            valid_mask=vm)


def _mesh_pad_rows(spec: AnticlusterSpec, shape: tuple[int, ...],
                   has_mask: bool) -> int:
    """Zero rows the mesh path auto-pads for ``n % n_shards != 0``.

    The padding rides the per-call ``valid_mask`` path (padding rows are
    masked out and the result is sliced back to ``n``), so it is only
    available when the caller brings no mask of their own -- with a user
    mask present the explicit divisibility error in ``_route`` stands (the
    two mask sources cannot compose).
    """
    if spec.mesh is None or len(shape) != 2 or has_mask:
        return 0
    return (-shape[0]) % max(_mesh_shards(spec), 1)


def anticluster(x, spec: AnticlusterSpec | None = None,
                **overrides) -> AnticlusterResult:
    """Partition ``x`` into ``spec.k`` anticlusters per the spec.

    The one-shot form of the session API: equivalent to
    ``AnticlusterEngine(spec).partition(x)[0]`` (bit-for-bit -- both sides
    run the same ``_route``/``_call_core`` dispatch with cold prices) but
    calling the module-level jitted cores directly, so repeated one-shot
    calls share the global compile cache instead of building per-session
    executables.  Use :class:`AnticlusterEngine` when you call repeatedly on
    same-shaped data and want warm-started prices + donated state buffers.

    Args:
      x: (n, d) features, or a stacked (G, M, D) batch of padded subproblems
        (pair with ``spec.valid_mask``; the stacked rank requires a flat
        plan -- hierarchy inside each group is not supported).
      spec: an :class:`AnticlusterSpec`; keyword ``overrides`` are applied on
        top (or used alone: ``anticluster(x, k=10)``).

    Returns:
      :class:`AnticlusterResult` with labels, the resolved plan, per-cluster
      sizes and diversity statistics.
    """
    spec = _resolve_spec(spec, overrides)

    x = jnp.asarray(x)
    if x.ndim not in (2, 3):
        raise ValueError(f"x must be (n, d) or (G, M, D), got {x.shape}")
    if spec.kplus_moments > 1:
        if x.ndim != 2 or spec.valid_mask is not None:
            raise NotImplementedError(
                "kplus_moments needs flat unmasked (n, d) input (the moment "
                "statistics are computed over the row axis)")
        x = jnp.asarray(kplus_augment(np.asarray(x), spec.kplus_moments))
    x = x.astype(spec.dtype)

    cats, n_categories, codes, n_codes = _resolve_constraints(spec)
    vm = None if spec.valid_mask is None else jnp.asarray(
        spec.valid_mask, jnp.bool_)
    get_solver(spec.solver)  # fail fast with the registered-name list

    n_rows = x.shape[0]
    pad = _mesh_pad_rows(spec, tuple(x.shape), vm is not None)
    x_solve, vm_solve, cats_solve, codes_solve = x, vm, cats, codes
    if pad:
        x_solve = jnp.concatenate(
            [x, jnp.zeros((pad, x.shape[1]), x.dtype)])
        vm_solve = jnp.concatenate([jnp.ones((n_rows,), jnp.bool_),
                                    jnp.zeros((pad,), jnp.bool_)])
        if cats is not None:  # padding rows draw an arbitrary stratum
            cats_solve = jnp.concatenate(
                [cats, jnp.zeros((pad,), jnp.int32)])
        if codes is not None:
            codes_solve = jnp.concatenate(
                [codes, jnp.zeros((pad, codes.shape[-1]), jnp.int32)])
    mode, plan, solver, chunk = _route(spec, tuple(x_solve.shape),
                                       cats is not None,
                                       vm_solve is not None)

    want_state = spec.stats and mode != "mesh"
    with obs.span("anticluster", shape=tuple(x_solve.shape), mode=mode,
                  solver=solver, k=spec.k):
        out = _call_core(x_solve, spec, mode, plan, solver, chunk,
                         cats_solve, n_categories, vm_solve,
                         codes=codes_solve, n_codes=n_codes,
                         return_state=want_state)
        labels, st = out if want_state else (out, None)
        # Finish the label computation before dispatching the statistics
        # ops: host-callback solvers (e.g. "scipy") deadlock on CPU if new
        # work is enqueued while their callback computation is still in
        # flight.  (examples/scipy_deadlock_repro.py demonstrates the hang
        # this guard prevents;
        # tests/test_anticluster.py::test_scipy_solver_stats_no_deadlock
        # pins it.)
        labels = jax.block_until_ready(labels)
    if mode == "mesh":
        n_shards = _mesh_shards(spec)
        plan = ((n_shards,) + plan) if n_shards > 1 else plan
    if pad:
        labels = labels[:n_rows]
    sizes, sd, rng = _result_stats(x, labels, spec.k, vm,
                                   diversity=spec.stats)
    bound, gap = (None, None) if st is None else _certificate(
        x, labels, st["prices"], mode, spec.k, vm)
    return AnticlusterResult(
        labels=labels, cluster_sizes=sizes, diversity_sd=sd,
        diversity_range=rng, k=spec.k, plan=plan, solver=solver,
        variant=spec.variant, dual_bound=bound, gap=gap)


class AnticlusterEngine:
    """Device-resident, warm-startable session API for repeated solves.

    One engine per repeated workload (a training run's per-epoch mini-batch
    partitions, a CV harness, a serving lane).  The engine builds ONE
    jit-compiled executable per input signature ``(shape, dtype)`` --
    verified by :attr:`compile_count` staying at 1 across same-shape epochs
    -- with the incoming :class:`ABAState` buffers donated (on backends that
    support donation the old state's memory is reused in place), and keeps
    the result *statistics* out of the compiled path (they are host-level
    conveniences, skippable via ``spec.stats=False``).

    ``partition(x)`` is the cold start: it runs with a zeroed state and is
    bit-for-bit identical to ``anticluster(x, spec)``.  ``repartition(x,
    state)`` threads the carried state through the cores: every batch LAP at
    every hierarchy level warm-starts its epsilon-scaling schedule from the
    previous run's final prices, which is where the paper's repeated
    workloads (Section 1) recover their throughput -- the assignment stays
    eps-optimal (warm prices change round counts, not the optimality
    guarantee), and the objective stays within the auction's usual tolerance
    of the cold solve.

    A spec with a ``mesh`` makes the session *distributed*: the engine
    compiles ONE ``shard_map``-based executable (per input signature) whose
    state is a :class:`ShardedABAState` -- per-shard, per-level price stacks
    laid out with ``jax.sharding.NamedSharding`` over the spec's data axes
    (see :meth:`state_shardings`) -- so warm-started repartitioning runs
    collective-free across the mesh with zero retraces and zero resharding,
    and a zeroed sharded state reproduces the one-shot mesh path bit for
    bit.  Everything the shard-local core supports composes: streaming
    (``chunk_size``), categories, valid_mask (flat per-shard plans).

    Not supported here (use the one-shot :func:`anticluster`):
    ``spec.kplus_moments > 1`` (host-side feature augmentation),
    ``spec.batched=False`` (legacy benchmarking path).
    """

    _donation_advisory_silenced = False

    def __init__(self, spec: AnticlusterSpec | None = None, **overrides):
        # Engines always request state-buffer donation; the CPU backend
        # cannot honor it and emits an advisory per executable.  Install the
        # filter once, process-wide -- a per-call warnings.catch_warnings()
        # would mutate global filter state on every repartition and race
        # under threaded serving.  Only on CPU: on an accelerator the
        # advisory means a donation really failed, and stays loud.
        if not AnticlusterEngine._donation_advisory_silenced \
                and jax.default_backend() == "cpu":
            warnings.filterwarnings(
                "ignore", message="Some donated buffers were not usable")
            AnticlusterEngine._donation_advisory_silenced = True
        spec = _resolve_spec(spec, overrides)
        if spec.mesh is not None:
            _mesh_shards(spec)  # fail fast on bad data_axes / mesh
        if spec.kplus_moments > 1:
            raise NotImplementedError(
                "kplus_moments augmentation is host-side; use the one-shot "
                "anticluster()")
        if not spec.batched:
            raise NotImplementedError(
                "the engine requires the batched level engine "
                "(spec.batched=True)")
        get_solver(spec.solver)  # fail fast
        self.spec = spec
        (self._cats, self._n_categories,
         self._codes, self._n_codes) = _resolve_constraints(spec)
        self._vm = (None if spec.valid_mask is None
                    else jnp.asarray(spec.valid_mask, jnp.bool_))
        self._fns: dict = {}
        self._routes: dict = {}  # shape -> (mode, plan, solver, chunk)
        self._trace_count = 0
        #: host-side (NumPy) copy of the last solve's solver telemetry
        #: pytree; stays None unless ``spec.telemetry`` is set (see
        #: :class:`AnticlusterSpec`).
        self.last_telemetry = None

    @property
    def compile_count(self) -> int:
        """Number of executable traces built so far (1 per input signature).

        Incremented from inside the traced function, so it counts actual
        (re)traces -- the compile-exactly-once contract across same-shape
        epochs is ``engine.compile_count == 1``.
        """
        return self._trace_count

    def _routed(self, shape: tuple[int, ...], has_vm: bool | None = None):
        # memoized: repartition is the per-epoch hot path and the route
        # (incl. resolve_plan's factorization search) is static per shape.
        # ``has_vm`` defaults to the spec's static mask; a per-call mask
        # (see ``repartition``) routes with has_vm=True for the same shape.
        if has_vm is None:
            has_vm = self._vm is not None
        key = (shape, has_vm)
        routed = self._routes.get(key)
        if routed is None:
            routed = _route(self.spec, shape, self._cats is not None,
                            has_vm)
            self._routes[key] = routed
        return routed

    def _solve_shape(self, shape: tuple[int, ...]):
        """``(padded_shape, pad)`` the executables actually run on.

        Mesh sessions auto-pad ``n % n_shards != 0`` inputs with ``pad``
        masked zero rows (see ``_mesh_pad_rows``); every state/shape query
        and ``repartition`` itself agree on this padded geometry, and
        results are sliced back to the caller's ``n``.  ``pad == 0``
        everywhere else.
        """
        shape = tuple(shape)
        pad = _mesh_pad_rows(self.spec, shape, self._vm is not None)
        if pad:
            return (shape[0] + pad, shape[1]), pad
        return shape, 0

    def price_shapes(self, shape) -> tuple[tuple[int, ...], ...]:
        """Per-level price shapes of the state carried for input ``shape``.

        Mesh specs carry per-shard stacks: each level's shape gains a
        leading ``n_shards`` axis (see :class:`ShardedABAState`).
        """
        shape, pad = self._solve_shape(tuple(shape))
        mode, plan, _solver, _chunk = self._routed(
            shape, True if pad else None)
        if mode == "mesh":
            from repro.core.sharded import sharded_price_shapes
            return sharded_price_shapes(plan, _mesh_shards(self.spec))
        if mode == "stacked":
            return ((shape[0], self.spec.k),)
        if mode == "hier":
            return plan_price_shapes(plan)
        return ((1, self.spec.k),)

    def state_shardings(self, x_or_shape):
        """NamedShardings matching the session state for input ``shape``.

        ``None`` for meshless specs (single-device state).  For mesh specs,
        a :class:`ShardedABAState`-shaped tree of
        ``jax.sharding.NamedSharding`` leaves sharding every leading axis
        over the spec's data axes -- the layout ``init_state`` places its
        zeros with, ``repartition`` keeps, and a checkpoint restore should
        ``device_put`` with (``repro.train.checkpoint.restore_engine_state``
        does).
        """
        shape = (tuple(x_or_shape) if isinstance(x_or_shape, (tuple, list))
                 else tuple(jnp.shape(x_or_shape)))
        shape, pad = self._solve_shape(shape)
        if self._routed(shape, True if pad else None)[0] != "mesh":
            return None
        axes = resolve_data_axes(self.spec.mesh, self.spec.data_axes)
        # eval_shape: leaf ranks without materializing a throwaway state
        like = jax.eval_shape(lambda: self._cold_state(shape))
        return shard_leading(self.spec.mesh, axes, like)

    def _cold_state(self, shape):
        """Host-side zeroed state pytree for ``shape`` (no placement)."""
        shape, pad = self._solve_shape(shape)
        mode, _plan, _solver, _chunk = self._routed(
            shape, True if pad else None)
        prices = tuple(jnp.zeros(s, jnp.float32)
                       for s in self.price_shapes(shape))
        if mode == "mesh":
            n, d = shape
            n_shards = _mesh_shards(self.spec)
            return ShardedABAState(
                prices, jnp.zeros((n_shards, d), jnp.float32),
                jnp.zeros((n_shards,), jnp.float32),
                jnp.full((n,), -1, jnp.int32))
        if mode == "stacked":
            G, M, D = shape
            return ABAState(prices, jnp.zeros((G, D), jnp.float32),
                            jnp.zeros((G,), jnp.float32),
                            jnp.full((G, M), -1, jnp.int32))
        n, d = shape
        return ABAState(prices, jnp.zeros((d,), jnp.float32),
                        jnp.zeros((), jnp.float32),
                        jnp.full((n,), -1, jnp.int32))

    def init_state(self, x_or_shape) -> "ABAState | ShardedABAState":
        """A zeroed (cold-start) state for ``x`` / its shape.

        :class:`ABAState` for meshless specs; :class:`ShardedABAState`
        (placed with :meth:`state_shardings`) for mesh specs.
        """
        shape = (tuple(x_or_shape) if isinstance(x_or_shape, (tuple, list))
                 else tuple(jnp.shape(x_or_shape)))
        state = self._cold_state(shape)
        shardings = self.state_shardings(shape)
        return state if shardings is None else jax.device_put(state,
                                                              shardings)

    def partition(self, x, *,
                  valid_mask=None) -> tuple[AnticlusterResult, ABAState]:
        """Cold solve: ``repartition`` from a zeroed state (bit-identical to
        ``anticluster(x, spec)``); compiles on first use per shape."""
        return self.repartition(x, self.init_state(jnp.shape(x)),
                                valid_mask=valid_mask)

    def repartition(self, x, state, *,
                    valid_mask=None) -> tuple[AnticlusterResult, Any]:
        """Warm solve: same-shape re-partition carrying ``state``'s prices.

        The state is *consumed* (its buffers are donated to the compiled
        call); use the returned state for the next epoch.  A zeroed state
        (``init_state``) reproduces ``partition`` bit-for-bit.  Mesh specs
        take and return a :class:`ShardedABAState` (per-shard layout kept
        end to end); meshless specs an :class:`ABAState`.

        ``valid_mask`` marks padding rows *per call* (bool, the labels'
        shape): unlike ``spec.valid_mask`` (one static mask baked into the
        session) it is a runtime argument of the same compiled executable,
        so one engine can serve differently-padded same-shape inputs with
        zero retraces -- the serving tier's row-bucket admission
        (`repro.serve`) leans on this.  Masked rows never influence real
        rows and draw arbitrary labels in [0, k); mutually exclusive with
        ``spec.valid_mask``.
        """
        return self._dispatch(x, state, valid_mask).wait()

    def overlap_capable(self, x_or_shape) -> bool:
        """Whether :meth:`dispatch_repartition` can overlap for this input.

        False iff the route's resolved solver executes on the host from
        inside the trace (``Solver.host_callback`` -- e.g. ``"scipy"`` via
        ``jax.pure_callback``): such a solve occupies the host thread while
        in flight, so an async dispatch buys nothing and risks the known
        host-callback deadlock the stats guard exists for.
        """
        shape = (tuple(x_or_shape) if isinstance(x_or_shape, (tuple, list))
                 else tuple(jnp.shape(x_or_shape)))
        shape, pad = self._solve_shape(shape)
        _mode, _plan, solver, _chunk = self._routed(
            shape, True if pad else None)
        return not get_solver(solver).host_callback

    def dispatch_repartition(self, x, state, *,
                             valid_mask=None) -> "PendingRepartition":
        """Non-blocking warm repartition: enqueue the solve, don't sync.

        Runs exactly :meth:`repartition`'s validation and compiled call but
        returns immediately after the async dispatch (JAX queues the
        executable; the host thread never touches ``block_until_ready``).
        The returned :class:`PendingRepartition` finishes the epoch on
        ``wait()`` -- ``dispatch_repartition(x, state).wait()`` is
        bit-for-bit identical to ``repartition(x, state)``, stats included.

        ``state`` is consumed at dispatch time (buffers donated), so thread
        states linearly: never reuse a state an in-flight call took.

        Raises ``RuntimeError`` when :meth:`overlap_capable` is False (a
        host-callback solver such as ``"scipy"`` -- dispatch would occupy
        the host thread anyway); callers wanting a fallback should check
        ``overlap_capable`` and call :meth:`repartition` instead, as
        ``repro.train.pipeline.ABAPipeline`` does.
        """
        shape = tuple(jnp.shape(x))
        if not self.overlap_capable(shape):
            _mode, _plan, solver, _chunk = self._routed(
                self._solve_shape(shape)[0])
            raise RuntimeError(
                f"solver {solver!r} runs via a host callback and cannot be "
                "dispatched asynchronously (the solve occupies the host "
                "thread -- no overlap is possible); check "
                "engine.overlap_capable(x) and use the synchronous "
                "repartition() instead")
        return self._dispatch(x, state, valid_mask)

    def _dispatch(self, x, state, valid_mask) -> "PendingRepartition":
        """Validate, resolve the route and enqueue the compiled solve.

        Shared tail of :meth:`repartition` (which ``wait()``s inline) and
        :meth:`dispatch_repartition` (which hands the pending handle out):
        everything up to -- but excluding -- the first sync lives here.
        """
        spec = self.spec
        x = jnp.asarray(x).astype(spec.dtype)
        shape = tuple(x.shape)
        vm = self._vm
        per_call_mask = valid_mask is not None
        if per_call_mask:
            if self._vm is not None:
                raise ValueError(
                    "spec.valid_mask and a per-call valid_mask are mutually "
                    "exclusive; build the engine without spec.valid_mask to "
                    "pass masks per call")
            vm = jnp.asarray(valid_mask, jnp.bool_)
            if tuple(vm.shape) != shape[:-1]:
                raise ValueError(
                    f"valid_mask shape {tuple(vm.shape)} does not match the "
                    f"label shape {shape[:-1]} of input {shape}")
        n_rows = shape[0]
        pad = 0
        if not per_call_mask:
            solve_shape, pad = self._solve_shape(shape)
            if pad:
                # mesh auto-pad: masked zero rows make n divisible by the
                # shard count; the pad mask rides the per-call-mask
                # executable, so it composes with warm state like any mask
                x = jnp.concatenate(
                    [x, jnp.zeros((pad, shape[1]), x.dtype)])
                vm = jnp.concatenate([jnp.ones((n_rows,), jnp.bool_),
                                      jnp.zeros((pad,), jnp.bool_)])
                shape = solve_shape
                per_call_mask = True
        mode, plan, solver, _chunk = self._routed(shape, vm is not None)
        state_cls = ShardedABAState if mode == "mesh" else ABAState
        if not isinstance(state, state_cls):
            raise TypeError(
                f"a {'mesh' if mode == 'mesh' else 'single-device'} engine "
                f"carries {state_cls.__name__}, got "
                f"{type(state).__name__} (build states with "
                "engine.init_state / previous repartition calls)")
        expected = self.price_shapes(shape)
        got = tuple(tuple(p.shape) for p in state.prices)
        if got != expected:
            raise ValueError(
                f"state prices {got} do not match the {expected} this "
                f"engine carries for input shape {shape} (state from a "
                "different shape/plan?)")
        key = (shape, jnp.dtype(spec.dtype).name, per_call_mask)
        fn = self._fns.get(key)
        if fn is None:
            fn = self._build(shape, per_call_mask=per_call_mask)
            self._fns[key] = fn
        span = None
        if obs.enabled():
            # async span: dispatch and wait() may happen on different
            # threads / stack frames (the pipeline's overlapped epochs)
            span = obs.begin("engine/repartition", shape=shape, mode=mode,
                             solver=solver, k=spec.k,
                             telemetry=spec.telemetry)
            if mode == "stream":
                obs.event("stream/plan", shape=shape, chunk=_chunk)
        args = (x, tuple(state.prices)) + ((vm,) if per_call_mask else ())
        if spec.telemetry:
            labels, prices, msum, mcnt, tele = fn(*args)
        else:
            labels, prices, msum, mcnt = fn(*args)
            tele = None
        return PendingRepartition(self, x, vm, labels, prices, msum, mcnt,
                                  mode, plan, solver, pad, n_rows, state_cls,
                                  tele=tele, span=span)

    def update(self, x, state, *, added=None,
               removed=None) -> tuple[AnticlusterResult, Any, ABAState]:
        """Absorb a delta into a live partition without a full re-solve.

        ``x``/``state`` are the current (n, d) rows and the
        :class:`ABAState` from the ``partition``/``repartition``/``update``
        call that produced them.  ``removed`` names departing rows of ``x``
        (int indices or an (n,) bool mask); ``added`` is an (m, d) block of
        arriving rows.  Returns ``(result, new_x, new_state)`` where
        ``new_x = concat(x[kept], added)`` is the post-delta row order the
        labels/state refer to -- feed the pair straight into the next
        ``update``/``repartition``.

        Small deltas take the *incremental* path (``result.updated`` is
        True): kept rows keep their labels, departures free capacity and
        down-date the carried centrality moments, and arrivals are assigned
        by a restricted auction over the open cluster slots with every
        other dual price frozen (see :mod:`repro.incremental`).  The delta
        path falls back -- loudly, with a ``RuntimeWarning`` -- to a full
        warm ``repartition`` (``result.updated`` False) when the delta
        exceeds ``spec.update_threshold * n_new`` or balance cannot be
        restored locally; the fallback is bit-for-bit identical to calling
        ``repartition`` on the post-delta rows with the carried prices.
        A zero delta is exactly ``repartition(x, state)``.

        Flat / streamed / hierarchical category-free sessions only; mesh,
        stacked, categorical, and masked sessions raise
        ``NotImplementedError`` (repartition instead).
        """
        from repro import incremental as _incremental
        return _incremental.engine_update(self, x, state, added=added,
                                          removed=removed)

    def _build(self, shape: tuple[int, ...], per_call_mask: bool = False):
        """One shape-keyed executable: solve + state refresh, donated state.

        Mesh specs compile the whole thing -- ``shard_map`` execution plus
        the per-shard price refresh -- into this one jitted callable too, so
        distributed repartitioning retraces exactly as often as the local
        path: once per input signature.  With ``per_call_mask`` the valid
        mask is a runtime argument of the executable (one trace covers every
        padding pattern of the shape) instead of a baked-in constant.
        """
        spec = self.spec
        mode, plan, solver, chunk = self._routed(
            shape, True if per_call_mask else None)
        cats, ncats = self._cats, self._n_categories
        codes, ncodes = self._codes, self._n_codes
        if (cats is not None and len(shape) == 2
                and cats.shape[0] < shape[0]):
            # mesh auto-pad: padding rows draw an arbitrary stratum (they
            # are masked out, so quotas over real rows are unaffected)
            pad_n = shape[0] - cats.shape[0]
            cats = jnp.concatenate([cats, jnp.zeros((pad_n,), jnp.int32)])
            if codes is not None:
                codes = jnp.concatenate(
                    [codes, jnp.zeros((pad_n, codes.shape[-1]), jnp.int32)])

        def body(x, prices, vm):
            self._trace_count += 1  # python side effect: runs once per trace
            labels, st = _call_core(x, spec, mode, plan, solver, chunk,
                                    cats, ncats, vm, codes=codes,
                                    n_codes=ncodes, prices=prices,
                                    return_state=True,
                                    telemetry=spec.telemetry)
            # solver telemetry rides the output pytree only when the spec
            # opts in -- the default executable is byte-identical to the
            # pre-telemetry one (the engine compile_count pins rely on it)
            tele = st.pop("telemetry", None) if spec.telemetry else None
            # re-center the dual prices per group (the auction is invariant
            # to a uniform shift) so carried state stays bounded over epochs
            new_prices = tuple(p - jnp.max(p, axis=-1, keepdims=True)
                               for p in st["prices"])
            if mode == "mesh":
                # per-shard moments come straight from the sharded state
                out = (labels, new_prices, st["moment_sum"],
                       st["moment_count"])
                return out + (tele,) if spec.telemetry else out
            mu = st["mu"]
            if mode == "stacked":
                cnt = (jnp.full((shape[0],), float(shape[1]), jnp.float32)
                       if vm is None else jnp.sum(vm, axis=1, dtype=jnp.float32))
            else:
                cnt = (jnp.asarray(float(shape[0]), jnp.float32)
                       if vm is None else jnp.sum(vm, dtype=jnp.float32))
            out = (labels, new_prices, mu * cnt[..., None], cnt)
            return out + (tele,) if spec.telemetry else out

        if per_call_mask:
            return jax.jit(lambda x, prices, vm: body(x, prices, vm),
                           donate_argnums=(1,))
        static_vm = self._vm
        return jax.jit(lambda x, prices: body(x, prices, static_vm),
                       donate_argnums=(1,))


class PendingRepartition:
    """An in-flight (asynchronously dispatched) engine repartition.

    Produced by :meth:`AnticlusterEngine.dispatch_repartition`: the compiled
    solve is already enqueued on the device; the arrays held here are JAX's
    async futures.  ``wait()`` performs the one deliberate sync (the same
    ``block_until_ready`` guard ``repartition`` uses before its host-level
    statistics) and finishes the result exactly as the synchronous path
    would -- ``dispatch(...).wait()`` is bit-for-bit ``repartition(...)``.

    ``wait()`` is idempotent (the finished pair is cached).  ``ready()``
    polls completion without blocking, for callers that want to interleave
    more host work while the solve drains.
    """

    def __init__(self, engine, x, vm, labels, prices, msum, mcnt,
                 mode, plan, solver, pad, n_rows, state_cls,
                 tele=None, span=None):
        self._engine = engine
        self._x, self._vm = x, vm
        self._labels, self._prices = labels, prices
        self._msum, self._mcnt = msum, mcnt
        self._mode, self._plan, self._solver = mode, plan, solver
        self._pad, self._n_rows = pad, n_rows
        self._state_cls = state_cls
        self._tele = tele
        self._span = span
        self._done: tuple | None = None

    def ready(self) -> bool:
        """True iff the dispatched solve has finished (non-blocking)."""
        if self._done is not None:
            return True
        try:
            return all(a.is_ready() for a in jax.tree_util.tree_leaves(
                (self._labels, self._prices)))
        except AttributeError:  # backend arrays without is_ready()
            return True

    def wait(self) -> tuple[AnticlusterResult, Any]:
        """Sync, compute stats (per spec) and return ``(result, state)``."""
        if self._done is not None:
            return self._done
        engine, spec = self._engine, self._engine.spec
        x, vm = self._x, self._vm
        mode, plan, solver = self._mode, self._plan, self._solver
        pad, n_rows = self._pad, self._n_rows
        # Finish labels before dispatching the (host-level) statistics ops:
        # host-callback solvers deadlock otherwise (see anticluster()).
        labels = jax.block_until_ready(self._labels)
        prices, msum, mcnt = self._prices, self._msum, self._mcnt
        if self._tele is not None:
            # hold the solver telemetry on the host (NumPy) so the session
            # can inspect it after the donated device state is gone
            engine.last_telemetry = jax.tree_util.tree_map(
                np.asarray, self._tele)
        if mode == "mesh":
            n_shards = _mesh_shards(spec)
            plan = ((n_shards,) + plan) if n_shards > 1 else plan
        # padding rows are masked in vm, so the stats match the unpadded run
        sizes, sd, rng = _result_stats(x, labels, spec.k, vm,
                                       diversity=spec.stats)
        bound, gap = (None, None)
        if spec.stats:
            bound, gap = _certificate(x, labels, prices, mode, spec.k, vm)
        result = AnticlusterResult(
            labels=labels[:n_rows] if pad else labels, cluster_sizes=sizes,
            diversity_sd=sd, diversity_range=rng, k=spec.k, plan=plan,
            solver=solver, variant=spec.variant, dual_bound=bound, gap=gap)
        # the state keeps the padded geometry (labels' length keys the shape)
        state = self._state_cls(prices=prices, moment_sum=msum,
                                moment_count=mcnt, prev_labels=labels)
        if self._span is not None:
            summary = obs.summarize_auction_telemetry(
                engine.last_telemetry if self._tele is not None else None)
            if summary is not None:
                self._span.set(rounds_total=summary["rounds_total"],
                               warm_fraction=summary.get("warm_fraction"))
                trace = obs.active()
                if trace is not None:
                    for phase, r in enumerate(summary["rounds_per_phase"]):
                        trace.event("solver/phase", phase=phase,
                                    rounds=int(r))
            self._span.finish(gap=gap)
        self._done = (result, state)
        self._x = self._labels = self._prices = None  # free the refs
        self._msum = self._mcnt = None
        self._tele = self._span = None
        return self._done
