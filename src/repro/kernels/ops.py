"""Public jit'd wrappers for the Pallas kernels.

On TPU the kernels run compiled (and nothing falls back: a kernel the
TPU compiler refuses fails the call); on CPU they run in ``interpret=True``
mode, which executes the kernel body as jnp ops -- correct but slow, so the wrappers fall back to the jnp reference for *large* CPU
inputs while tests pin ``force="pallas"`` to exercise the kernel path.

Both dispatchers accept leading *chunk*/stack dims:

- ``cdist`` takes ``(..., m, d)`` rows against one shared ``(n, d)`` centroid
  set; leading dims are flattened into the row axis (one tiled kernel launch,
  not one per chunk) and restored on the output.  Used by chunked distance
  workloads (e.g. ``benchmarks.kernel_bench``'s chunked row); the streaming
  ABA core's own centrality pass stays on fused elementwise jnp because its
  single-centroid distance is bandwidth-bound either way and the bit-parity
  contract pins its exact arithmetic.
- ``bid_top2`` takes an optional stacked ``(G, m, d) x (G, k, d)`` problem
  batch -- the ABA core's fused path feeds its per-scan-step group stacks
  through this (per-group centroids differ, so it vmaps the kernel; Pallas
  turns the vmap into an extra grid dimension on TPU and the interpret path
  is vmap-safe on CPU).

The interpret-budget rule sees the *total* row count either way, so a big
chunked CPU call still falls back to the jnp reference instead of crawling
through Python-interpreted tiles.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.kernels.auction import (auction_phase_pallas, block_bytes,
                                   padded_cost)
from repro.kernels.bid_top2 import bid_top2_pallas
from repro.kernels.cdist import cdist_pallas
from repro.kernels.gather import (bid_top2_gather_pallas, cdist_gather_pallas,
                                  gather_rows_pallas, row_table)
from repro.kernels.ref import bid_top2_ref, cdist_ref

_CPU_INTERPRET_BUDGET = 1 << 22  # elements; above this CPU uses the ref
_GATHER_FUSE_MAX_D = 512  # fused-gather kernels keep full rows in VMEM
# Scoped VMEM the resident auction kernel asks for.  A phase holds the
# instance's padded cost block twice (the pipeline prefetches the next
# instance's) plus the round's temporaries, under one more block's worth
# (measured: 3.6 MiB at n = 512, 11.5 MiB at n = 1024, compiled for a
# v5e), so it engages while four blocks fit: n up to 1,408.
_RESIDENT_VMEM_BYTES = 32 << 20


def _backend() -> str:
    return jax.default_backend()


def resolve_path(m: int, k: int, force: str | None = None) -> str:
    """Which path an (m, k)-sized dispatch takes: 'pallas' (TPU compiled),
    'pallas-interpret' (forced, or CPU under the interpret budget), or 'ref'
    (jnp fallback).  The single copy of the rule: the dispatchers below
    branch on it and benchmarks label their rows with it.  ``m`` is the
    *total* row count (leading chunk dims included).
    """
    if force == "ref":
        return "ref"
    if _backend() == "tpu":
        return "pallas"
    if force == "pallas" or m * k <= _CPU_INTERPRET_BUDGET:
        return "pallas-interpret"
    return "ref"


def resident_auction_path(n: int, force: str | None = None) -> str:
    """Which path an eps phase of the dense auction on (n, n) instances
    takes: 'pallas' (TPU, four padded blocks fit the kernel's scoped VMEM),
    'pallas-interpret' (forced on CPU) or 'xla' (the XLA round loop).

    On CPU the default is the XLA loop: interpreting a thousand-round loop
    has no fidelity value, and the results are bitwise the same.  Tests pin
    ``force="pallas"`` to run the kernel in interpret mode.
    """
    if force == "xla":
        return "xla"
    if _backend() == "tpu":
        if force == "pallas" or 4 * block_bytes(n) <= _RESIDENT_VMEM_BYTES:
            return "pallas"
        return "xla"
    return "pallas-interpret" if force == "pallas" else "xla"


def resident_auction(cost: jnp.ndarray, *, force: str | None = None):
    """The dense auction's phase on the resident kernel, for a (B, n, n)
    ``cost`` stack, or None where the XLA round loop runs (see
    :func:`resident_auction_path`).

    The phase takes ``(prices, eps, assign0, max_rounds, min_rounds)`` and
    returns ``(assign, prices, rounds)``: the rounds are those of the
    instance that ran longest, as the batched XLA loop counts them.
    """
    n = cost.shape[-1]
    path = resident_auction_path(n, force)
    if path == "xla":
        return None
    padded = padded_cost(cost)

    def phase(prices, eps, assign0, max_rounds, min_rounds):
        assign, prices, rounds = auction_phase_pallas(
            padded, prices, eps, assign0, n=n, max_rounds=max_rounds,
            min_rounds=min_rounds, vmem_limit_bytes=_RESIDENT_VMEM_BYTES,
            interpret=path != "pallas")
        return assign, prices, jnp.max(rounds)
    return phase


def gather_path(force: str | None = None) -> str:
    """Which path a row-gather dispatch takes: 'pallas' (TPU compiled DMA
    pipeline), 'pallas-interpret' (forced only), or 'ref' (jnp take).

    Deliberately NOT :func:`resolve_path`: on CPU the default is ALWAYS the
    ref -- interpreting a per-row DMA loop in Python is pure overhead with no
    fidelity value (there is no DMA to overlap), and the streaming core calls
    this inside every chunk step.  Tests pin ``force="pallas"`` to exercise
    the kernel ring under interpret mode.
    """
    if force == "ref":
        return "ref"
    if _backend() == "tpu":
        return "pallas"
    if force == "pallas":
        return "pallas-interpret"
    return "ref"


def row_gatherer(x: jnp.ndarray, *, force: str | None = None, **block_kw):
    """``idx -> x[idx]`` as float32, with ``x`` laid out for the gather once.

    On TPU this is the double-buffered DMA gather
    (:func:`repro.kernels.gather.gather_rows_pallas`) -- the next block's
    HBM row movement overlaps the current block's copy-out -- reading the
    lane-padded :func:`repro.kernels.gather.row_table` built here, outside
    any loop the caller runs the gather in (XLA does not hoist that padded
    copy out of a scan body by itself).  On CPU it is the plain jnp take
    (bit-identical, so the streaming core's parity contract is
    path-independent).  Out-of-range indices are clipped on the kernel
    path; callers clamp before the ref path.
    """
    path = gather_path(force)
    if path == "ref":
        return lambda idx: x[idx].astype(jnp.float32)
    table = row_table(x)
    return lambda idx: gather_rows_pallas(
        table, idx, d=x.shape[1], interpret=path != "pallas", **block_kw)


def gather_rows(x: jnp.ndarray, idx: jnp.ndarray, *,
                force: str | None = None, **block_kw) -> jnp.ndarray:
    """``x[idx]`` as float32: (n, d), (m,) -> (m, d); one-off
    :func:`row_gatherer`."""
    return row_gatherer(x, force=force, **block_kw)(idx)


def cdist(x: jnp.ndarray, c: jnp.ndarray, *, idx: jnp.ndarray | None = None,
          force: str | None = None, **block_kw) -> jnp.ndarray:
    """Squared-distance cost matrix; kernel on TPU, ref fallback on big-CPU.

    ``x`` may carry leading chunk dims: ``(..., m, d) x (n, d) -> (..., m, n)``
    (flattened into one tiled launch against the shared ``c``).

    With ``idx`` the rows are ``x[idx]`` (x must be 2-D): on TPU the fused
    gather-compute kernel streams each row block HBM -> VMEM exactly once via
    the double-buffered DMA ring and never materializes the gathered copy
    (falling back to gather + tiled kernel when d exceeds the full-row VMEM
    budget); elsewhere it is a plain take + the usual dispatch.
    """
    if idx is not None:
        assert x.ndim == 2, "idx gather needs flat (n, d) x"
        path = resolve_path(idx.shape[0], c.shape[0], force)
        if path == "ref" or x.shape[1] > _GATHER_FUSE_MAX_D:
            return cdist(gather_rows(x, idx, force=force), c, force=force,
                         **block_kw)
        return cdist_gather_pallas(x, idx, c, interpret=path != "pallas",
                                   **block_kw)
    lead = x.shape[:-2]
    if lead:
        x = x.reshape(-1, x.shape[-1])
    path = resolve_path(x.shape[0], c.shape[0], force)
    out = (cdist_ref(x, c) if path == "ref"
           else cdist_pallas(x, c, interpret=path != "pallas", **block_kw))
    return out.reshape(*lead, -1, out.shape[-1]) if lead else out


def bid_top2(x: jnp.ndarray, c: jnp.ndarray, prices: jnp.ndarray, *,
             idx: jnp.ndarray | None = None, force: str | None = None,
             **block_kw):
    """Fused auction bidding reduction (v1, j1, v2 per row).

    Accepts a single ``(m, d) x (k, d)`` problem or a stacked
    ``(G, m, d) x (G, k, d)`` batch with ``(G, k)`` prices (each group has
    its own centroid set, so the stack vmaps the kernel).

    With ``idx`` the rows are ``x[idx]`` (x must be flat (n, d)): on TPU the
    fused gather-bid kernel DMAs each row block once through the
    double-buffered ring and reduces it against every centroid tile while
    the next block streams in; elsewhere it is a take + the usual dispatch.
    """
    if idx is not None:
        assert x.ndim == 2, "idx gather needs flat (n, d) x"
        path = resolve_path(idx.shape[0], c.shape[-2], force)
        if path == "ref" or x.shape[1] > _GATHER_FUSE_MAX_D:
            return bid_top2(gather_rows(x, idx, force=force), c, prices,
                            force=force, **block_kw)
        return bid_top2_gather_pallas(x, idx, c, prices,
                                      interpret=path != "pallas", **block_kw)
    if x.ndim == 3:
        total_m = x.shape[0] * x.shape[1]
        path = resolve_path(total_m, c.shape[-2], force)
        if path == "ref":
            return jax.vmap(bid_top2_ref)(x, c, prices)
        return jax.vmap(
            lambda xg, cg, pg: bid_top2_pallas(
                xg, cg, pg, interpret=path != "pallas", **block_kw)
        )(x, c, prices)
    path = resolve_path(x.shape[0], c.shape[0], force)
    if path == "ref":
        return bid_top2_ref(x, c, prices)
    return bid_top2_pallas(x, c, prices, interpret=path != "pallas",
                           **block_kw)
