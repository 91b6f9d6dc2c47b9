"""Kernel + solver microbenchmarks.

Reports three stories:

1. ``cdist`` reference arithmetic intensity (roofline anchor).
2. **Fused vs naive bidding**: the auction round's top-2 reduction through
   the ``kernels.ops.bid_top2`` dispatch (Pallas kernel on TPU; the same
   kernel body under ``interpret=True`` on small-CPU, jnp reference on
   big-CPU) against the naive path that materializes the (m, k) value
   matrix every round.  On CPU the interpret path is Python-speed -- the
   row records which path the dispatch resolved so the numbers are honest;
   the TPU speedup story is carried by the roofline analysis.
3. **Batched vs vmapped solver**: one fused ``auction_solve`` loop over a
   (B, k, k) stack vs ``vmap`` over B scalar solves.
4. **Registry sweep**: every LAP backend in the solver registry
   (``repro.core.assignment.available_solvers``) on the same stack, so a
   ``register_solver``-ed backend shows up here with zero edits.
5. **Epoch bench (cold vs warm)**: one ``anticluster()`` one-shot epoch vs
   one warm ``AnticlusterEngine.repartition`` epoch on the same shape --
   the repeated-workload story (mini-batch creation per training epoch).
   The regression gate compares wall time per row (so a warm-path slowdown
   past 2x the checked-in baseline fails CI); both rows also record the
   anticlustering objective into the trajectory JSON for drift inspection,
   and the printed ``speedup=``/``obj_dev_pct=`` labels carry the
   warm-beats-cold evidence (the tested quality contract -- warm objective
   within 1% of cold -- lives in tests/test_engine.py).
6. **Warm re-entry schedule**: the adaptive infeasibility-scaled re-entry
   (default) against the legacy fixed jump-to-final-phase shortcut
   (``AuctionConfig(adaptive_reentry=False)``) -- the
   ``engine/epoch_warm_fixed`` row pins that adaptive is no worse on the
   steady-state shape.
7. **Sharded epoch bench**: the same cold/warm story through a mesh spec
   (``engine/epoch_{cold,warm}_sharded`` rows) -- one ``shard_map``
   executable carrying per-shard prices (``ShardedABAState``) across
   epochs; the shape id records the device count.

``--smoke`` runs tiny shapes only (the CI smoke step) and, like every run,
writes the machine-readable trajectory to ``BENCH_kernel.json``
(``benchmarks.common.BENCH_SCHEMA``) for the CI regression gate; the
nightly workflow runs the full (non-smoke) sweep including the full-size
epoch bench.
"""

from __future__ import annotations

import numpy as np
import jax
import jax.numpy as jnp

from repro.core.assignment import (AuctionConfig, auction_solve,
                                   available_solvers, get_solver, scipy_solve)
from repro.kernels import bid_top2, bid_top2_ref, cdist, cdist_ref
from repro.kernels.ops import gather_path, gather_rows, resolve_path

from benchmarks.common import BenchRecorder, row, timed


def run(full: bool = False, smoke: bool = False,
        json_path: str = "BENCH_kernel.json"):
    rng = np.random.default_rng(0)
    rec = BenchRecorder()

    cdist_shapes = [(256, 256, 32)] if smoke else [(512, 512, 64),
                                                   (1024, 1024, 256)]
    for m, k, d in cdist_shapes:
        x = jnp.asarray(rng.normal(size=(m, d)).astype(np.float32))
        c = jnp.asarray(rng.normal(size=(k, d)).astype(np.float32))
        _, t = timed(lambda: cdist_ref(x, c).block_until_ready(), repeats=5)
        ai = (2 * m * k * d) / ((m * d + k * d + m * k) * 4)
        row(f"kernel/cdist_ref/{m}x{k}x{d}", t,
            f"arith_intensity={ai:.1f}flops_per_byte")
        rec.add(f"kernel/cdist_ref/{m}x{k}x{d}", f"{m}x{k}x{d}", t)
        # leading-chunk-dim dispatch (the streaming path's call shape):
        # the same rows as (C, m/C, d) chunks against shared centroids
        xc = x.reshape(4, m // 4, d)
        _, t_c = timed(lambda: cdist(xc, c).block_until_ready(), repeats=5)
        row(f"kernel/cdist_chunked/4x{m // 4}x{k}x{d}", t_c,
            f"flat_us={t * 1e6:.1f};path={resolve_path(m, k)}")
        rec.add(f"kernel/cdist_chunked/4x{m // 4}x{k}x{d}",
                f"4x{m // 4}x{k}x{d}", t_c)

    # --- streaming chunk gather (double-buffered DMA on TPU) --------------
    # The per-chunk row movement of aba_stream: gather (m,) rows from an
    # (n, d) table, then the fused gather+cdist that hides the next block's
    # DMA behind the current block's compute.  On CPU both resolve to the
    # XLA reference gather (path= records it); the kernel path is exercised
    # under interpret=True by tests and measured for real on TPU.
    gat_shapes = [(4096, 512, 32)] if smoke else [(65536, 8192, 64)]
    for n_g, m_g, d_g in gat_shapes:
        tbl = jnp.asarray(rng.normal(size=(n_g, d_g)).astype(np.float32))
        idx = jnp.asarray(rng.integers(0, n_g, size=(m_g,)), jnp.int32)
        c = jnp.asarray(rng.normal(size=(64, d_g)).astype(np.float32))
        _, t_g = timed(lambda: gather_rows(tbl, idx).block_until_ready(),
                       repeats=5)
        row(f"kernel/gather_rows/{n_g}x{m_g}x{d_g}", t_g,
            f"path={gather_path()}")
        rec.add(f"kernel/gather_rows/{n_g}x{m_g}x{d_g}",
                f"{n_g}x{m_g}x{d_g}", t_g)
        _, t_gc = timed(
            lambda: cdist(tbl, c, idx=idx).block_until_ready(), repeats=5)
        row(f"kernel/cdist_gather/{n_g}x{m_g}x{d_g}", t_gc,
            f"gather_us={t_g * 1e6:.1f};path={gather_path()}")
        rec.add(f"kernel/cdist_gather/{n_g}x{m_g}x{d_g}",
                f"{n_g}x{m_g}x{d_g}", t_gc)

    # --- fused vs naive bidding round ------------------------------------
    bid_shapes = [(128, 256, 16)] if smoke else \
        [(512, 512, 64), (2048, 512, 64)] + ([(8192, 4096, 128)] if full else [])
    for m, k, d in bid_shapes:
        x = jnp.asarray(rng.normal(size=(m, d)).astype(np.float32))
        c = jnp.asarray(rng.normal(size=(k, d)).astype(np.float32))
        p = jnp.asarray(rng.normal(size=(k,)).astype(np.float32))
        _, t_f = timed(lambda: bid_top2(x, c, p)[0].block_until_ready(),
                       repeats=3)
        _, t_n = timed(lambda: bid_top2_ref(x, c, p)[0].block_until_ready(),
                       repeats=3)
        row(f"kernel/bid_top2_fused/{m}x{k}x{d}", t_f,
            f"naive_us={t_n * 1e6:.1f};speedup={t_n / t_f:.2f}x;"
            f"path={resolve_path(m, k)}")
        rec.add(f"kernel/bid_top2_fused/{m}x{k}x{d}", f"{m}x{k}x{d}", t_f)

    # --- batched vs vmapped auction solver -------------------------------
    stack_shapes = [(8, 24)] if smoke else \
        [(16, 64), (64, 64)] + ([(64, 256)] if full else [])
    vmapped = jax.jit(jax.vmap(auction_solve))
    for B, n in stack_shapes:
        stack = jnp.asarray(rng.normal(size=(B, n, n)).astype(np.float32))
        _, t_b = timed(lambda: auction_solve(stack).block_until_ready(),
                       repeats=3)
        _, t_v = timed(lambda: vmapped(stack).block_until_ready(), repeats=3)
        row(f"solver/auction_batched/{B}x{n}", t_b,
            f"vmap_us={t_v * 1e6:.1f};speedup={t_v / t_b:.2f}x;"
            f"solves_per_s={B / t_b:.0f}")
        rec.add(f"solver/auction_batched/{B}x{n}", f"{B}x{n}", t_b)

    solver_ns = (24,) if smoke else (64, 128, 256) + ((512,) if full else ())
    for n in solver_ns:
        cmat = jnp.asarray(rng.normal(size=(n, n)).astype(np.float32))
        _, t_a = timed(lambda: auction_solve(cmat).block_until_ready(),
                       repeats=3)
        cn = np.asarray(cmat)
        _, t_s = timed(lambda: scipy_solve(cn), repeats=3)
        row(f"solver/auction/{n}", t_a, f"scipy_lapjv_us={t_s*1e6:.0f}")
        rec.add(f"solver/auction/{n}", f"{n}x{n}", t_a)

    # --- registry sweep: every registered LAP backend on one stack --------
    # (canonical price-carrying signature: solve -> (assignment, prices))
    B, n = (4, 16) if smoke else (16, 64)
    stack = jnp.asarray(rng.normal(size=(B, n, n)).astype(np.float32))
    for name in available_solvers():
        solver = get_solver(name)
        _, t = timed(
            lambda: solver.solve(stack, AuctionConfig())[0]
            .block_until_ready(), repeats=3)
        row(f"solver/registry/{name}/{B}x{n}", t,
            f"solves_per_s={B / t:.0f};"
            f"factored={'yes' if solver.factored else 'no'}")
        rec.add(f"solver/registry/{name}/{B}x{n}", f"{B}x{n}", t)

    # --- epoch bench: cold one-shot vs warm engine repartition ------------
    from repro.anticluster import AnticlusterEngine, AnticlusterSpec, \
        anticluster
    from repro.core.objective import objective_centroid

    n_e, k_e, d_e = (2048, 16, 8) if smoke else (
        (65536, 64, 16) if full else (16384, 64, 16))
    x = jnp.asarray(rng.normal(size=(n_e, d_e)).astype(np.float32))
    spec = AnticlusterSpec(k=k_e, plan=None, stats=False)
    cold_res, t_cold = timed(lambda: anticluster(x, spec), repeats=3)
    obj_cold = float(objective_centroid(x, cold_res.labels, k_e))

    engine = AnticlusterEngine(spec)
    _res0, state0 = engine.partition(x)  # compile + cold solve
    carry = {"state": state0}

    def warm_epoch():
        r, carry["state"] = engine.repartition(x, carry["state"])
        carry["res"] = r
        return r.labels

    _, t_warm = timed(warm_epoch, repeats=3)
    obj_warm = float(objective_centroid(x, carry["res"].labels, k_e))
    shape_e = f"{n_e}x{k_e}x{d_e}"
    row(f"engine/epoch_warm/{shape_e}", t_warm,
        f"cold_us={t_cold * 1e6:.1f};speedup={t_cold / t_warm:.2f}x;"
        f"obj_dev_pct={(obj_warm - obj_cold) / abs(obj_cold) * 100:.4f};"
        f"compiles={engine.compile_count}")
    rec.add(f"engine/epoch_cold/{shape_e}", shape_e, t_cold, obj_cold)
    rec.add(f"engine/epoch_warm/{shape_e}", shape_e, t_warm, obj_warm)

    # --- warm re-entry schedule: adaptive (default) vs legacy fixed -------
    # Same warm epoch with adaptive_reentry=False (always jump straight to
    # the final small-eps phase).  The adaptive default measures dual
    # infeasibility per solve and must be no worse on this steady-state
    # shape (it pays one probe bidding round, skips the same phases).
    engine_f = AnticlusterEngine(spec.replace(
        auction_config=AuctionConfig(adaptive_reentry=False)))
    _resf, statef = engine_f.partition(x)
    carry_f = {"state": statef}

    def warm_epoch_fixed():
        r, carry_f["state"] = engine_f.repartition(x, carry_f["state"])
        carry_f["res"] = r
        return r.labels

    _, t_warm_f = timed(warm_epoch_fixed, repeats=3)
    obj_warm_f = float(objective_centroid(x, carry_f["res"].labels, k_e))
    row(f"engine/epoch_warm_fixed/{shape_e}", t_warm_f,
        f"adaptive_us={t_warm * 1e6:.1f};"
        f"adaptive_vs_fixed={t_warm_f / t_warm:.2f}x")
    rec.add(f"engine/epoch_warm_fixed/{shape_e}", shape_e, t_warm_f,
            obj_warm_f)

    # --- sharded epoch bench: mesh engine cold vs warm --------------------
    # The distributed-session story: one shard_map executable, per-shard
    # warm prices (ShardedABAState).  Runs over every available device (the
    # CI smoke runs single-device; the mesh smoke job forces two).
    from jax.sharding import Mesh

    n_dev = jax.device_count()
    if k_e % n_dev or n_e % n_dev:
        n_dev = 1  # unplaceable device count: measure the 1-device mesh
    mesh = Mesh(np.asarray(jax.devices()[:n_dev]).reshape(n_dev), ("data",))
    spec_s = AnticlusterSpec(k=k_e, mesh=mesh, data_axes=("data",),
                             stats=False)
    cold_s, t_cold_s = timed(lambda: anticluster(x, spec_s), repeats=3)
    obj_cold_s = float(objective_centroid(x, cold_s.labels, k_e))
    engine_s = AnticlusterEngine(spec_s)
    _res_s, state_s = engine_s.partition(x)
    carry_s = {"state": state_s}

    def warm_epoch_sharded():
        r, carry_s["state"] = engine_s.repartition(x, carry_s["state"])
        carry_s["res"] = r
        return r.labels

    _, t_warm_s = timed(warm_epoch_sharded, repeats=3)
    obj_warm_s = float(objective_centroid(x, carry_s["res"].labels, k_e))
    shape_s = f"{n_e}x{k_e}x{d_e}@{n_dev}dev"
    row(f"engine/epoch_warm_sharded/{shape_s}", t_warm_s,
        f"cold_us={t_cold_s * 1e6:.1f};speedup={t_cold_s / t_warm_s:.2f}x;"
        f"obj_dev_pct={(obj_warm_s - obj_cold_s) / abs(obj_cold_s) * 100:.4f};"
        f"compiles={engine_s.compile_count}")
    rec.add(f"engine/epoch_cold_sharded/{shape_s}", shape_s, t_cold_s,
            obj_cold_s)
    rec.add(f"engine/epoch_warm_sharded/{shape_s}", shape_s, t_warm_s,
            obj_warm_s)

    rec.write(json_path)


if __name__ == "__main__":
    from repro.compile_cache import use_compile_cache

    use_compile_cache()
    import argparse

    ap = argparse.ArgumentParser()
    ap.add_argument("--full", action="store_true")
    ap.add_argument("--smoke", action="store_true",
                    help="tiny shapes only (CI smoke step)")
    ap.add_argument("--json", default="BENCH_kernel.json",
                    help="trajectory output path (BENCH_SCHEMA rows)")
    args = ap.parse_args()
    run(full=args.full, smoke=args.smoke, json_path=args.json)
