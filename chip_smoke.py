#!/usr/bin/env python3
"""Drive the system's main path once on a TPU and check what comes out.

    python chip_smoke.py              # one chip: phases a-f
    python chip_smoke.py --chips 4    # four chips: the row-sharded mesh path

Phases (one process, the chip's default backend, data made from ``--seed``):

  a  streaming + hierarchy at the paper's mini-batch scale: the imagenet8
     preset shape (1,281,167 x 192) with ``anticluster(k=8192,
     chunk_size="auto")`` -- plan (64, 128), ``auction_fused``, so the
     row-gather ring and the stacked ``bid_top2`` kernel run.
  b  flat streaming at a wide K: the music shape (515,345 x 91), K = 512.
  c  the dense flat route: the mnist shape (60,000 x 784), K = 512.
  d  ``AnticlusterEngine`` on (b)'s data: partition, then two warm
     repartitions on drifted features.
  e  ``AnticlusterRouter``: a handful of requests of a few thousand rows.
  f  plain reference: the creditcard shape (30,000 x 24), K = 100, against
     ``repro.core.aba.aba_reference``.

Every phase checks its result (sizes in {floor(n/K), ceil(n/K)}, objective
above a seeded random partition's, and in a/b the kernels against their jnp
references at the phase's shapes) and prints its route, kernel path and
set-up times on earlier lines.  Any failure exits non-zero; so does a
machine where JAX finds no TPU.  On success the last line is the JSON
object ``{"ok": true, "device": {...}}``.

With ``--chips 4`` only (a)'s data runs, row-sharded over a 4-device
``Mesh`` through ``anticluster(x, spec.evolve(mesh=...))`` and a sharded
engine's warm repartition, compared with the same rows on one chip.
"""

from __future__ import annotations

import argparse
import json
import pathlib
import sys
import time
import traceback

ROOT = pathlib.Path(__file__).resolve().parent
SRC = ROOT / "src"

# Objective of a result relative to the plain reference (phase f) and of the
# 4-chip mesh result relative to one chip: both solve the same ABA objective
# with an eps-optimal auction, so they agree far more closely than the gap
# between any ABA result and a random partition (about 1e-2 relative here).
REF_RTOL = 1e-3
MESH_RTOL = 1e-3


def log(msg: str) -> None:
    print(msg, flush=True)


def expect(ok, msg: str) -> None:
    """A check that holds under ``python -O`` too."""
    if not ok:
        raise RuntimeError(msg)


def timed(fn):
    """(result, seconds) of ``fn()``, with the device work finished."""
    import jax
    t0 = time.perf_counter()
    out = jax.block_until_ready(fn())
    return out, time.perf_counter() - t0


def peak_bytes(dev) -> int | None:
    stats = dev.memory_stats() or {}
    return stats.get("peak_bytes_in_use")


def load(name: str, seed: int):
    """A preset-shaped f32 matrix on the default device."""
    import jax
    from repro.data.synthetic import load as synth
    return jax.device_put(synth(name, seed=seed))


def objective(x, labels, k: int) -> float:
    from repro.core.objective import objective_centroid
    return float(objective_centroid(x, labels, k))


def random_objective(x, k: int, seed: int) -> float:
    import jax.numpy as jnp
    from repro.core.baselines import random_partition
    return objective(x, jnp.asarray(random_partition(x.shape[0], k, seed)),
                     k)


def check_partition(x, res, k: int, seed: int) -> dict:
    """Balanced sizes and an objective above a random partition's."""
    import numpy as np
    n = x.shape[0]
    sizes = np.asarray(res.cluster_sizes)
    lo, hi = n // k, -(-n // k)
    expect(sizes.sum() == n and sizes.min() >= lo and sizes.max() <= hi,
           f"sizes outside [{lo}, {hi}]: min {sizes.min()} max {sizes.max()}")
    ofv = objective(x, res.labels, k)
    rnd = random_objective(x, k, seed)
    expect(ofv > rnd, f"objective {ofv} not above random {rnd}")
    return {"sizes": f"[{sizes.min()}, {sizes.max()}]", "objective": ofv,
            "random_objective": rnd}


def route_notes(x, spec) -> dict:
    """Route, plan, solver and whether the lowered solve calls a kernel."""
    import jax
    from repro.anticluster import _call_core, _route
    mode, plan, solver, chunk = _route(spec, tuple(x.shape), False, False)
    text = jax.jit(lambda a: _call_core(
        a, spec, mode, plan, solver, chunk, None, 0, None)).lower(x).as_text()
    return {"route": mode, "plan": plan, "solver": solver, "chunk": chunk,
            "tpu_custom_call": "tpu_custom_call" in text}


def check_kernels(x, bid_shapes, rows: int, seed: int) -> dict:
    """The gather ring and ``bid_top2`` on this chip against ``force="ref"``.

    ``bid_shapes`` are the (G, m, k) stacks the phase's solve dispatches;
    rows and centroids are drawn from ``x`` itself.  The reference runs at
    the highest matmul precision; the argmax may differ only on ties, so
    the check compares the reference value at the kernel's argmax.
    """
    import jax
    import jax.numpy as jnp
    import numpy as np
    from repro.kernels.ops import bid_top2, gather_path, gather_rows, \
        resolve_path
    rng = np.random.default_rng(seed)
    n, d = x.shape
    notes = {"gather_path": gather_path()}
    idx = jnp.asarray(rng.integers(0, n, size=rows), jnp.int32)
    got = np.asarray(gather_rows(x, idx))
    want = np.asarray(gather_rows(x, idx, force="ref"))
    expect(np.array_equal(got, want), "gather_rows differs from x[idx]")
    for G, m, k in bid_shapes:
        pick = rng.integers(0, n, size=G * (m + k))
        xs = x[pick[:G * m]].reshape(G, m, d)
        cs = x[pick[G * m:]].reshape(G, k, d)
        p = jnp.asarray(rng.normal(size=(G, k)), jnp.float32)
        notes[f"bid_path[{G}x{m}x{k}]"] = resolve_path(G * m, k)
        v1, j1, v2 = (np.asarray(a) for a in bid_top2(xs, cs, p))
        with jax.default_matmul_precision("highest"):
            r1, _, r2 = (np.asarray(a) for a in bid_top2(xs, cs, p,
                                                         force="ref"))
        xs64, cs64 = (np.asarray(a, np.float64) for a in (xs, cs))
        vals = (-2.0 * np.einsum("gmd,gkd->gmk", xs64, cs64)
                + (cs64 ** 2).sum(-1)[:, None, :]
                - np.asarray(p, np.float64)[:, None, :])
        at_j1 = np.take_along_axis(vals, j1[..., None], axis=-1)[..., 0]
        tol = 1e-5 * float(np.abs(vals).max() + 1.0)
        err = max(float(np.abs(v1 - r1).max()), float(np.abs(v2 - r2).max()),
                  float(np.abs(at_j1 - vals.max(-1)).max()))
        notes[f"bid_err[{G}x{m}x{k}]"] = f"{err:.3g} (tol {tol:.3g})"
        expect(err <= tol, f"bid_top2 {G}x{m}x{k}: error {err} > {tol}")
    return notes


class CompileMeter:
    """Compile seconds and persistent-cache hits JAX reports, so one cold
    call splits into compile and run without a second run."""

    EVENTS = ("/jax/core/compile/jaxpr_trace_duration",
              "/jax/core/compile/jaxpr_to_mlir_module_duration",
              "/jax/core/compile/backend_compile_duration")

    def __init__(self):
        self.compile_s, self.hits, self.misses = 0.0, 0, 0

    def install(self):
        import jax
        jax.monitoring.register_event_duration_secs_listener(self._duration)
        jax.monitoring.register_event_listener(self._event)

    def _duration(self, event, secs, **_):
        if event in self.EVENTS:
            self.compile_s += secs

    def _event(self, event, **_):
        if event == "/jax/compilation_cache/cache_hits":
            self.hits += 1
        elif event == "/jax/compilation_cache/cache_misses":
            self.misses += 1

    def snapshot(self) -> tuple:
        return self.compile_s, self.hits, self.misses


METER = CompileMeter()


def solve(x, spec) -> tuple:
    """One cold ``anticluster`` call, split into compile and run seconds."""
    from repro.anticluster import anticluster
    c0, h0, m0 = METER.snapshot()
    res, t = timed(lambda: anticluster(x, spec))
    c1, h1, m1 = METER.snapshot()
    return res, {"solve_s": round(t, 3), "compile_s": round(c1 - c0, 3),
                 "run_s": round(t - (c1 - c0), 3),
                 "cache_hits": h1 - h0, "cache_misses": m1 - m0}


# ---------------------------------------------------------------------------
# Phases (one chip)
# ---------------------------------------------------------------------------


def phase_a(ctx):
    from repro.anticluster import AnticlusterSpec
    x, t_data = timed(lambda: load("imagenet8", ctx.seed))
    spec = AnticlusterSpec(k=8192, chunk_size="auto")
    notes = {"shape": tuple(x.shape), "data_s": round(t_data, 3)}
    notes.update(route_notes(x, spec))
    res, t = solve(x, spec)
    notes.update(t)
    notes.update(check_partition(x, res, spec.k, ctx.seed))
    expect(len(res.plan) == 2, f"expected a 2-level plan, got {res.plan}")
    k1, k2 = res.plan  # the streamed level, then k1 stacked groups
    notes.update(check_kernels(x, [(1, k1, k1), (k1, k2, k2)],
                               rows=spec.resolve_chunk(x.shape[0], k1),
                               seed=ctx.seed))
    return notes


def phase_b(ctx):
    from repro.anticluster import AnticlusterSpec
    x, t_data = timed(lambda: load("music", ctx.seed))
    spec = AnticlusterSpec(k=512, chunk_size="auto")
    notes = {"shape": tuple(x.shape), "data_s": round(t_data, 3)}
    notes.update(route_notes(x, spec))
    res, t = solve(x, spec)
    notes.update(t)
    notes.update(check_partition(x, res, spec.k, ctx.seed))
    notes.update(check_kernels(x, [(1, spec.k, spec.k)],
                               rows=spec.resolve_chunk(x.shape[0], spec.k),
                               seed=ctx.seed))
    ctx.b = (x, spec, res)
    return notes


def phase_c(ctx):
    from repro.anticluster import AnticlusterSpec
    x, t_data = timed(lambda: load("mnist", ctx.seed))
    spec = AnticlusterSpec(k=512)
    notes = {"shape": tuple(x.shape), "data_s": round(t_data, 3)}
    notes.update(route_notes(x, spec))
    res, t = solve(x, spec)
    notes.update(t)
    notes.update(check_partition(x, res, spec.k, ctx.seed))
    return notes


def phase_d(ctx):
    import jax
    import numpy as np
    from repro.anticluster import AnticlusterEngine
    if ctx.b is None:
        raise RuntimeError("phase d reuses phase b's data; b did not finish")
    x, spec, res_b = ctx.b
    eng = AnticlusterEngine(spec)
    c0 = METER.compile_s
    (res, state), t_cold = timed(lambda: eng.partition(x))
    expect(np.array_equal(np.asarray(res.labels), np.asarray(res_b.labels)),
           "engine.partition(x) differs from anticluster(x, spec)")
    notes = {"cold_s": round(t_cold, 3),
             "cold_compile_s": round(METER.compile_s - c0, 3),
             "cold_parity": True}
    key = jax.random.PRNGKey(ctx.seed)
    for epoch in (1, 2):
        key, sub = jax.random.split(key)
        xe = x + 0.05 * jax.random.normal(sub, x.shape, x.dtype)
        (res, state), t_warm = timed(lambda: eng.repartition(xe, state))
        chk = check_partition(xe, res, spec.k, ctx.seed + epoch)
        notes[f"warm{epoch}_s"] = round(t_warm, 3)
        notes[f"warm{epoch}_objective"] = chk["objective"]
    expect(eng.compile_count == 1, f"compile_count {eng.compile_count} != 1")
    notes["compile_count"] = eng.compile_count
    return notes


def phase_e(ctx):
    import numpy as np
    from repro.anticluster import AnticlusterSpec
    from repro.serve import AnticlusterRouter
    rng = np.random.default_rng(ctx.seed)
    base = np.asarray(load("creditcard", ctx.seed))
    sizes = [int(s) for s in rng.integers(2000, 5000, size=6)]
    reqs = [base[rng.choice(base.shape[0], s, replace=False)] for s in sizes]
    spec = AnticlusterSpec(k=20)
    t0 = time.perf_counter()
    with AnticlusterRouter(spec) as router:
        tickets = [router.submit(r) for r in reqs]
        # result() re-raises an engine error the router stored on a ticket
        results = [t.result(timeout=600) for t in tickets]
        lanes = router.lane_count
    wall = time.perf_counter() - t0
    for r, res in zip(reqs, results):
        check_partition(r, res, spec.k, ctx.seed)
    return {"requests": sizes, "lanes": lanes, "wall_s": round(wall, 3)}


def phase_f(ctx):
    import numpy as np
    from repro.anticluster import AnticlusterSpec
    from repro.core.aba import aba_reference
    x = load("creditcard", ctx.seed)
    spec = AnticlusterSpec(k=100)
    res, t = solve(x, spec)
    notes = dict(t)
    notes.update(check_partition(x, res, spec.k, ctx.seed))
    xn = np.asarray(x)
    ref_labels, t_ref = timed(lambda: aba_reference(xn, spec.k))
    ref = objective(x, ref_labels, spec.k)
    rel = abs(notes["objective"] - ref) / abs(ref)
    notes.update({"reference_objective": ref, "rel_diff": rel,
                  "rel_tol": REF_RTOL, "reference_s": round(t_ref, 3)})
    expect(rel <= REF_RTOL, f"objective {rel:.3g} from the reference")
    return notes


# ---------------------------------------------------------------------------
# Four chips: phase a's data on a row-sharded mesh
# ---------------------------------------------------------------------------


def phase_mesh(ctx):
    import jax
    import numpy as np
    from jax.sharding import Mesh, NamedSharding, PartitionSpec as P
    from repro.anticluster import AnticlusterEngine, AnticlusterSpec
    devs = jax.devices()
    expect(len(devs) >= 4, f"--chips 4 needs 4 devices, found {len(devs)}")
    mesh = Mesh(np.asarray(devs[:4]), ("data",))
    host = load("imagenet8", ctx.seed)
    n = host.shape[0] - host.shape[0] % 4   # row-shardable: drop n % 4 rows
    x1 = host[:n]
    x = jax.device_put(x1, NamedSharding(mesh, P("data")))
    shards = x.addressable_shards
    quartered = len(shards) == 4 and all(
        s.data.shape == (n // 4, x.shape[1]) for s in shards)
    expect(quartered, f"x is not quartered: {[s.data.shape for s in shards]}")
    notes = {"shape": tuple(x.shape), "shards": [tuple(s.data.shape)
                                                 for s in shards]}
    spec = AnticlusterSpec(k=8192, chunk_size="auto")
    one, t_one = solve(x1, spec)
    mspec = spec.evolve(mesh=mesh)
    res, t_mesh = solve(x, mspec)
    notes.update({"one_chip_plan": one.plan, "mesh_plan": res.plan,
                  "one_chip": t_one, "mesh": t_mesh})
    chk = check_partition(x1, res, spec.k, ctx.seed)
    ofv1 = objective(x1, one.labels, spec.k)
    rel = abs(chk["objective"] - ofv1) / abs(ofv1)
    notes.update({"mesh_objective": chk["objective"], "one_chip_objective":
                  ofv1, "rel_diff": rel, "rel_tol": MESH_RTOL,
                  "sizes": chk["sizes"]})
    expect(rel <= MESH_RTOL, f"mesh objective {rel:.3g} from one chip")
    eng = AnticlusterEngine(mspec)
    (_, state), t_cold = timed(lambda: eng.partition(x))
    xe = x + 0.05 * jax.random.normal(jax.random.PRNGKey(ctx.seed), x.shape,
                                      x.dtype)
    (warm, _), t_warm = timed(lambda: eng.repartition(xe, state))
    check_partition(xe, warm, spec.k, ctx.seed + 1)
    expect(eng.compile_count == 1, f"compile_count {eng.compile_count} != 1")
    notes.update({"engine_cold_s": round(t_cold, 3),
                  "engine_warm_s": round(t_warm, 3),
                  "engine_compile_count": eng.compile_count})
    return notes


class Context:
    """The run's seed, and phase b's data and result for phase d."""

    def __init__(self, seed: int):
        self.seed = seed
        self.b = None


ONE_CHIP = {"a": phase_a, "b": phase_b, "c": phase_c, "d": phase_d,
            "e": phase_e, "f": phase_f}


def run_phases(phases: dict, ctx: Context) -> list[str]:
    """Run each phase, print its notes; return the names that failed."""
    import jax
    from repro.kernels.ops import gather_path
    dev = jax.devices()[0]
    failed = []
    for name, fn in phases.items():
        log(f"--- phase {name}: {fn.__name__}")
        t0 = time.perf_counter()
        try:
            notes = fn(ctx)
        except Exception:  # report every phase, then fail the run
            traceback.print_exc(file=sys.stdout)
            log(f"phase {name}: FAIL")
            failed.append(name)
            continue
        notes["phase_s"] = round(time.perf_counter() - t0, 3)
        notes["peak_bytes_in_use"] = peak_bytes(dev)
        notes.setdefault("gather_path", gather_path())
        for key, val in notes.items():
            log(f"  {key}: {val}")
        log(f"phase {name}: PASS")
    return failed


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--phases", default="abcdef",
                    help="one-chip phases to run (default: all)")
    args = ap.parse_args(argv)
    if not (SRC / "repro").is_dir():
        log(f"chip_smoke: no repro package under {SRC}; run from a checkout")
        return 2
    sys.path.insert(0, str(SRC))
    import jax
    devs = jax.devices()
    if devs[0].platform != "tpu":
        log(f"chip_smoke: no TPU found (JAX sees {devs[0].platform}); "
            "this check runs on the chip only")
        return 2
    from repro.compile_cache import use_compile_cache
    log(f"compile cache: {use_compile_cache()}")
    METER.install()
    log(f"devices: {len(devs)} x {devs[0].device_kind}; jax {jax.__version__}")
    ctx = Context(args.seed)
    if args.chips == 4:
        phases = {"mesh": phase_mesh}
    else:
        phases = {p: ONE_CHIP[p] for p in args.phases}
    failed = run_phases(phases, ctx)
    if failed:
        log(f"chip_smoke: FAILED phases {failed}")
        return 1
    print(json.dumps({"ok": True, "device": {
        "platform": devs[0].platform, "kind": devs[0].device_kind,
        "count": len(devs)}}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
