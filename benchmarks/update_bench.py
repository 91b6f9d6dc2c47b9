"""Incremental-update trajectory: ``engine.update`` vs full ``repartition``.

The delta subsystem's whole value proposition is measurable: absorbing a
small arrival/departure delta into a live partition must be *faster* than
re-solving the post-delta rows from warm state, while staying within a hair
of its objective.  This benchmark sweeps delta fractions on a live
:class:`~repro.anticluster.AnticlusterEngine` session and, per fraction,
measures

* ``update/...``      -- warm ``engine.update`` wall time (the delta path;
  asserted to actually take it, ``result.updated``),
* ``repart/...``      -- warm full ``repartition`` of the same post-delta
  rows (the baseline the delta path must beat), and
* the objective ratio between the two (the local patch is allowed to drift,
  but only marginally).

Every run emits ``BENCH_update.json`` (``benchmarks.common.BENCH_SCHEMA``);
CI runs ``--smoke``, gates wall times against the checked-in baseline via
``benchmarks.check_regression``, and this module *additionally* self-gates
the acceptance contract in smoke mode: at delta fractions <= 10% the update
path must beat the full repartition wall clock AND land within 1% of its
objective, else exit non-zero.  ``--full`` sweeps larger sessions (nightly).
"""

from __future__ import annotations

import sys
import time

import numpy as np
import jax.numpy as jnp

from repro.anticluster import AnticlusterEngine
from repro.core import objective_centroid
from repro.data import synthetic

from benchmarks.common import BenchRecorder, row

# smoke acceptance contract: delta fractions at or below this must beat the
# full warm repartition on wall time and stay within OBJ_TOL of its ofv
GATE_FRACTION = 0.10
OBJ_TOL = 0.01


def _timed_update(eng, x, state, added, removed):
    t0 = time.time()
    res, new_x, new_state = eng.update(x, state, added=added,
                                       removed=removed)
    np.asarray(res.labels)  # sync
    return res, new_x, new_state, time.time() - t0


def run(full: bool = False, smoke: bool = False,
        json_path: str = "BENCH_update.json") -> int:
    rec = BenchRecorder()
    # (n, d, k, delta fractions)
    if smoke:
        shapes = [(4096, 8, 16, (0.02, 0.05, 0.10))]
    elif full:
        shapes = [(65536, 16, 64, (0.01, 0.02, 0.05, 0.10, 0.20)),
                  (262144, 16, 256, (0.01, 0.05, 0.10))]
    else:
        shapes = [(16384, 8, 32, (0.01, 0.05, 0.10))]
    print("# update_bench: n,d,k,frac,update_s,repart_s,speedup,"
          "obj_ratio,updated")
    failures = []

    for n, d, k, fracs in shapes:
        rng = np.random.default_rng(0)
        x0 = jnp.asarray(synthetic.make("lowrank", n, d, seed=0))
        # threshold high enough that every swept fraction takes the delta
        # path -- the point is to measure it, not the fallback
        eng = AnticlusterEngine(k=k, stats=False, update_threshold=0.5)
        _, state = eng.partition(x0)

        for frac in fracs:
            m = max(1, int(round(frac * n)))
            added = jnp.asarray(
                synthetic.make("lowrank", m, d, seed=1 + m))
            removed = np.sort(rng.choice(n, size=m, replace=False))

            # fresh live session per fraction (x stays (n, d): remove m,
            # add m), warmed so wall times are compile-free on both paths
            _, st_warm = eng.partition(x0)
            _timed_update(eng, x0, st_warm, added, removed)  # warm trace
            _, st = eng.partition(x0)
            res_u, new_x, _, t_u = _timed_update(eng, x0, st, added,
                                                 removed)
            if not res_u.updated:
                failures.append(f"n={n} frac={frac}: fell back to a full "
                                "repartition (delta path not exercised)")
            o_u = float(objective_centroid(new_x, res_u.labels, k))

            # the baseline: warm full repartition of the same rows (state
            # from a prior same-shape solve, exactly the live alternative)
            _, st_b = eng.partition(new_x)
            t0 = time.time()
            res_r, _ = eng.repartition(new_x, st_b)
            np.asarray(res_r.labels)
            t_r = time.time() - t0
            o_r = float(objective_centroid(new_x, res_r.labels, k))

            ratio = o_u / o_r if o_r else float("nan")
            tag = f"n{n}_k{k}_f{int(frac * 100):02d}"
            rec.add(f"update/delta/{tag}", f"{n}x{d}x{k}", t_u, o_u)
            rec.add(f"update/repart/{tag}", f"{n}x{d}x{k}", t_r, o_r)
            print(f"update,{n},{d},{k},{frac:.2f},{t_u:.4f},{t_r:.4f},"
                  f"{t_r / max(t_u, 1e-9):.2f}x,{ratio:.5f},"
                  f"{res_u.updated}", flush=True)
            row(f"update/delta/{tag}", t_u,
                f"repart_s={t_r:.4f};obj_ratio={ratio:.5f}")

            if smoke and frac <= GATE_FRACTION:
                if t_u >= t_r:
                    failures.append(
                        f"n={n} frac={frac}: update {t_u:.4f}s did not "
                        f"beat repartition {t_r:.4f}s")
                if not ratio >= 1.0 - OBJ_TOL:
                    failures.append(
                        f"n={n} frac={frac}: objective ratio {ratio:.5f} "
                        f"below {1.0 - OBJ_TOL} of the full re-solve")

    rec.write(json_path)
    if failures:
        print("# update_bench acceptance FAILURES:")
        for f in failures:
            print(f"#   {f}")
        return 1
    return 0


if __name__ == "__main__":
    from repro.compile_cache import use_compile_cache

    use_compile_cache()
    import argparse

    ap = argparse.ArgumentParser()
    ap.add_argument("--full", action="store_true",
                    help="nightly sweep (larger sessions, more fractions)")
    ap.add_argument("--smoke", action="store_true",
                    help="tiny shape + acceptance gate (CI smoke step)")
    ap.add_argument("--json", default="BENCH_update.json",
                    help="trajectory output path (BENCH_SCHEMA rows)")
    args = ap.parse_args()
    sys.exit(run(full=args.full, smoke=args.smoke, json_path=args.json))
