"""Paper Table 8: very large K via hierarchical decomposition (the mini-batch
regime: anticluster size down to 2-3) vs random partitioning."""

from __future__ import annotations

import time

import numpy as np
import jax.numpy as jnp

from repro.anticluster import anticluster
from repro.core import objective_centroid
from repro.core.baselines import random_partition
from repro.data import synthetic

from benchmarks.common import dev_pct, row


def run(full: bool = False):
    n = 1_281_167 if full else 131_072
    d = 192 if full else 48
    x = synthetic.make("lowrank", n, d, seed=0)
    xj = jnp.asarray(x)
    ks = [n // 128, n // 32, n // 8, n // 4, n // 2]  # sizes 128 ... 2
    print(f"# table8: imagenet-like n={n} d={d}: K,min_sz,max_sz,"
          "cpu_aba_s,ofv_aba,ofv_rand,dev%")
    for i, k in enumerate(ks):
        t0 = time.time()
        labels = np.asarray(anticluster(xj, k=k, max_k=256,
                                stats=False).labels)
        dt = time.time() - t0
        if i == 0:
            # batched-vs-vmapped solver throughput on the same workload:
            # the hierarchical levels as ONE batched auction call per scan
            # step vs the legacy vmap over per-group scalar solves.  Both
            # paths are warmed first so jit compilation stays out of the
            # timed window (the headline dt above deliberately includes it).
            t1 = time.time()
            np.asarray(anticluster(xj, k=k, max_k=256,
                                   stats=False).labels)
            dt_batched = time.time() - t1
            np.asarray(anticluster(xj, k=k, max_k=256, batched=False,
                       stats=False).labels)  # warmup
            t2 = time.time()
            np.asarray(anticluster(xj, k=k, max_k=256, batched=False,
                                   stats=False).labels)
            dt_vmap = time.time() - t2
            row(f"table8/solver_batched_vs_vmap/k{k}", dt_batched,
                f"vmap_s={dt_vmap:.2f};"
                f"speedup={dt_vmap / max(dt_batched, 1e-9):.2f}x")
        counts = np.bincount(labels, minlength=k)
        oa = float(objective_centroid(xj, jnp.asarray(labels), k))
        lr = random_partition(n, k, seed=0)
        orr = float(objective_centroid(xj, jnp.asarray(lr), k))
        print(f"table8,{k},{counts.min()},{counts.max()},{dt:.2f},"
              f"{oa:.2f},{orr:.2f},{dev_pct(oa, orr):+.4f}", flush=True)
        row(f"table8/k{k}", dt, f"ofv={oa:.1f};dev_rand={dev_pct(oa, orr):+.2f}%")


if __name__ == "__main__":
    from repro.compile_cache import use_compile_cache

    use_compile_cache()
    run()
