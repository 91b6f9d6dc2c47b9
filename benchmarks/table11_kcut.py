"""Paper Table 11: balanced k-cut on tabular data -- ABA vs the greedy
refinement baseline (METIS proxy, 30-random-neighbour information budget, see
DESIGN.md) vs random.  Reports W(C) (equivalently cut cost), runtimes, and
the min/max anticluster size ratio."""

from __future__ import annotations

import time

import numpy as np
import jax.numpy as jnp

from repro.anticluster import anticluster
from repro.core import objective_pairwise
from repro.core.baselines import greedy_kcut, random_partition
from repro.data import synthetic

from benchmarks.common import dev_pct, row

SETTINGS = [("abalone", (4, 10)), ("facebook", (7, 18)), ("frogs", (8, 16)),
            ("electric", (10, 30)), ("creditcard", (2, 6))]


def run(full: bool = False):
    print("# table11: dataset,K,W_aba,dev_kcut,dev_rand,cpu_aba_s,cpu_kcut_s,"
          "ratio_aba,ratio_kcut")
    for name, kvals in SETTINGS:
        x = synthetic.load(name, max_n=None if full else 10_000)
        xj = jnp.asarray(x)
        n = len(x)
        for k in kvals:
            t0 = time.time()
            la = np.asarray(anticluster(xj, k=k, plan=None, stats=False).labels)
            t_aba = time.time() - t0
            wa = float(objective_pairwise(xj, jnp.asarray(la), k))
            t0 = time.time()
            lm = greedy_kcut(x, k, seed=0)
            t_m = time.time() - t0
            wm = float(objective_pairwise(xj, jnp.asarray(lm), k))
            lr = random_partition(n, k, seed=0)
            wr = float(objective_pairwise(xj, jnp.asarray(lr), k))

            def ratio(lab):
                c = np.bincount(lab, minlength=k)
                return (1.0 if c.max() - c.min() <= 1
                        else c.min() / max(c.max(), 1))

            print(f"table11,{name},{k},{wa:.1f},{dev_pct(wa, wm):+.4f},"
                  f"{dev_pct(wa, wr):+.4f},{t_aba:.3f},{t_m:.3f},"
                  f"{ratio(la):.3f},{ratio(lm):.3f}", flush=True)
            row(f"table11/{name}/k{k}", t_aba,
                f"dev_kcut={dev_pct(wa, wm):+.4f}%;dev_rand={dev_pct(wa, wr):+.4f}%")


if __name__ == "__main__":
    from repro.compile_cache import use_compile_cache

    use_compile_cache()
    run()
