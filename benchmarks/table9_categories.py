"""Paper Tables 9+10: anticlustering with a categorical constraint --
quality/time (T9) and diversity-balance stats (T10) vs the exchange heuristic
and category-balanced random.  Categories derived by k-means as in the paper
(Section 5.4); the MILP/Gurobi baseline is replaced by the exact-small
optimality check in tests/."""

from __future__ import annotations

import time

import numpy as np
import jax.numpy as jnp

from repro.anticluster import anticluster
from repro.core import diversity_stats, objective_centroid
from repro.core.baselines import fast_anticlustering, random_partition
from repro.data import synthetic

from benchmarks.common import dev_pct, kmeans_labels, row

SETTINGS = [("abalone", 3, (4, 10)), ("facebook", 3, (7, 18)),
            ("frogs", 4, (8, 16)), ("electric", 3, (10, 30)),
            ("pulsar", 2, (18, 35))]


def run(full: bool = False):
    print("# table9/10: dataset,G,K,ofv_aba,dev_PR5,dev_rand,cpu_aba_s,"
          "cpu_PR5_s,sd_aba,sd_dev_PR5,sd_dev_rand")
    for name, g, kvals in SETTINGS:
        x = synthetic.load(name, max_n=None if full else 10_000)
        cats = kmeans_labels(x[:, :4], g, seed=0)
        xj = jnp.asarray(x)
        for k in kvals:
            t0 = time.time()
            la = np.asarray(anticluster(
                xj, k=k, plan=None, categories=jnp.asarray(cats),
                n_categories=g, stats=False).labels)
            t_aba = time.time() - t0
            oa = float(objective_centroid(xj, jnp.asarray(la), k))
            sd_a, _ = (float(v) for v in diversity_stats(xj, jnp.asarray(la), k))
            t0 = time.time()
            lb = fast_anticlustering(x, k, n_partners=5, seed=0,
                                     categories=cats)
            t_ex = time.time() - t0
            ob = float(objective_centroid(xj, jnp.asarray(lb), k))
            sd_b, _ = (float(v) for v in diversity_stats(xj, jnp.asarray(lb), k))
            lr = random_partition(len(x), k, seed=0, categories=cats)
            orr = float(objective_centroid(xj, jnp.asarray(lr), k))
            sd_r, _ = (float(v) for v in diversity_stats(xj, jnp.asarray(lr), k))
            print(f"table9,{name},{g},{k},{oa:.2f},{dev_pct(oa, ob):+.4f},"
                  f"{dev_pct(oa, orr):+.4f},{t_aba:.3f},{t_ex:.3f},"
                  f"{sd_a:.3f},{dev_pct(sd_a, sd_b):+.1f},"
                  f"{dev_pct(sd_a, sd_r):+.1f}", flush=True)
            row(f"table9/{name}/k{k}", t_aba,
                f"dev_PR5={dev_pct(oa, ob):+.4f}%;sd_dev={dev_pct(sd_a, sd_b):+.0f}%")


if __name__ == "__main__":
    from repro.compile_cache import use_compile_cache

    use_compile_cache()
    run()
