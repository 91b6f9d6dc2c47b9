"""Paper Table 6: balanced diversity -- sd/range of per-anticluster diversity,
ABA vs exchange heuristic vs random (the paper's headline quality claim)."""

from __future__ import annotations

import numpy as np
import jax.numpy as jnp

from repro.anticluster import anticluster
from repro.core import diversity_stats
from repro.core.baselines import fast_anticlustering, random_partition
from repro.data import synthetic

from benchmarks.common import dev_pct, row

DATASETS = ["travel", "npi", "creditcard", "plants", "mnist"]


def run(full: bool = False, k: int = 5):
    cap = None if full else 20_000
    print("# table6: dataset,K,sd_aba,sd_dev_PR5,sd_dev_rand,"
          "range_aba,range_dev_PR5,range_dev_rand")
    for name in DATASETS:
        x = synthetic.load(name, max_n=cap)
        xj = jnp.asarray(x)
        la = np.asarray(anticluster(xj, k=k).labels)
        sd_a, rg_a = (float(v) for v in diversity_stats(xj, jnp.asarray(la), k))
        lb = fast_anticlustering(x, k, n_partners=5, seed=0)
        sd_b, rg_b = (float(v) for v in diversity_stats(xj, jnp.asarray(lb), k))
        lr = random_partition(len(x), k, seed=0)
        sd_r, rg_r = (float(v) for v in diversity_stats(xj, jnp.asarray(lr), k))
        print(f"table6,{name},{k},{sd_a:.4f},{dev_pct(sd_a, sd_b):+.1f},"
              f"{dev_pct(sd_a, sd_r):+.1f},{rg_a:.4f},"
              f"{dev_pct(rg_a, rg_b):+.1f},{dev_pct(rg_a, rg_r):+.1f}",
              flush=True)
        row(f"table6/{name}/k{k}", 0.0,
            f"sd_aba={sd_a:.4f};sd_dev_PR5={dev_pct(sd_a, sd_b):+.0f}%;"
            f"sd_dev_rand={dev_pct(sd_a, sd_r):+.0f}%")


if __name__ == "__main__":
    from repro.compile_cache import use_compile_cache

    use_compile_cache()
    run()
