"""Paper Table 4: ABA vs fast_anticlustering (P-N5/P-R5/P-R50) vs Rand --
objective values and running times on the Table 2 dataset presets."""

from __future__ import annotations

import time

import numpy as np
import jax.numpy as jnp

from repro.anticluster import anticluster
from repro.core import objective_centroid
from repro.core.baselines import fast_anticlustering, random_partition
from repro.data import synthetic

from benchmarks.common import dev_pct, row

DATASETS = ["travel", "npi", "creditcard", "plants", "survival", "mnist"]


def run(full: bool = False, ks=(5, 50)):
    cap = None if full else 20_000
    print("# table4: dataset,K,ofv_aba,dev_PN5,dev_PR5,dev_PR50,dev_rand,"
          "cpu_aba_s,cpu_PN5_s,cpu_PR5_s,cpu_PR50_s")
    for name in DATASETS:
        x = synthetic.load(name, max_n=cap)
        xj = jnp.asarray(x)
        n = len(x)
        for k in ks:
            t0 = time.time()
            la = np.asarray(anticluster(xj, k=k, stats=False).labels)
            t_aba = time.time() - t0
            oa = float(objective_centroid(xj, jnp.asarray(la), k))
            devs, times = [], []
            for partners, mode in ((5, "nearest"), (5, "random"),
                                   (50, "random")):
                t0 = time.time()
                lb = fast_anticlustering(x, k, n_partners=partners,
                                         partner_mode=mode, seed=0)
                times.append(time.time() - t0)
                ob = float(objective_centroid(xj, jnp.asarray(lb), k))
                devs.append(dev_pct(oa, ob))
            lr = random_partition(n, k, seed=0)
            dev_r = dev_pct(oa, float(objective_centroid(xj, jnp.asarray(lr),
                                                         k)))
            print(f"table4,{name},{k},{oa:.2f},"
                  + ",".join(f"{d:+.4f}" for d in devs + [dev_r]) + ","
                  + f"{t_aba:.3f}," + ",".join(f"{t:.3f}" for t in times),
                  flush=True)
            row(f"table4/{name}/k{k}/aba", t_aba,
                f"ofv={oa:.1f};dev_PR5={devs[1]:+.4f}%")


if __name__ == "__main__":
    from repro.compile_cache import use_compile_cache

    use_compile_cache()
    run()
