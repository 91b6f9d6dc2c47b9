"""Paper Figure 7: hierarchical decomposition settings -- objective vs
runtime for different factorizations of K (balanced factors fastest, quality
within a fraction of a percent)."""

from __future__ import annotations

import time

import numpy as np
import jax.numpy as jnp

from repro.anticluster import anticluster
from repro.core import objective_centroid
from repro.data import synthetic

from benchmarks.common import row


def run(full: bool = False):
    n = 200_000 if full else 40_000
    d = 64 if full else 32
    k = 1000 if full else 500
    x = synthetic.make("lowrank", n, d, seed=0)
    xj = jnp.asarray(x)
    plans = ([(k,)] if k <= 500 else []) + [
        (2, k // 2), (5, k // 5), (10, k // 10), (20, k // 20),
    ]
    print(f"# fig7: imagenet32-like n={n} d={d} K={k}: plan,ofv,dev%,cpu_s")
    best = None
    for plan in plans:
        t0 = time.time()
        labels = np.asarray(anticluster(xj, k=k, plan=plan,
                                        stats=False).labels)
        dt = time.time() - t0
        o = float(objective_centroid(xj, jnp.asarray(labels), k))
        if best is None:
            best = o
        print(f"fig7,{'x'.join(map(str, plan))},{o:.2f},"
              f"{(o - best) / best * 100:+.4f},{dt:.2f}", flush=True)
        row(f"fig7/plan{'x'.join(map(str, plan))}", dt,
            f"ofv={o:.1f};dev={(o - best) / best * 100:+.4f}%")


if __name__ == "__main__":
    from repro.compile_cache import use_compile_cache

    use_compile_cache()
    run()
