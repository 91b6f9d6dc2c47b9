"""Benchmark entrypoint: one module per paper table/figure.

``PYTHONPATH=src python -m benchmarks.run [--full] [--only table4,...]``
prints ``name,us_per_call,derived`` CSV rows plus per-table detail lines.
"""

from __future__ import annotations

import argparse
import time


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--full", action="store_true",
                    help="paper-scale datasets (hours on this CPU)")
    ap.add_argument("--only", default="",
                    help="comma list: table4,table6,fig7,table8,table9,"
                         "table11,kernels")
    args = ap.parse_args()
    only = set(filter(None, args.only.split(",")))

    from benchmarks import (fig7_hierarchical, kernel_bench, table4_quality,
                            table6_balance, table8_largek, table9_categories,
                            table11_kcut)

    jobs = [("table4", table4_quality), ("table6", table6_balance),
            ("fig7", fig7_hierarchical), ("table8", table8_largek),
            ("table9", table9_categories), ("table11", table11_kcut),
            ("kernels", kernel_bench)]
    print("name,us_per_call,derived")
    for name, mod in jobs:
        if only and name not in only:
            continue
        t0 = time.time()
        mod.run(full=args.full)
        print(f"# {name} done in {time.time() - t0:.1f}s", flush=True)


if __name__ == "__main__":
    from repro.compile_cache import use_compile_cache

    use_compile_cache()
    main()
