#!/usr/bin/env python3
"""Read the correctness numbers of the sound program and of its control.

    python3 bench/control.py --workload <cell> --sound <seeds> --control <seeds>

Runs the cell in one process, once per seed, each with a window of one
call: the sound program on the ``--sound`` seeds (the lower readings of
``bench/limits/<cell>.json``), then the control on the ``--control`` seeds
(the upper readings).  The control is the program's own bfloat16 feature
path (``AnticlusterSpec(dtype=bfloat16)``), the nearest precision below
the float32 the configurations state.  Prints one JSON line per run.  The
benchmark's own runs never run this.
"""

from __future__ import annotations

import argparse
import json
import os
import pathlib
import sys

ROOT = pathlib.Path(__file__).resolve().parents[1]


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--sound", default="", help="comma-separated seeds")
    ap.add_argument("--control", default="", help="comma-separated seeds")
    args = ap.parse_args(argv)
    sys.path[:0] = [str(ROOT / "src"), str(ROOT)]
    from bench import run
    os.environ["JAX_COMPILATION_CACHE_DIR"] = str(run.CACHE_DIR)
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    import jax.numpy as jnp
    from repro.compile_cache import use_compile_cache
    use_compile_cache()
    runs = [(s, None) for s in args.sound.split(",") if s] + \
        [(s, jnp.bfloat16) for s in args.control.split(",") if s]
    for seed, dtype in runs:
        try:
            out = run.run_cell(args.workload, int(seed), 0.0, False,
                               dtype=dtype)
        except run.NoChip as e:
            run.log(f"control: {e}")
            return 2
        print(json.dumps({"seed": int(seed), "control": dtype is not None,
                          "correct": out["correct"],
                          "checks": out["checks"]}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
