import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=512"

"""S`Perf hillclimbing driver: lowers the three selected cells under a
sequence of hypothesis-driven configuration changes and records the roofline
terms for each (before/after pairs land in perf_results.json; the narrative
log lives in EXPERIMENTS.md S`Perf).

Cells (selection rationale in EXPERIMENTS.md):
  A falcon-mamba-7b/train_4k  -- worst memory term of the whole table
  B qwen2.5-14b/train_4k      -- flagship dense train; largest collective term
  C aba-pipeline/aba_1m       -- the paper's own technique on the mesh

Run:  PYTHONPATH=src python -m benchmarks.perf_iterations [--only A,B,C]
"""

import argparse
import json
import sys
import time

from repro.models.config import SSMSpec
from repro.launch import dryrun as D


def measure(name, arch, shape, overrides=None, aba_over=None):
    t0 = time.time()
    if arch == "aba-pipeline":
        rec = run_aba(shape, aba_over or {})
    elif arch == "pipeline-live":
        rec = run_pipeline_live(aba_over or {})
    else:
        rec = D.run_cell(arch, shape, multi_pod=False, overrides=overrides)
    rec["iter"] = name
    rec["wall_s"] = round(time.time() - t0, 1)
    line = {k: rec.get(k) for k in ("status", "dominant", "compile_s")}
    if rec.get("terms"):
        line |= {k: round(v, 4) for k, v in rec["terms"].items()}
        line["useful"] = round(rec.get("useful_flops_ratio") or 0, 3)
    print(f"[{name}] {line}", flush=True)
    return rec


def run_aba(shape, over):
    """ABA cell with plan/rounds/phases overrides."""
    import gc
    import jax
    import jax.numpy as jnp
    from jax.sharding import NamedSharding, PartitionSpec as P
    from repro.core.assignment import AuctionConfig
    from repro.core.sharded import sharded_core
    from repro.launch import hlo_cost
    import traceback

    spec = dict(D.ABA_CELLS[shape])
    spec.update(over)
    mesh = D.make_production_mesh(multi_pod=False)
    acfg = AuctionConfig(fixed_rounds=spec["rounds"],
                         n_phases=spec.get("phases", 4))
    rec = {"arch": "aba-pipeline", "shape": shape, "mesh": "16x16",
           "devices": 256, "overrides": {k: str(v) for k, v in over.items()}}
    try:
        def fn(x):
            return sharded_core(x, spec["k"], mesh, data_axes="auto",
                               max_k=spec.get("max_k", 512),
                               auction_config=acfg)

        x_sh = NamedSharding(mesh, P(("data",), None))
        jitted = jax.jit(fn, in_shardings=(x_sh,),
                         out_shardings=NamedSharding(mesh, P(("data",))))
        args = (jax.ShapeDtypeStruct((spec["n"], spec["d"]), jnp.float32),)
        t0 = time.time()
        with mesh:
            compiled = jitted.lower(*args).compile()
        text = compiled.as_text()
        hc = hlo_cost.analyze(text)
        mem = compiled.memory_analysis()
        flops, byts = float(hc["flops"]), float(hc["bytes"])
        coll = float(hc["collective_bytes"])
        mf = D.aba_model_flops(spec, mesh)
        terms = {"compute_s": flops / D.PEAK_FLOPS,
                 "memory_s": byts / D.HBM_BW,
                 "collective_s": coll / D.LINK_BW}
        rec.update(status="ok", compile_s=round(time.time() - t0, 1),
                   flops_per_device=flops, bytes_per_device=byts,
                   collective_bytes_per_device=hc["collectives"],
                   terms=terms, dominant=max(terms, key=terms.get),
                   model_flops_total=mf, hlo_flops_total=flops * 256,
                   useful_flops_ratio=mf / (flops * 256) if flops else None,
                   memory=dict(temp_bytes=mem.temp_size_in_bytes),
                   unknown_trip_whiles=hc["unknown_trip_whiles"])
        del compiled, text
        gc.collect()
    except Exception as e:
        rec.update(status="error", error=f"{type(e).__name__}: {e}",
                   trace=traceback.format_exc()[-2000:])
    return rec


def run_pipeline_live(over):
    """Live ``repro.train.pipeline`` cell: the dryrun rows above cost the
    ABA solve's HLO; this one actually consumes the pipeline's epoch
    iterator with a reduced registry model and records per-epoch walls --
    the overlap receipt at container scale (the heavy end-to-end arms live
    in ``benchmarks/pipeline_bench.py``)."""
    import traceback

    import numpy as np
    import jax
    import jax.numpy as jnp
    from repro.data.synthetic import lm_token_stream
    from repro.launch.mesh import make_host_mesh
    from repro.models import transformer as T
    from repro.models.registry import get_config
    from repro.train import ABAPipeline
    from repro.train.optimizer import OptConfig, adamw_init
    from repro.train.train_step import make_train_step

    spec = dict(n_docs=2048, batch=64, seq=16, epochs=3, refresh=True)
    spec.update(over)
    rec = {"arch": "pipeline-live", "shape": "train_small",
           "overrides": {k: str(v) for k, v in over.items()}}
    try:
        cfg = get_config("smollm-360m", reduced=True)
        mesh = make_host_mesh(1, 1)
        tokens, feats = lm_token_stream(spec["n_docs"], spec["seq"],
                                        cfg.vocab_size, seed=0)
        pipe = ABAPipeline(feats, spec["batch"], seed=0)
        params = T.init_params(cfg, jax.random.PRNGKey(0))
        opt = adamw_init(params)
        step = jax.jit(make_train_step(
            cfg, mesh, OptConfig(lr=3e-3, warmup_steps=5,
                                 decay_steps=len(pipe) * spec["epochs"]),
            loss_chunk=spec["seq"]))

        def drifted(e):
            r = np.random.default_rng(1000 + e)
            return (feats + 0.02 * r.normal(size=feats.shape)
                    ).astype(np.float32)

        walls, losses = [], []
        for ep in pipe.epochs(spec["epochs"],
                              features=drifted if spec["refresh"] else None):
            t0 = time.time()
            ls = []
            for idx in ep:
                batch = {"tokens": jnp.asarray(tokens[idx])}
                params, opt, m = step(params, opt, batch)
                ls.append(m["loss"])
            losses.append(float(ls[-1]))  # one coalesced sync per epoch
            walls.append(round(time.time() - t0, 3))
        toks = len(pipe) * spec["batch"] * spec["seq"]
        rec.update(status="ok", epoch_walls=walls, losses=losses,
                   compile_count=pipe.engine.compile_count,
                   tokens_per_s_warm=round(toks / min(walls[1:]), 1),
                   overlapped=pipe.overlapped)
    except Exception as e:
        rec.update(status="error", error=f"{type(e).__name__}: {e}",
                   trace=traceback.format_exc()[-2000:])
    return rec


ITERS = {
    "A": [
        ("A0 falcon train baseline (per-step scan)", "falcon-mamba-7b",
         "train_4k", {}, None),
        ("A1 falcon chunk=8 (fused SSM chunks)", "falcon-mamba-7b",
         "train_4k", {"ssm": SSMSpec(scan_chunk=8)}, None),
        ("A2 falcon chunk=16", "falcon-mamba-7b", "train_4k",
         {"ssm": SSMSpec(scan_chunk=16)}, None),
        ("A3 falcon chunk=32", "falcon-mamba-7b", "train_4k",
         {"ssm": SSMSpec(scan_chunk=32)}, None),
        # A4 = in-scan sharding anchors (code-level, applies to A1-A3 too)
        ("A4 falcon chunk=16 + scan anchors", "falcon-mamba-7b", "train_4k",
         {"ssm": SSMSpec(scan_chunk=16)}, None),
        ("A5 falcon chunk=16 + anchors + SP", "falcon-mamba-7b", "train_4k",
         {"ssm": SSMSpec(scan_chunk=16), "seq_parallel": True}, None),
    ],
    "B": [
        ("B0 qwen train baseline", "qwen2.5-14b", "train_4k", {}, None),
        ("B1 qwen embed dmodel-shard (no gather AR)", "qwen2.5-14b",
         "train_4k", {"embed_shard": "dmodel"}, None),
        ("B2 qwen chunk_kv=2048", "qwen2.5-14b", "train_4k",
         {"attn_chunk_kv": 2048}, None),
        ("B3 qwen chunk_kv=4096 (one kv step)", "qwen2.5-14b", "train_4k",
         {"attn_chunk_kv": 4096}, None),
        ("B4 qwen best combo", "qwen2.5-14b", "train_4k",
         {"embed_shard": "dmodel", "attn_chunk_kv": 2048}, None),
        # B5 = flash output anchor (code-level; baseline B0 predates it)
        ("B5 qwen flash out anchor", "qwen2.5-14b", "train_4k", {}, None),
        ("B6 qwen seq-parallel residuals", "qwen2.5-14b", "train_4k",
         {"seq_parallel": True}, None),
        ("B7 qwen anchor+SP+ck2048", "qwen2.5-14b", "train_4k",
         {"seq_parallel": True, "attn_chunk_kv": 2048}, None),
    ],
    "C": [
        ("C0 aba baseline flat K_local=512", "aba-pipeline", "aba_1m",
         None, {}),
        ("C1 aba hierarchical plan (Lemma 1: 8x64)", "aba-pipeline",
         "aba_1m", None, {"max_k": 64}),
        ("C2 aba hier + fewer rounds (64-col problems)", "aba-pipeline",
         "aba_1m", None, {"max_k": 64, "rounds": 96}),
        ("C3 aba hier + 2 eps phases", "aba-pipeline", "aba_1m",
         None, {"max_k": 64, "rounds": 96, "phases": 2}),
    ],
    "P": [
        ("P0 train pipeline, static membership", "pipeline-live",
         "train_small", None, {"refresh": False}),
        ("P1 train pipeline, overlapped per-epoch refresh", "pipeline-live",
         "train_small", None, {"refresh": True}),
    ],
}


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--only", default="A,B,C,P")
    ap.add_argument("--out", default="perf_results.json")
    args = ap.parse_args()
    try:
        results = json.load(open(args.out))
    except Exception:
        results = []
    done = {r.get("iter") for r in results}
    for group in args.only.split(","):
        for name, arch, shape, over, aba_over in ITERS[group.strip()]:
            if name in done:
                print(f"[skip] {name}", flush=True)
                continue
            results.append(measure(name, arch, shape, over, aba_over))
            with open(args.out + ".tmp", "w") as f:
                json.dump(results, f, indent=1)
            os.replace(args.out + ".tmp", args.out)


if __name__ == "__main__":
    from repro.compile_cache import use_compile_cache

    use_compile_cache()
    main()
