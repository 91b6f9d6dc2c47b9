"""Serving-tier load benchmark: SLOs vs offered QPS.

Open-loop load generation against the async :class:`AnticlusterRouter`:
requests (four near-shapes, 100-120 rows x 4 dims, k=5 -- all inside the
128-row bucket) arrive on a fixed schedule at each offered QPS, carry a
latency deadline, and the sweep records per-point SLOs:

* ``serve/{mode}/qps{q}``      -- wall_s = p50 latency, objective =
  achieved throughput (completed req/s)
* ``serve/{mode}/qps{q}/p99``  -- wall_s = p99 latency, objective =
  shed rate (deadline + backpressure rejections / offered)

Two modes at every point, same spec and same traffic:

* ``cont`` -- continuous batching (``max_group=8``, row buckets on):
  queued requests join the next in-flight stacked call, so under load the
  service amortizes one solve across up to 8 requests.
* ``seq`` -- sequential warm serving (``max_group=1``, row buckets off):
  the pre-router baseline; every request is its own warm solo solve.

The acceptance story is the crossover: at an offered load past seq's
single-stream capacity (~1/solve_time), cont sustains higher throughput at
equal offered QPS.  The run FAILS (exit 1) if cont never beats seq --
continuous batching earning its complexity is part of the gated
trajectory, not a narrative claim.

``--smoke`` sweeps two points (one in-capacity, one past seq capacity)
with short windows -- the CI step; the nightly full sweep adds the low-
and high-QPS extremes and longer windows.  Wall times are CI-runner
indicative; the regression gate's 2x factor + 5ms floor absorb jitter.
"""

from __future__ import annotations

import argparse
import sys
import time

import numpy as np

from repro import obs
from repro.serve import AnticlusterRouter, Rejected

from benchmarks.common import BenchRecorder, obs_disabled_overhead

# instrumented call sites a single served request crosses with tracing off
# (admit event + queue-wait event + serve/solve span + engine/repartition
# begin check + resolve latency record + headroom) -- the disabled-overhead
# gate multiplies the measured per-site cost by this
_OBS_SITES_PER_REQUEST = 6

SIZES = (100, 104, 112, 120)   # near-shapes sharing the 128-row bucket
D, K = 4, 5
DEADLINE_S = 2.0


def _make_router(mode: str) -> AnticlusterRouter:
    if mode == "cont":
        return AnticlusterRouter(k=K, plan=None, max_group=8)
    return AnticlusterRouter(k=K, plan=None, max_group=1, row_buckets=False)


def _prewarm(router: AnticlusterRouter, xs) -> None:
    """Compile every lane the sweep can hit, then one warm pass."""
    if router.max_group > 1:
        for g in (8, 4, 2, 1):  # stacked group buckets at rows=128
            router.partition_many([xs[i % len(xs)] for i in range(g)])
    else:
        for x in xs:            # one solo lane per distinct shape
            router.partition(x)
    for x in xs:
        router.partition(x)


def drive(router: AnticlusterRouter, qps: float, duration: float,
          xs) -> dict:
    """Open-loop: submit on a fixed schedule, then wait out the backlog."""
    interval = 1.0 / qps
    tickets, rejected_full = [], 0
    t0 = time.monotonic()
    i = 0
    while i * interval < duration:
        target = t0 + i * interval
        now = time.monotonic()
        if target > now:
            time.sleep(target - now)
        try:
            tickets.append(router.submit(xs[i % len(xs)],
                                         deadline=DEADLINE_S))
        except Rejected:
            rejected_full += 1
        i += 1
    for t in tickets:
        try:
            t.result(timeout=duration + 10 * DEADLINE_S)
        except Rejected:
            pass
    wall = time.monotonic() - t0
    lat = sorted(t.latency for t in tickets if t.rejection is None)
    offered = i
    shed = offered - len(lat)
    return dict(
        offered=offered,
        completed=len(lat),
        throughput=len(lat) / wall,
        shed_rate=shed / offered if offered else 0.0,
        p50=lat[len(lat) // 2] if lat else float("nan"),
        p99=lat[min(len(lat) - 1, int(len(lat) * 0.99))] if lat
            else float("nan"),
    )


def run(smoke: bool = False, json_path: str = "BENCH_serve.json") -> int:
    # smoke points: 100 QPS sits well inside BOTH modes' capacity (stable
    # latencies; seq saturates ~175 on a CI-class core, so 150 would be
    # bimodal run-to-run) and 400 is decisively past seq's capacity
    qps_points = [100.0, 400.0] if smoke else [50.0, 100.0, 400.0, 600.0]
    duration = 3.0 if smoke else 6.0
    assert not obs.enabled(), "timed arms must run with tracing disabled"
    rng = np.random.default_rng(0)
    xs = [rng.normal(size=(n, D)).astype(np.float32) for n in SIZES]
    rec = BenchRecorder()
    thr: dict[tuple[str, float], float] = {}
    print("mode,qps,p50_ms,p99_ms,throughput_rps,shed_rate", flush=True)
    for mode in ("cont", "seq"):
        router = _make_router(mode)
        try:
            _prewarm(router, xs)
            for qps in qps_points:
                s = drive(router, qps, duration, xs)
                thr[(mode, qps)] = s["throughput"]
                shape = f"128x{D}@{qps:g}qps"
                rec.add(f"serve/{mode}/qps{qps:g}", shape, s["p50"],
                        s["throughput"])
                rec.add(f"serve/{mode}/qps{qps:g}/p99", shape, s["p99"],
                        s["shed_rate"])
                print(f"{mode},{qps:g},{s['p50'] * 1e3:.2f},"
                      f"{s['p99'] * 1e3:.2f},{s['throughput']:.1f},"
                      f"{s['shed_rate']:.3f}", flush=True)
        finally:
            router.close()
    rec.write(json_path)
    # observability cost gate: with tracing disabled (asserted inside the
    # helper) the per-site cost times the sites one request crosses must
    # stay under 2% of the cheapest measured p50 -- tracing-off must be
    # free at serving granularity, deterministically (no A/B timing noise)
    per_site = obs_disabled_overhead()
    p50_min = min(r["wall_s"] for r in rec.rows
                  if not r["bench"].endswith("/p99"))
    overhead = per_site * _OBS_SITES_PER_REQUEST
    print(f"# obs disabled overhead: {per_site * 1e9:.0f} ns/site x "
          f"{_OBS_SITES_PER_REQUEST} sites = {overhead * 1e6:.2f} us/req "
          f"({overhead / p50_min * 100:.3f}% of min p50 "
          f"{p50_min * 1e3:.1f} ms)", flush=True)
    assert overhead <= 0.02 * p50_min, \
        "disabled tracing exceeds 2% of serve p50"
    wins = [q for q in qps_points
            if thr[("cont", q)] > 1.1 * thr[("seq", q)]]
    if wins:
        best = max(wins, key=lambda q: thr[("cont", q)] / thr[("seq", q)])
        print(f"# continuous batching beats sequential at qps={best:g}: "
              f"{thr[('cont', best)]:.1f} vs {thr[('seq', best)]:.1f} rps",
              flush=True)
        return 0
    print("# FAIL: continuous batching never beat sequential serving",
          flush=True)
    return 1


if __name__ == "__main__":
    from repro.compile_cache import use_compile_cache

    use_compile_cache()
    ap = argparse.ArgumentParser()
    ap.add_argument("--smoke", action="store_true",
                    help="two-point sweep with short windows (CI)")
    ap.add_argument("--json", default="BENCH_serve.json")
    args = ap.parse_args()
    sys.exit(run(smoke=args.smoke, json_path=args.json))
