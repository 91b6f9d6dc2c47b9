#!/usr/bin/env python3
"""Run one benchmark cell once and print its result as the last line.

    python3 bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

Everything is found by name from ``BENCHMARK.json``: the cell names a
configuration (``bench/configs/<config>.json``: shape, spec, route) and a
traffic mix (``bench/traffic/<mix>.json``: parameters the one driver below
reads); each per-layer metric is a reader ``bench/metrics/<metric>.py``;
the limits of the correctness comparison are ``bench/limits/<cell>.json``;
peaks are ``bench/peaks.json``.  Adding a cell adds files and entries only.

A run: set-up (inputs made on the device from ``--seed``, the persistent
compile cache, one warm-up of the cell's own shapes), then a window that
drives the user's entry point back to back for ``--seconds`` and closes at
the end of the first call that finishes after them.  With ``--trace 1``
one more call runs with the profiler on for a slice of it, and the result
line carries the per-layer metrics instead of the end-to-end ones.  After
the window the labels are compared with the plain reference
(``bench/reference.py``).  With no TPU, or fewer chips than the cell asks
for, it exits 2 and prints no result.
"""

from __future__ import annotations

import time

T_START = time.perf_counter()  # set-up counts from the process's start

import argparse  # noqa: E402
import importlib.util  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import pathlib  # noqa: E402
import sys  # noqa: E402
import tempfile  # noqa: E402
import threading  # noqa: E402
import types  # noqa: E402

ROOT = pathlib.Path(__file__).resolve().parents[1]
# Fixed path inside the checkout: the path is part of the cache's key.
CACHE_DIR = ROOT / ".jax_cache"

# Profiled slice of the traced call: it starts this share of a call's time
# in (past the centrality pass, inside the batch scan) and lasts this long.
# A 32-wide LAP's auction runs about 2.7 million device ops a second; the
# slice holds at least one of the streamed level's chunk gathers (one per
# 256 batch steps, about 0.33 s apart in imagenet8-mb128).
TRACE_AT = 0.4
TRACE_SLICE_S = 0.5


class NoChip(RuntimeError):
    """JAX found no TPU, or fewer chips than the cell asks for."""


def log(msg: str) -> None:
    print(msg, file=sys.stderr, flush=True)


# ---------------------------------------------------------------------------
# Finding a cell's pieces by name
# ---------------------------------------------------------------------------


def load_cell(name: str, root: pathlib.Path = ROOT) -> dict:
    """The cell's entry with its configuration, traffic, limits and
    metrics, all read from the files their names point to."""
    bench = json.loads((root / "BENCHMARK.json").read_text())
    cells = {w["name"]: w for w in bench["workloads"]}
    if name not in cells:
        raise SystemExit(f"unknown workload {name!r}; known: {sorted(cells)}")
    cell = cells[name]
    configs = {c["name"]: c for c in bench["configs"]}
    config = json.loads((root / configs[cell["config"]]["file"]).read_text())
    d = root / "bench"
    e2e = [m for m in bench["end_to_end"]
           if name in m.get("workloads", cells)]
    moved = {m["name"] for m in e2e}
    layer = [m for m in bench["per_layer"]
             if name in m.get("workloads", ()) or (
                 "workloads" not in m and m["moves"] in moved)]
    return {"cell": cell, "config": config,
            "traffic": json.loads(
                (d / "traffic" / f"{cell['traffic']}.json").read_text()),
            "limits": json.loads((d / "limits" / f"{name}.json").read_text()),
            "end_to_end": e2e, "per_layer": layer}


def load_reader(metric: str, root: pathlib.Path = ROOT):
    """The reader module ``bench/metrics/<metric>.py``."""
    path = root / "bench" / "metrics" / f"{metric}.py"
    spec = importlib.util.spec_from_file_location(
        f"bench_metric_{metric.replace('.', '_')}", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


# ---------------------------------------------------------------------------
# The devices and the compile counter
# ---------------------------------------------------------------------------


def devices_for(chips: int):
    import jax
    devs = jax.devices()
    if devs[0].platform != "tpu":
        raise NoChip(f"no TPU: JAX sees {devs[0].platform}")
    if len(devs) < chips:
        raise NoChip(f"the cell asks for {chips} chips, JAX sees {len(devs)}")
    return devs[:chips]


def peak_bytes(devices) -> int:
    """Peak bytes in use so far on the fullest of ``devices``."""
    return max((dev.memory_stats() or {}).get("peak_bytes_in_use", 0)
               for dev in devices)


class CompileMeter:
    """Compile seconds, compilations and persistent-cache hits that JAX
    reports through ``jax.monitoring``."""

    def __init__(self):
        self.compile_s, self.compiles, self.hits = 0.0, 0, 0

    def install(self):
        import jax
        jax.monitoring.register_event_duration_secs_listener(self._duration)
        jax.monitoring.register_event_listener(self._event)

    def _duration(self, event, secs, **_):
        if event == "/jax/core/compile/backend_compile_duration":
            self.compile_s += secs
            self.compiles += 1
        elif event.startswith("/jax/core/compile/"):
            self.compile_s += secs

    def _event(self, event, **_):
        if event == "/jax/compilation_cache/cache_hits":
            self.hits += 1


# ---------------------------------------------------------------------------
# The one traffic driver
# ---------------------------------------------------------------------------


class Traffic:
    """Drives the user's entry point as the traffic file says.

    ``cold``: ``inputs`` distinct matrices, one-shot ``anticluster()`` on
    each in turn.  ``warm``: one engine session; ``partition(x_0)`` and
    ``warm_epochs`` repartitions in set-up, then ``repartition(x_t,
    state)`` on a chain ``x_{t+1} = x_t + drift * N(0, 1)`` made on the
    device between calls.
    """

    def __init__(self, config: dict, traffic: dict, seed: int, dtype=None):
        import jax
        from repro.anticluster import AnticlusterSpec
        self.traffic, self.seed = traffic, seed
        self.kind = traffic["kind"]
        self.n, self.d = config["rows"], config["dims"]
        kw = {key: tuple(v) if isinstance(v, list) else v
              for key, v in config["spec"].items()}
        if dtype is not None:
            kw["dtype"] = dtype
        self.spec = AnticlusterSpec(**kw)
        self._jax = jax

    def setup(self) -> dict:
        """Make the inputs and warm up the cell's shapes; returns notes."""
        from bench import gen
        t0 = time.perf_counter()
        if self.kind == "cold":
            self.xs = self._jax.block_until_ready(gen.inputs(
                self.seed, self.traffic["inputs"], self.n, self.d))
        elif self.kind == "warm":
            from repro.anticluster import AnticlusterEngine
            self.x0 = self._jax.block_until_ready(gen.inputs(
                self.seed, 1, self.n, self.d)[0])
            self.engine = AnticlusterEngine(self.spec)
            self.epoch = 0
            self.x_t = self.x0
        else:
            raise ValueError(f"unknown traffic kind {self.kind!r}")
        data_s = time.perf_counter() - t0
        data_peak = peak_bytes(self._jax.local_devices())
        t0 = time.perf_counter()
        if self.kind == "cold":
            res = self._jax.block_until_ready(
                self._anticluster(self.xs[0]))
            route = (res.plan, res.solver)
        else:
            res, self.state = self.engine.partition(self.x0)
            for _ in range(self.traffic["warm_epochs"]):
                res, self.state = self._next_epoch()
            self._jax.block_until_ready((res, self.state))
            route = (res.plan, res.solver)
        return {"data_s": data_s, "data_peak": data_peak,
                "warmup_s": time.perf_counter() - t0,
                "route": route}

    def _anticluster(self, x):
        from repro.anticluster import anticluster
        return anticluster(x, self.spec)

    def _next_epoch(self):
        from bench import gen
        self.epoch += 1
        self.x_t = gen.drift(self.x_t, self.seed, self.epoch,
                             self.traffic["drift"])
        return self.engine.repartition(self.x_t, self.state)

    def call(self, i: int):
        """Call ``i`` of the window: labels on the host, and the tag that
        :meth:`x_of` takes to rebuild its input."""
        import numpy as np
        if self.kind == "cold":
            res = self._jax.block_until_ready(
                self._anticluster(self.xs[i % len(self.xs)]))
            return np.asarray(res.labels), i % len(self.xs)
        res, self.state = self._next_epoch()
        self._jax.block_until_ready((res, self.state))
        return np.asarray(res.labels), self.epoch

    def x_of(self, tag: int):
        """The input of a call, on the host, from the tag :meth:`call`
        gave.  Warm epochs are rebuilt from ``x_0`` by the same drift."""
        import numpy as np
        from bench import gen
        if self.kind == "cold":
            return np.asarray(self.xs[tag])
        x = self.x0
        for e in range(1, tag + 1):
            x = gen.drift(x, self.seed, e, self.traffic["drift"])
        return np.asarray(x)

    def telemetry(self):
        """The solver's telemetry of one cold solve (its own compiled twin);
        None on routes that report none."""
        import numpy as np
        from repro.anticluster import AnticlusterEngine
        eng = AnticlusterEngine(self.spec.evolve(telemetry=True))
        x = self.xs[0] if self.kind == "cold" else self.x0
        self._jax.block_until_ready(eng.partition(x))
        tele = eng.last_telemetry
        return None if tele is None else {
            k: np.asarray(v) for k, v in tele.items()}

    def free(self):
        for name in ("xs", "x0", "x_t", "engine", "state"):
            self.__dict__.pop(name, None)


# ---------------------------------------------------------------------------
# One run
# ---------------------------------------------------------------------------


def trace_call(traffic: Traffic, i: int, solve_s: float) -> dict:
    """Call ``i`` with the profiler on for a slice inside it; the compact
    trace (see ``bench/trace.py``)."""
    import jax
    from bench import trace
    worker = threading.Thread(target=traffic.call, args=(i,))
    with tempfile.TemporaryDirectory(prefix="bench-trace-") as tdir:
        worker.start()
        time.sleep(TRACE_AT * solve_s)
        jax.profiler.start_trace(tdir)
        time.sleep(TRACE_SLICE_S)
        jax.profiler.stop_trace()
        worker.join()
        return trace.load_xspace(tdir)


def run_cell(name: str, seed: int, seconds: float, trace_on: bool,
             root: pathlib.Path = ROOT, require_tpu: bool = True,
             dtype=None) -> dict:
    """Set up, measure, check; the result object the last line prints."""
    import jax
    import numpy as np
    from bench import reference, roofline, trace
    pieces = load_cell(name, root)
    cell, config, tr = pieces["cell"], pieces["config"], pieces["traffic"]
    devices = devices_for(cell["chips"]) if require_tpu else \
        jax.devices()[:cell["chips"]]
    kind = devices[0].device_kind
    meter = CompileMeter()
    meter.install()
    traffic = Traffic(config, tr, seed, dtype=dtype)
    notes = traffic.setup()
    setup_s = time.perf_counter() - T_START
    log(f"set-up: {setup_s:.3f} s (data {notes['data_s']:.3f} s, peak "
        f"{notes['data_peak']} bytes after it; warm-up "
        f"{notes['warmup_s']:.3f} s); compile {meter.compile_s:.3f} s, "
        f"{meter.compiles} compiles, {meter.hits} cache hits; route "
        f"{notes['route']}")

    # --- the window ---------------------------------------------------------
    compiles0 = meter.compiles + meter.hits
    calls = []
    t0 = time.perf_counter()
    while True:
        calls.append(traffic.call(len(calls)))
        elapsed = time.perf_counter() - t0
        if elapsed >= seconds:
            break
    compiles_in_window = meter.compiles + meter.hits - compiles0
    solve_s = elapsed / len(calls)
    peak = peak_bytes(devices)
    log(f"window: {len(calls)} calls in {elapsed:.3f} s; "
        f"{compiles_in_window} compiles in it; peak {peak} bytes")

    # --- traced slice and counters (trace runs) ----------------------------
    layer = {}
    breakdown = None
    busy = None
    if trace_on:
        t1 = time.perf_counter()
        red = trace.Reduced(trace_call(traffic, len(calls), solve_s))
        needs = set()
        readers = {m["name"]: load_reader(m["name"], root)
                   for m in pieces["per_layer"]}
        for mod in readers.values():
            needs.update(getattr(mod, "NEEDS", ()))
        tele = traffic.telemetry() if "telemetry" in needs else None
        # what a metric reader (bench/metrics/<metric>.py) sees
        run = types.SimpleNamespace(
            trace=red, compiles_in_window=compiles_in_window,
            telemetry=tele, peaks=roofline.peaks(kind), config=config)
        for m in pieces["per_layer"]:
            val = readers[m["name"]].read(run)
            if val is not None:
                layer[m["name"]] = {"value": float(val), "unit": m["unit"]}
        breakdown = red.breakdown()
        busy = (red.mean_busy_s, red.window_s)
        log(f"trace: slice {red.window_s:.6f} s, busy {red.mean_busy_s:.6f} "
            f"s, reduced in {time.perf_counter() - t1:.3f} s; "
            f"{json.dumps(layer)}")

    # --- correctness, after the program's state is freed --------------------
    t2 = time.perf_counter()
    k = config["spec"]["k"]
    rng = np.random.default_rng(seed & 0xFFFFFFFFFFFFFFFF)
    checked = sorted(rng.choice(len(calls), size=min(
        tr["checked_calls"], len(calls)), replace=False).tolist())
    tags = sorted({tag for _lab, tag in calls})
    xs_host = {tag: traffic.x_of(tag) for tag in tags}
    traffic.free()
    balance = [reference.balance_errors(lab, k) for lab, _tag in calls]
    regret = [reference.batch_regret(xs_host[calls[i][1]], calls[i][0],
                                     tuple(config["route"]["plan"]))
              for i in checked]
    lifts = {}
    for (lab, tag), bad in zip(calls, balance):
        if tag not in lifts and not bad:
            lifts[tag] = reference.objective_lift(xs_host[tag], lab, k)
    lim = pieces["limits"]
    numbers = {"balance_errors": (max(balance), lim["balance_errors"]),
               "batch_regret": (max(regret), lim["batch_regret"])}
    failed = sum(b > lim["balance_errors"] for b in balance) + sum(
        r > lim["batch_regret"] for r in regret)
    correct = failed == 0
    log(f"check: {len(calls)} calls, {len(checked)} replayed in "
        f"{time.perf_counter() - t2:.3f} s")

    if trace_on:
        metrics = layer
    else:
        values = {"solve_s": solve_s,
                  "objective_lift": sum(lifts.values()) / max(len(lifts), 1),
                  "peak_hbm_gb": peak / 1e9, "setup_s": setup_s}
        metrics = {m["name"]: {"value": values[m["name"]], "unit": m["unit"]}
                   for m in pieces["end_to_end"]}
    device = {"platform": devices[0].platform, "kind": kind,
              "count": len(devices), "memory_peak_bytes": peak}
    if busy is not None:
        device["busy_s"], device["window_s"] = busy
    out = {"correct": correct, "attempted": len(calls), "failed": failed,
           "metrics": metrics, "device": device}
    if breakdown is not None:
        out["breakdown"] = breakdown
    out["checks"] = {key: {"value": v, "limit": lim_v}
                     for key, (v, lim_v) in numbers.items()}
    for key, (v, lim_v) in numbers.items():
        log(f"check {key}: {v!r} (limit {lim_v!r})")
    return out


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    # the benchmark's own cache directory, whatever the environment says
    os.environ["JAX_COMPILATION_CACHE_DIR"] = str(CACHE_DIR)
    # libtpu keeps no log files (by default it writes them to /tmp)
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    sys.path[:0] = [str(ROOT / "src"), str(ROOT)]
    import jax
    from repro.compile_cache import use_compile_cache
    use_compile_cache()
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    try:
        out = run_cell(args.workload, args.seed, args.seconds,
                       bool(args.trace))
    except NoChip as e:
        log(f"bench: {e}; this benchmark runs on the chip only")
        return 2
    print(json.dumps(out), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
