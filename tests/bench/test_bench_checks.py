"""The correctness comparison decides: a sound run passes; the control
(the program's own bfloat16 feature path) and each planted fault fail.

Each case drives a whole run of ``bench/run.py`` (set-up, window, check)
at a small size on the CPU, with the harness's look for a chip skipped,
and the timed path broken underneath where the case says so.
"""

from __future__ import annotations

import dataclasses
import json
import pathlib
import shutil
import sys

import jax.numpy as jnp
import numpy as np
import pytest

ROOT = pathlib.Path(__file__).resolve().parents[2]
sys.path.insert(0, str(ROOT))

from bench import reference, run  # noqa: E402

SEED = 2**31 + 41

TINY = {
    # flat dense auction, as mnist-k512
    "tiny-flat": {"rows": 2048, "dims": 64, "spec": {"k": 32},
                  "route": {"mode": "flat", "plan": [32]}},
    # streamed level 1 under a 2-level plan, as imagenet8-mb128
    "tiny-hier": {"rows": 4096, "dims": 64,
                  "spec": {"k": 64, "plan": [8, 8], "chunk_size": 1024},
                  "route": {"mode": "hier", "plan": [8, 8]}},
}
CELLS = {"tiny-flat.cold": ("tiny-flat", "cold"),
         "tiny-hier.cold": ("tiny-hier", "cold"),
         "tiny-hier.warm": ("tiny-hier", "warm")}


@pytest.fixture(scope="module")
def root(tmp_path_factory):
    """A checkout of the benchmark whose cells are small."""
    tmp = tmp_path_factory.mktemp("bench")
    shutil.copytree(ROOT / "bench", tmp / "bench")
    bench = json.loads((ROOT / "BENCHMARK.json").read_text())
    bench["configs"] = [{"name": c, "source": "x", "reduced": [], "why": "x",
                         "file": f"bench/configs/{c}.json"} for c in TINY]
    bench["workloads"] = [{"name": n, "config": c, "traffic": t, "why": "x",
                           "chips": 1} for n, (c, t) in CELLS.items()]
    for m in bench["per_layer"]:
        m.pop("workloads", None)
    (tmp / "BENCHMARK.json").write_text(json.dumps(bench))
    for c, cfg in TINY.items():
        (tmp / "bench/configs" / f"{c}.json").write_text(json.dumps(cfg))
    limits = json.loads(
        (ROOT / "bench/limits/imagenet8-mb128.cold.json").read_text())
    for n in CELLS:
        (tmp / "bench/limits" / f"{n}.json").write_text(json.dumps(limits))
    return tmp


def go(root, cell, **kw):
    return run.run_cell(cell, SEED, 0.5, False, root=root, require_tpu=False,
                        **kw)


@pytest.mark.parametrize("cell", ["tiny-flat.cold", "tiny-hier.cold",
                                  "tiny-hier.warm"])
def test_sound_run_is_correct(root, cell):
    out = go(root, cell)
    assert out["correct"], out["checks"]
    assert out["failed"] == 0 and out["attempted"] >= 1
    assert list(out)[-1] == "checks"
    assert out["metrics"]["objective_lift"]["value"] > 0


@pytest.mark.parametrize("cell", ["tiny-flat.cold", "tiny-hier.cold"])
def test_control_bfloat16_features_fail(root, cell):
    """The control: the program's ``dtype=bfloat16`` path, the nearest
    precision below the float32 the configurations state."""
    out = go(root, cell, dtype=jnp.bfloat16)
    assert not out["correct"]
    assert out["checks"]["batch_regret"]["value"] > \
        out["checks"]["batch_regret"]["limit"]


def _patch_labels(monkeypatch, alter):
    import repro.anticluster as ac
    real = ac.anticluster

    def broken(x, spec=None, **kw):
        res = real(x, spec, **kw)
        return dataclasses.replace(
            res, labels=alter(res.labels, res.k, x))

    monkeypatch.setattr(ac, "anticluster", broken)


def test_fault_answer_altered_fails(root, monkeypatch):
    """One label changed where the program produced it."""
    _patch_labels(monkeypatch,
                  lambda lab, k, _x: lab.at[0].set((lab[0] + 1) % k))
    out = go(root, "tiny-flat.cold")
    assert not out["correct"]
    assert out["checks"]["balance_errors"]["value"] > 0


def test_fault_half_the_rows_left_out_fails(root, monkeypatch):
    """The program solves the first half of the rows and copies its labels
    onto the rest: still balanced, no longer ABA."""
    import repro.anticluster as ac
    real = ac.anticluster

    def broken(x, spec=None, **kw):
        half = x.shape[0] // 2
        res = real(x[:half], spec, **kw)
        return dataclasses.replace(
            res, labels=jnp.concatenate([res.labels, res.labels]))

    monkeypatch.setattr(ac, "anticluster", broken)
    out = go(root, "tiny-flat.cold")
    assert not out["correct"]
    assert out["checks"]["balance_errors"]["value"] == 0
    assert out["checks"]["batch_regret"]["value"] > \
        out["checks"]["batch_regret"]["limit"]


def test_fault_state_returned_unchanged_fails(root, monkeypatch):
    """A warm repartition that hands back the previous epoch's answer."""
    import repro.anticluster as ac
    real = ac.AnticlusterEngine.repartition
    kept = {}

    def broken(self, x, state, **kw):
        if "res" in kept:
            return kept["res"], state
        res, st = real(self, x, state, **kw)
        kept["res"] = res
        return res, st

    monkeypatch.setattr(ac.AnticlusterEngine, "repartition", broken)
    out = go(root, "tiny-hier.warm")
    assert not out["correct"]
    assert out["checks"]["batch_regret"]["value"] > \
        out["checks"]["batch_regret"]["limit"]


def test_reference_replay_matches_a_hand_built_partition():
    """ABA by hand on 8 rows, K = 4: the first batch (farthest rows) takes
    one cluster each; the next batch's exact LAP is scored 0 regret, and
    swapping two of its labels scores more."""
    rng = np.random.default_rng(0)
    x = rng.normal(size=(8, 3)).astype(np.float32)
    order, _dist = reference.centrality(x)
    lab = np.empty(8, np.int64)
    lab[order[:4]] = np.arange(4)
    cents = x[order[:4]].astype(np.float64)
    xb = x[order[4:]].astype(np.float64)
    cost = ((xb[:, None, :] - cents[None]) ** 2).sum(-1)
    from scipy.optimize import linear_sum_assignment
    r, c = linear_sum_assignment(cost, maximize=True)
    lab[order[4:][r]] = c
    assert reference.batch_regret(x, lab, (4,)) == pytest.approx(0, abs=1e-12)
    assert reference.balance_errors(lab, 4) == 0
    worse = lab.copy()
    a, b = order[4], order[5]
    worse[a], worse[b] = lab[b], lab[a]
    assert reference.batch_regret(x, worse, (4,)) > 0
    collide = lab.copy()
    collide[order[5]] = lab[order[4]]
    assert reference.batch_regret(x, collide, (4,)) >= 0.5
    assert reference.balance_errors(collide, 4) > 0


@pytest.mark.parametrize("gap,repaired", [(1e-5, True), (1e-3, False)])
def test_ties_at_a_batch_cut(gap, repaired):
    """Two rows whose distances differ below float32 resolution may sit
    in either batch: a program order that put them the other way round is
    no collision.  A real gap is."""
    dist = np.array([10.0, 5.0 + gap, 5.0, 1.0])
    order = np.arange(4)
    sub = np.array([0, 0, 1, 1])  # the program batched rows (0, 2), (1, 3)
    got = reference.resolve_ties(order, dist, sub, 2)
    if repaired:
        assert list(got) == [0, 2, 1, 3]
    else:
        assert list(got) == [0, 1, 2, 3]
