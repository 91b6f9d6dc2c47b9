"""The benchmark's arithmetic: roofline counts against hand-counted shapes,
the peaks table, the on-device generator against
``repro.data.synthetic.make``, and the baseline of ``objective_lift``."""

from __future__ import annotations

import pathlib
import sys

import numpy as np
import pytest

ROOT = pathlib.Path(__file__).resolve().parents[2]
sys.path.insert(0, str(ROOT))

from bench import gen, reference, roofline  # noqa: E402

V5E = roofline.peaks("TPU v5 lite")


def test_peaks_of_v5e_and_their_source():
    assert V5E["bf16_flop_per_s"] == 197e12
    assert V5E["hbm_byte_per_s"] == 819e9
    assert V5E["hbm_bytes"] == 16e9
    text = roofline.PEAKS_FILE.read_text()
    assert "Google Cloud documentation, TPU v5e" in text


def test_unknown_device_kind_is_an_error():
    with pytest.raises(KeyError, match="no peaks"):
        roofline.peaks("TPU v9 imaginary")


@pytest.mark.parametrize("G,m,k,d,ops,nbytes", [
    # one 32-wide level-1 LAP round at d = 192:
    # 2*32*32*192 ops; 4 * (32*192 + 32*192 + 2*32 + 3*32) bytes
    (1, 32, 32, 192, 393_216, 49_792),
    # the stacked level-2 round, 32 groups of the same
    (32, 32, 32, 192, 12_582_912, 1_593_344),
    # a 512-wide flat round at d = 784
    (1, 512, 512, 784, 411_041_792, 3_221_504),
])
def test_bid_top2_work(G, m, k, d, ops, nbytes):
    assert roofline.bid_top2_work(G, m, k, d) == (ops, nbytes)


def test_gather_work():
    # 8,192 rows of the (n, 1, 256) table into (8192, 192), plus indices
    assert roofline.gather_work(8192, 192, 256) == (0.0, 4 * 8192 * 449)


@pytest.mark.parametrize("ops,nbytes,secs,pct,bound", [
    (197e12, 0.0, 1.0, 100.0, "compute"),
    (0.0, 819e9, 2.0, 50.0, "bandwidth"),
    (393_216, 49_792, 1e-6, 100 * 49_792 / 819e9 / 1e-6, "bandwidth"),
])
def test_roofline_share(ops, nbytes, secs, pct, bound):
    got, b = roofline.roofline_share(ops, nbytes, secs, V5E)
    assert got == pytest.approx(pct) and b == bound


def test_lowrank_matches_the_program_generator():
    """Column moments of the device generator match ``make`` (standardized
    columns: mean 0, sd 1) and so does its low-rank spectrum, within
    sampling error; the bits need not match."""
    from repro.data.synthetic import make
    n, d = 4096, 64
    ours = np.asarray(gen.inputs(2**31 + 9, 1, n, d)[0], np.float64)
    theirs = make("lowrank", n, d, seed=9).astype(np.float64)
    for x in (ours, theirs):
        assert np.abs(x.mean(0)).max() < 1e-5
        assert np.abs(x.std(0) - 1).max() < 1e-4
    r = gen.rank(d)
    share = [np.linalg.svd(x, compute_uv=False) ** 2 for x in (ours, theirs)]
    top = [s[:r].sum() / s.sum() for s in share]
    assert abs(top[0] - top[1]) < 0.02
    assert top[0] > 0.85  # u @ v dominates 0.3 * noise at r = d / 8


def test_inputs_repeat_from_a_seed_and_differ_across_seeds():
    a = gen.inputs(2**33 + 1, 2, 256, 16)
    b = gen.inputs(2**33 + 1, 2, 256, 16)
    c = gen.inputs(1, 2, 256, 16)
    assert np.array_equal(np.asarray(a[0]), np.asarray(b[0]))
    assert not np.array_equal(np.asarray(a[0]), np.asarray(a[1]))
    assert not np.array_equal(np.asarray(a[0]), np.asarray(c[0]))


def _gram_spectrum(x):
    """The pairwise distances' invariants: sorted row norms and singular
    values about the centroid."""
    x = np.asarray(x, np.float64)
    x = x - x.mean(0)
    return np.sort((x * x).sum(1)), np.linalg.svd(x, compute_uv=False)


@pytest.mark.parametrize("seed", [0, 2**31 + 5, 2**40 + 3])
def test_every_seed_poses_the_same_problem(seed):
    """Each seed relabels the same base matrix: rows permuted, columns'
    signs flipped, so all distances are kept."""
    base = gen.inputs(1, 1, 512, 24)[0]
    ours = gen.inputs(seed, 1, 512, 24)[0]
    for u, v in zip(_gram_spectrum(base), _gram_spectrum(ours)):
        np.testing.assert_allclose(u, v, rtol=1e-5, atol=1e-6)
    assert sorted(np.abs(np.asarray(ours)).ravel().tolist()) == \
        sorted(np.abs(np.asarray(base)).ravel().tolist())


def test_drift_chain_is_the_same_chain_for_every_seed():
    a = gen.inputs(3, 1, 256, 16)[0]
    b = gen.inputs(2**35 + 3, 1, 256, 16)[0]
    for e in (1, 2):
        a, b = gen.drift(a, 3, e, 0.05), gen.drift(b, 2**35 + 3, e, 0.05)
    for u, v in zip(_gram_spectrum(a), _gram_spectrum(b)):
        np.testing.assert_allclose(u, v, rtol=1e-5, atol=1e-6)
    assert not np.array_equal(np.asarray(a), np.asarray(b))


@pytest.mark.parametrize("n,k", [(64, 4), (60, 7)])
def test_random_objective_is_the_mean_over_random_partitions(n, k):
    """The exact baseline of ``objective_lift`` against the mean between-
    cluster sum of squares of many seeded random partitions."""
    rng = np.random.default_rng(n * k)
    x = rng.normal(size=(n, 3))
    tot = reference.total_ss(x)
    draws = [reference.between_ss(x, rng.permutation(np.arange(n) % k), k)
             for _ in range(4000)]
    sem = np.std(draws) / np.sqrt(len(draws))
    assert abs(np.mean(draws) - reference.random_between_ss(tot, n, k)) \
        < 4 * sem


def test_every_seed_gives_the_auction_the_same_work():
    """The seed changes the bits, never the work: the solver's round
    counts are equal on two seeds' inputs."""
    from repro.anticluster import AnticlusterEngine, AnticlusterSpec
    eng = AnticlusterEngine(AnticlusterSpec(k=32, telemetry=True))
    rounds = []
    for seed in (5, 2**33 + 5):
        eng.partition(gen.inputs(seed, 1, 2048, 64)[0])
        rounds.append(np.asarray(eng.last_telemetry["rounds"]))
    np.testing.assert_array_equal(rounds[0], rounds[1])
