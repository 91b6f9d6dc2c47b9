"""The plain reference: what a partition from ABA has to satisfy.

Assignment-Based Anticlustering (arXiv:2601.06351, Algorithm 1, with the
hierarchy of Section 4.4) sorts the rows of a group by their distance to
the group's centroid, farthest first, cuts that order into batches of K
rows, gives the first batch one cluster each, and assigns every later batch
by a linear assignment problem (LAP) that maximizes the summed squared
distance of each row to the running centroid of its cluster.  A hierarchy
(K_1, ..., K_L) does this per level inside every group the levels above
made; global labels compose as ``g * K_l + sub``.

This module replays that definition on the labels a timed call returned,
in float64 and with an exact LAP (scipy's Hungarian solver), and reads two
numbers:

``balance_errors``
    clusters whose size leaves {floor(n/K), ceil(n/K)}, plus labels outside
    [0, K).  Exact: the limit is 0.
``batch_regret``
    the mean, over every batch of every level, of the share of that LAP's
    attainable gain over a random assignment which the returned assignment
    leaves out, at the centroids its own earlier batches imply.  A batch
    whose K rows do not land in K distinct clusters scores 1.  A wrong
    centrality order (rows in the wrong batch) or a LAP solved badly both
    raise it.

Nothing here imports the program: numpy and scipy only.
"""

from __future__ import annotations

import itertools
import math

import numpy as np
from scipy.optimize import linear_sum_assignment

# rows per block of the float64 distance pass (bounds host memory)
_BLOCK = 1 << 16
# Relative resolution of a squared distance summed in float32 over up to
# ~1,000 columns: a program that computes in float32 may order two rows
# whose float64 distances lie this close either way round.
TIE_RTOL = 1e-5
# most rows a tie at one batch cut may hold (larger ties are not searched)
_TIE_MAX = 8


def centrality(x: np.ndarray) -> tuple[np.ndarray, np.ndarray]:
    """(order, distance): rows by float64 squared distance to the centroid,
    farthest first (stable, as Algorithm 1 sorts)."""
    n = x.shape[0]
    mu = np.sum(x, axis=0, dtype=np.float64) / n
    dist = np.empty(n, np.float64)
    for s in range(0, n, _BLOCK):
        blk = x[s:s + _BLOCK].astype(np.float64) - mu
        dist[s:s + _BLOCK] = np.einsum("ij,ij->i", blk, blk)
    order = np.argsort(-dist, kind="stable")
    return order, dist[order]


def resolve_ties(order: np.ndarray, dist: np.ndarray, sub: np.ndarray,
                 k: int) -> np.ndarray:
    """The order with rows tied at a batch cut moved across it where that
    gives the earlier batch distinct labels.

    Rows whose distances lie within ``TIE_RTOL`` of a cut are in either
    batch to float32 resolution; the program's order decides, and only its
    labels show it.  Any other repeat stays, and scores 1.
    """
    order = order.copy()
    for c in range(k, len(order), k):
        if len(np.unique(sub[order[c - k:c]])) == k:
            continue
        tol = TIE_RTOL * dist[c]
        lo, hi = c - 1, c + 1
        while lo > c - k and dist[lo - 1] - dist[c] <= tol:
            lo -= 1
        while hi < len(order) and dist[c - 1] - dist[hi] <= tol:
            hi += 1
        if dist[c - 1] - dist[c] > tol or hi - lo > _TIE_MAX:
            continue
        head = order[c - k:lo]
        tied = list(order[lo:hi])
        for pick in itertools.combinations(range(len(tied)), c - lo):
            rows = np.concatenate([head, [tied[i] for i in pick]])
            if len(np.unique(sub[rows])) == k:
                rest = [t for i, t in enumerate(tied) if i not in pick]
                order[lo:hi] = [tied[i] for i in pick] + rest
                break
    return order


def group_regret(x: np.ndarray, sub: np.ndarray, k: int) -> tuple[float, int]:
    """(summed regret, batches) of one group's level assignment ``sub``."""
    order, dist = centrality(x)
    order = resolve_ties(order, dist, sub, k)
    sums = np.zeros((k, x.shape[1]), np.float64)
    counts = np.zeros(k, np.int64)
    total, n_batches = 0.0, 0
    for b in range(0, len(order), k):
        rows = order[b:b + k]
        lab = sub[rows]
        xb = x[rows].astype(np.float64)
        n_batches += 1
        distinct = (lab.min() >= 0 and lab.max() < k
                    and len(np.unique(lab)) == len(lab))
        if not distinct:
            total += 1.0
        elif b:
            cents = sums / np.maximum(counts, 1)[:, None]
            # reduced cost: the row constant ||x||^2 cancels in every gap
            cost = -2.0 * xb @ cents.T + np.einsum("kd,kd->k", cents, cents)
            r, c = linear_sum_assignment(cost, maximize=True)
            best = cost[r, c].sum()
            got = cost[np.arange(len(lab)), lab].sum()
            rand = cost.mean(axis=1).sum()
            if best - rand > 0:
                total += float(np.clip((best - got) / (best - rand), 0, 1))
        ok = (lab >= 0) & (lab < k)
        np.add.at(sums, lab[ok], xb[ok])
        np.add.at(counts, lab[ok], 1)
    return total, n_batches


def batch_regret(x: np.ndarray, labels: np.ndarray,
                 plan: tuple[int, ...]) -> float:
    """Mean regret over every batch of every level (see module doc)."""
    total, count = 0.0, 0
    for level, k_l in enumerate(plan):
        below = math.prod(plan[level + 1:])
        parent = np.floor_divide(labels, below * k_l)
        sub = np.floor_divide(labels, below) % k_l
        if level == 0:
            groups = [np.arange(len(labels))]
        else:
            srt = np.argsort(parent, kind="stable")
            cuts = np.flatnonzero(np.diff(parent[srt])) + 1
            groups = np.split(srt, cuts)
        for rows in groups:
            t, c = group_regret(x[rows], sub[rows], k_l)
            total += t
            count += c
    return total / max(count, 1)


def balance_errors(labels: np.ndarray, k: int) -> int:
    """Clusters off the balanced size, plus labels out of range."""
    n = labels.shape[0]
    bad = int(np.sum((labels < 0) | (labels >= k)))
    ok = labels[(labels >= 0) & (labels < k)]
    sizes = np.bincount(ok, minlength=k)
    return bad + int(np.sum((sizes < n // k) | (sizes > -(-n // k))))


def between_ss(x: np.ndarray, labels: np.ndarray, k: int) -> float:
    """sum_k n_k ||mu_k - mu||^2 in float64: the total sum of squares
    minus the centroid objective, computed without that cancellation."""
    n = x.shape[0]
    counts = np.bincount(labels, minlength=k).astype(np.float64)
    sums = np.zeros((k, x.shape[1]), np.float64)
    order = np.argsort(labels, kind="stable")
    for s in range(0, n, _BLOCK):
        blk = order[s:s + _BLOCK]
        lab = labels[blk]  # sorted: each label is one run of the block
        starts = np.concatenate([[0], np.flatnonzero(np.diff(lab)) + 1])
        sums[lab[starts]] += np.add.reduceat(
            x[blk].astype(np.float64), starts, axis=0)
    mu = sums.sum(axis=0) / n
    dev = sums - counts[:, None] * mu
    return float(np.sum(np.einsum("kd,kd->k", dev, dev)
                        / np.maximum(counts, 1)))


def total_ss(x: np.ndarray) -> float:
    """sum_i ||x_i - mu||^2 in float64."""
    n = x.shape[0]
    mu = np.sum(x, axis=0, dtype=np.float64) / n
    tot = 0.0
    for s in range(0, n, _BLOCK):
        blk = x[s:s + _BLOCK].astype(np.float64) - mu
        tot += float(np.einsum("ij,ij->", blk, blk))
    return tot


def random_between_ss(tot: float, n: int, k: int) -> float:
    """The expected ``between_ss`` of a uniformly random partition into k
    groups of fixed sizes: each group of m rows has E[m ||mean - mu||^2] =
    (tot / n) (n - m) / (n - 1), and these sum to tot (k - 1) / (n - 1)."""
    return tot * (k - 1) / (n - 1)


def objective_lift(x: np.ndarray, labels: np.ndarray, k: int) -> float:
    """100 * (objective - random objective) / random objective, where the
    objective is the centroid form sum_k sum_{i in C_k} ||x_i - mu_k||^2
    and the random objective its expectation over random partitions of the
    same sizes (exact, so the baseline adds no sampling noise)."""
    tot = total_ss(x)
    b = between_ss(x, labels, k)
    b_rand = random_between_ss(tot, x.shape[0], k)
    return 100.0 * (b_rand - b) / (tot - b_rand)
