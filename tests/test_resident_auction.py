"""The resident auction kernel against the XLA round loop, bit for bit.

``kernels/auction.py`` runs one eps phase of the dense auction inside one
Pallas kernel; ``_auction_phase``'s XLA loop is the reference.  Every case
forces the kernel in interpret mode (on CPU the dispatcher keeps the XLA
loop) and asserts the same assignment, prices and round count exactly --
per phase, and through a whole ``auction_solve(..., return_stats=True)``.
"""

import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core.aba import _MASK_COST
from repro.core.assignment import (AuctionConfig, _auction_phase,
                                   _top2_batched, auction_solve)
from repro.kernels import ops


def _phase(cost, prices, eps, *, max_rounds=None, skip=None, seeded=False,
           force=None):
    n = cost.shape[-1]

    def run(c, p, e):
        top2 = lambda q: _top2_batched(c - q[:, None, :])
        resident = None if force is None else ops.resident_auction(
            c, force=force)
        return _auction_phase(top2, p, e, max_rounds or 50 * n + 1000,
                              skip=skip, seed_top2=top2(p) if seeded else None,
                              resident=resident)
    return jax.jit(run)(cost, prices, eps)


def _solve(cost, prices=None, vmap=False, force=None, monkeypatch=None):
    """auction_solve with stats, vmapped over the stack if ``vmap``;
    ``force`` pins the phase path in a fresh trace (the jit cache would
    otherwise reuse the XLA one)."""
    solve = functools.partial(auction_solve, return_stats=True)
    if force is not None:
        real = ops.resident_auction_path
        monkeypatch.setattr(ops, "resident_auction_path",
                            lambda n, f=None: real(n, force))
        solve = jax.jit(functools.partial(auction_solve.__wrapped__,
                                          return_stats=True))
    if vmap:
        return jax.vmap(solve)(cost)
    return solve(cost, prices=prices)


def _normal(seed, *shape):
    return jnp.asarray(np.random.default_rng(seed).normal(size=shape),
                       jnp.float32)


def _span_eps(cost, div):
    return (jnp.max(cost, axis=(1, 2)) - jnp.min(cost, axis=(1, 2))) / div


def _cold_phase(B, n, div):
    cost = _normal(B * 1000 + n, B, n, n)
    return dict(cost=cost, prices=jnp.zeros((B, n), jnp.float32),
                eps=_span_eps(cost, div))


def _warm_phase(skip):
    B, n = len(skip), 40
    cost = _normal(7, B, n, n)
    _, prices = auction_solve(cost, return_prices=True)
    drifted = cost + 0.05 * _normal(8, B, n, n)
    return dict(cost=drifted, prices=prices, eps=_span_eps(cost, 4 * n),
                skip=jnp.asarray(skip), seeded=True)


def _masked_stack():
    """Categorical mask entries and constant-0 dummy rows, as aba_core
    builds them (stratified runs, padded router lanes)."""
    B, n = 3, 48
    cost = _normal(11, B, n, n)
    full = np.random.default_rng(12).random((B, n, n)) < 0.3
    cost = jnp.where(jnp.asarray(full), _MASK_COST, cost)
    return cost.at[:, 40:, :].set(0.0)


PHASES = {
    "one_phase_1x512": lambda: _cold_phase(1, 512, 8.0),
    "stack_4x32": lambda: _cold_phase(4, 32, 4.0 * 32),
    "ragged_2x100": lambda: _cold_phase(2, 100, 8.0),
    "ragged_3x300": lambda: _cold_phase(3, 300, 8.0),
    "stack_17x128": lambda: _cold_phase(17, 128, 8.0),
    "warm_skip": lambda: _warm_phase([True, False, True]),
    "all_skipped_seeded": lambda: _warm_phase([True, True]),
    "max_rounds_cap": lambda: dict(_cold_phase(2, 64, 4.0 * 64),
                                   max_rounds=5),
}
SOLVES = {
    "masked_solve": lambda: dict(cost=_masked_stack()),
    "solve_stats": lambda: dict(cost=_normal(21, 2, 64, 64)),
    "solve_stats_warm": lambda: dict(
        cost=_normal(22, 3, 64, 64) + 0.05 * _normal(23, 3, 64, 64),
        prices=auction_solve(_normal(22, 3, 64, 64),
                             return_prices=True)[1].at[0].set(0.0)),
    "vmapped_solve": lambda: dict(cost=_normal(24, 3, 24, 24), vmap=True),
}


@pytest.mark.parametrize("case", list(PHASES) + list(SOLVES))
def test_resident_kernel_matches_xla_loop_bitwise(case, monkeypatch):
    if case in PHASES:
        kw = PHASES[case]()
        want, got = _phase(**kw), _phase(**kw, force="pallas")
    else:
        kw = SOLVES[case]()
        want = _solve(**kw)
        got = _solve(**kw, force="pallas", monkeypatch=monkeypatch)
    leaves_w, leaves_g = jax.tree.leaves(want), jax.tree.leaves(got)
    assert len(leaves_w) == len(leaves_g)
    for w, g in zip(leaves_w, leaves_g):
        np.testing.assert_array_equal(np.asarray(g), np.asarray(w))
    if case == "max_rounds_cap":
        assert int(got[2]) == 5 and bool(jnp.any(got[0] < 0))
    if case == "all_skipped_seeded":  # the seeded round counts, as in XLA
        assert int(got[2]) == 1
    if case in SOLVES:  # every phase ran bidding rounds on these
        assert int(np.asarray(got[2]["rounds"]).sum()) > 0


def test_resident_path_rule(monkeypatch):
    """On CPU the XLA loop unless forced; on TPU the kernel while four
    padded blocks fit its scoped VMEM."""
    assert ops.resident_auction_path(512) == "xla"
    assert ops.resident_auction_path(512, "pallas") == "pallas-interpret"
    assert ops.resident_auction(jnp.zeros((1, 8, 8))) is None
    monkeypatch.setattr(ops, "_backend", lambda: "tpu")
    assert ops.resident_auction_path(1408) == "pallas"
    assert ops.resident_auction_path(1409) == "xla"
    assert ops.resident_auction_path(512, "xla") == "xla"


def test_fixed_rounds_keep_the_xla_loop(monkeypatch):
    """The dry-run's fixed-length scan never asks for the kernel."""
    def refuse(*_a, **_k):
        raise AssertionError("the resident kernel was asked for")
    monkeypatch.setattr(ops, "resident_auction", refuse)
    solve = jax.jit(functools.partial(auction_solve.__wrapped__,
                                      config=AuctionConfig(fixed_rounds=8)))
    assert sorted(np.asarray(solve(_normal(3, 8, 8)))) == list(range(8))
