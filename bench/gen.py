"""Inputs made on the device from ``--seed``.

The benchmark's own copy of the ``lowrank`` generator of
``repro.data.synthetic.make`` (a stand-in for an image-embedding matrix):
``x = u @ v + 0.3 * noise`` with ``r = max(4, min(d // 8, 64))``, columns
standardized.  The distribution is the same; the bits are not (jax.random
on the device instead of numpy on the host), so set-up pays no host
generation and no host-to-device copy.

Every seed gets the same work in another form.  The auction's round count,
and with it the time of a solve, follows the geometry of the rows, so base
matrices drawn afresh for every seed would make the seed, not the program,
set most of a run's time.  The base matrices (and the warm chain's drift)
are therefore drawn from one fixed key, and the seed picks, per input, a
permutation of the rows and a sign for each column.  Euclidean distances
are unchanged by these, so every seed poses the same anticlustering
problem, in bits that differ from seed to seed.  The columns keep their
order: a permutation of them reorders every float32 sum over them, and
the auction's round count follows such rounding (on the CPU, 34,233 to
36,710 rounds over four seeds of one problem with the columns permuted;
34,784 on each with the rows alone permuted).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

# The key of the geometry every seed shares.
BASE_SEED = 0


def rank(d: int) -> int:
    """The low-rank width ``make`` uses for ``d`` columns."""
    return max(4, min(d // 8, 64))


def seed_key(seed: int):
    """A PRNG key from a seed of any size (jax.random.key keeps 32 bits)."""
    key = jax.random.key(seed & 0xFFFFFFFF)
    return jax.random.fold_in(key, (seed >> 32) & 0xFFFFFFFF)


def lowrank(key, n: int, d: int) -> jnp.ndarray:
    """One standardized (n, d) float32 lowrank matrix."""
    ku, kv, ke = jax.random.split(key, 3)
    r = rank(d)
    u = jax.random.normal(ku, (n, r), jnp.float32)
    v = jax.random.normal(kv, (r, d), jnp.float32)
    x = jnp.dot(u, v, precision=jax.lax.Precision.HIGHEST)
    x = x + 0.3 * jax.random.normal(ke, (n, d), jnp.float32)
    mu = jnp.mean(x, axis=0)
    sd = jnp.sqrt(jnp.mean((x - mu) ** 2, axis=0))
    return (x - mu) / jnp.maximum(sd, 1e-9)


def relabel(key, x):
    """``x`` with its rows permuted and its columns' signs flipped, both
    drawn from ``key``: the same distances in other bits."""
    n, d = x.shape
    kr, ks = jax.random.split(key)
    sign = jnp.where(jax.random.bernoulli(ks, 0.5, (d,)), -1.0, 1.0)
    return jnp.take(x, jax.random.permutation(kr, n), axis=0) * sign


@functools.partial(jax.jit, static_argnames=("count", "n", "d"))
def _inputs(base, key, *, count, n, d):
    return tuple(relabel(jax.random.fold_in(key, i),
                         lowrank(jax.random.fold_in(base, i), n, d))
                 for i in range(count))


def inputs(seed: int, count: int, n: int, d: int) -> tuple:
    """``count`` distinct (n, d) inputs from ``seed``, in one device call:
    input ``i`` is base matrix ``i`` relabelled by the seed."""
    return _inputs(seed_key(BASE_SEED), seed_key(seed), count=count, n=n, d=d)


@jax.jit
def _drift(x, base, key, scale):
    noise = jax.random.normal(base, x.shape, x.dtype)
    return x + scale * relabel(key, noise)


def drift(x, seed: int, epoch: int, scale: float):
    """The next epoch of input 0's drifting chain: ``x + scale * N(0, 1)``,
    the noise drawn from the shared key and relabelled as input 0 was, so
    that every seed's chain is the same chain in other bits."""
    base = jax.random.fold_in(
        jax.random.fold_in(seed_key(BASE_SEED), 1 << 20), epoch)
    return _drift(x, base, jax.random.fold_in(seed_key(seed), 0), scale)
