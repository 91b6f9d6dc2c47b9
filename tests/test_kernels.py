"""Pallas kernels vs pure-jnp oracles: shape/dtype sweeps in interpret mode.

The kernel body executes as jnp ops on CPU, so these pin what each kernel
computes; whether the TPU compiler accepts the same BlockSpecs and DMAs is
checked separately, against a described v5e, in tests/test_tpu_compile.py.
"""

import numpy as np
import jax.numpy as jnp
import pytest
from _hypothesis_compat import given, settings, st

from repro.kernels import bid_top2, bid_top2_ref, cdist, cdist_ref


SHAPES = [(1, 1, 1), (7, 5, 3), (128, 128, 128), (130, 257, 70),
          (64, 512, 384), (200, 33, 1000)]


@pytest.mark.parametrize("m,n,d", SHAPES)
@pytest.mark.parametrize("dtype", [np.float32, np.float16])
def test_cdist_allclose(m, n, d, dtype, rng):
    x = rng.normal(size=(m, d)).astype(dtype)
    c = rng.normal(size=(n, d)).astype(dtype)
    got = np.asarray(cdist(jnp.asarray(x), jnp.asarray(c), force="pallas"))
    ref = np.asarray(cdist_ref(jnp.asarray(x), jnp.asarray(c)))
    np.testing.assert_allclose(got, ref, rtol=2e-3, atol=2e-3)


@pytest.mark.parametrize("bm,bn,bk", [(8, 128, 128), (128, 256, 512)])
def test_cdist_block_shapes(bm, bn, bk, rng):
    x = rng.normal(size=(100, 200)).astype(np.float32)
    c = rng.normal(size=(150, 200)).astype(np.float32)
    got = np.asarray(cdist(jnp.asarray(x), jnp.asarray(c), force="pallas",
                           bm=bm, bn=bn, bk=bk))
    ref = np.asarray(cdist_ref(jnp.asarray(x), jnp.asarray(c)))
    np.testing.assert_allclose(got, ref, rtol=2e-3, atol=2e-3)


@pytest.mark.parametrize("m,n,d", SHAPES)
def test_bid_top2_allclose(m, n, d, rng):
    x = rng.normal(size=(m, d)).astype(np.float32)
    c = rng.normal(size=(n, d)).astype(np.float32)
    p = rng.normal(size=(n,)).astype(np.float32)
    gv1, gj1, gv2 = (np.asarray(a) for a in bid_top2(
        jnp.asarray(x), jnp.asarray(c), jnp.asarray(p), force="pallas"))
    rv1, rj1, rv2 = (np.asarray(a) for a in bid_top2_ref(
        jnp.asarray(x), jnp.asarray(c), jnp.asarray(p)))
    np.testing.assert_allclose(gv1, rv1, rtol=1e-3, atol=1e-3)
    np.testing.assert_allclose(gv2, rv2, rtol=1e-3, atol=1e-3)
    # argmax can differ only on exact ties; check value equivalence
    vals = -2 * x @ c.T + (c * c).sum(1)[None] - p[None]
    np.testing.assert_allclose(vals[np.arange(m), gj1],
                               vals[np.arange(m), rj1], rtol=1e-3, atol=1e-3)


@settings(max_examples=10, deadline=None)
@given(m=st.integers(1, 50), n=st.integers(2, 80), d=st.integers(1, 40),
       seed=st.integers(0, 100))
def test_bid_top2_property(m, n, d, seed):
    rng = np.random.default_rng(seed)
    x = rng.normal(size=(m, d)).astype(np.float32)
    c = rng.normal(size=(n, d)).astype(np.float32)
    p = rng.normal(size=(n,)).astype(np.float32)
    v1, j1, v2 = (np.asarray(a) for a in bid_top2(
        jnp.asarray(x), jnp.asarray(c), jnp.asarray(p), force="pallas"))
    assert (v1 >= v2 - 1e-4).all()
    assert ((0 <= j1) & (j1 < n)).all()


# --- streaming-chunk gather kernels (double-buffered DMA ring) -------------
# interpret=True executes the same make_async_copy ring on CPU.

GATHER_SHAPES = [(1, 1, 1), (200, 37, 8), (1000, 256, 32), (513, 300, 130)]


@pytest.mark.parametrize("n,m,d", GATHER_SHAPES)
def test_gather_rows_exact(n, m, d, rng):
    from repro.kernels.ops import gather_rows
    x = rng.normal(size=(n, d)).astype(np.float32)
    idx = rng.integers(0, n, size=(m,)).astype(np.int32)
    got = np.asarray(gather_rows(jnp.asarray(x), jnp.asarray(idx),
                                 force="pallas", bm=64))
    # a gather moves bytes, it does no arithmetic: parity must be bitwise
    np.testing.assert_array_equal(got, x[idx])


def test_gather_rows_clips_out_of_range(rng):
    from repro.kernels.ops import gather_rows
    x = rng.normal(size=(50, 9)).astype(np.float32)
    idx = np.array([0, 49, 200, -1], np.int32)  # kernel path clips to [0, n)
    got = np.asarray(gather_rows(jnp.asarray(x), jnp.asarray(idx),
                                 force="pallas", bm=8))
    np.testing.assert_array_equal(got, x[np.clip(idx, 0, 49)])


@pytest.mark.parametrize("n,m,d", GATHER_SHAPES)
def test_cdist_gather_fused_allclose(n, m, d, rng):
    x = rng.normal(size=(n, d)).astype(np.float32)
    c = rng.normal(size=(max(2, d // 2), d)).astype(np.float32)
    idx = rng.integers(0, n, size=(m,)).astype(np.int32)
    got = np.asarray(cdist(jnp.asarray(x), jnp.asarray(c),
                           idx=jnp.asarray(idx), force="pallas", bm=64))
    ref = np.asarray(cdist_ref(jnp.asarray(x[idx]), jnp.asarray(c)))
    np.testing.assert_allclose(got, ref, rtol=2e-3, atol=2e-3)


@pytest.mark.parametrize("n,m,d", GATHER_SHAPES)
def test_bid_top2_gather_fused_allclose(n, m, d, rng):
    x = rng.normal(size=(n, d)).astype(np.float32)
    k = max(2, d // 2)
    c = rng.normal(size=(k, d)).astype(np.float32)
    p = rng.normal(size=(k,)).astype(np.float32)
    idx = rng.integers(0, n, size=(m,)).astype(np.int32)
    gv1, gj1, gv2 = (np.asarray(a) for a in bid_top2(
        jnp.asarray(x), jnp.asarray(c), jnp.asarray(p),
        idx=jnp.asarray(idx), force="pallas", bm=64, bn=128))
    rv1, rj1, rv2 = (np.asarray(a) for a in bid_top2_ref(
        jnp.asarray(x[idx]), jnp.asarray(c), jnp.asarray(p)))
    np.testing.assert_allclose(gv1, rv1, rtol=1e-3, atol=1e-3)
    np.testing.assert_allclose(gv2, rv2, rtol=1e-3, atol=1e-3)
    vals = -2 * x[idx] @ c.T + (c * c).sum(1)[None] - p[None]
    np.testing.assert_allclose(vals[np.arange(m), gj1],
                               vals[np.arange(m), rj1], rtol=1e-3, atol=1e-3)


def test_gather_wide_rows_fall_back_to_compose(rng):
    # d beyond the fused-kernel VMEM budget: the dispatcher must compose
    # gather + tiled cdist instead of launching the full-row kernel
    from repro.kernels.ops import _GATHER_FUSE_MAX_D
    d = _GATHER_FUSE_MAX_D + 16
    x = rng.normal(size=(40, d)).astype(np.float32)
    c = rng.normal(size=(4, d)).astype(np.float32)
    idx = rng.integers(0, 40, size=(16,)).astype(np.int32)
    got = np.asarray(cdist(jnp.asarray(x), jnp.asarray(c),
                           idx=jnp.asarray(idx), force="pallas"))
    ref = np.asarray(cdist_ref(jnp.asarray(x[idx]), jnp.asarray(c)))
    np.testing.assert_allclose(got, ref, rtol=2e-3, atol=2e-3)


@settings(max_examples=10, deadline=None)
@given(n=st.integers(1, 120), m=st.integers(1, 90), d=st.integers(1, 48),
       seed=st.integers(0, 100))
def test_gather_rows_property(n, m, d, seed):
    from repro.kernels.ops import gather_rows
    rng = np.random.default_rng(seed)
    x = rng.normal(size=(n, d)).astype(np.float32)
    idx = rng.integers(0, n, size=(m,)).astype(np.int32)
    got = np.asarray(gather_rows(jnp.asarray(x), jnp.asarray(idx),
                                 force="pallas", bm=32))
    np.testing.assert_array_equal(got, x[idx])


# Several row AND column blocks (m, k > 1024): the shape class whose 1-D
# norm/price/top-2 blocks the TPU compiler refused, and where the gather
# ring cycles both slots more than once.
MULTI_M, MULTI_K, MULTI_D = 1100, 1030, 12


@pytest.mark.parametrize("kernel", ["cdist", "bid_top2", "gather_rows",
                                    "cdist_gather", "bid_top2_gather"])
def test_multi_block_parity(kernel, rng):
    from repro.kernels.ops import gather_rows
    x = rng.normal(size=(3 * MULTI_M, MULTI_D)).astype(np.float32)
    c = rng.normal(size=(MULTI_K, MULTI_D)).astype(np.float32)
    p = rng.normal(size=(MULTI_K,)).astype(np.float32)
    idx = rng.integers(0, x.shape[0], size=(MULTI_M,)).astype(np.int32)
    xj, cj, pj, ij = (jnp.asarray(a) for a in (x, c, p, idx))
    rows = x[idx]
    if kernel == "gather_rows":
        np.testing.assert_array_equal(
            np.asarray(gather_rows(xj, ij, force="pallas")), rows)
        return
    if kernel in ("cdist", "cdist_gather"):
        got = cdist(jnp.asarray(rows), cj, force="pallas") \
            if kernel == "cdist" else cdist(xj, cj, idx=ij, force="pallas")
        np.testing.assert_allclose(
            np.asarray(got), np.asarray(cdist_ref(jnp.asarray(rows), cj)),
            rtol=2e-3, atol=2e-3)
        return
    got = bid_top2(jnp.asarray(rows), cj, pj, force="pallas") \
        if kernel == "bid_top2" else bid_top2(xj, cj, pj, idx=ij,
                                              force="pallas")
    gv1, gj1, gv2 = (np.asarray(a) for a in got)
    rv1, _, rv2 = (np.asarray(a) for a in bid_top2_ref(
        jnp.asarray(rows), cj, pj))
    np.testing.assert_allclose(gv1, rv1, rtol=1e-3, atol=1e-3)
    np.testing.assert_allclose(gv2, rv2, rtol=1e-3, atol=1e-3)
    vals = -2 * rows @ c.T + (c * c).sum(1)[None] - p[None]
    np.testing.assert_allclose(vals[np.arange(MULTI_M), gj1], rv1,
                               rtol=1e-3, atol=1e-3)


@pytest.mark.parametrize("s,di,ds,chunk", [(32, 64, 8, 8), (48, 128, 16, 16),
                                           (16, 512, 16, 4)])
def test_ssm_scan_allclose(s, di, ds, chunk, rng):
    from repro.kernels.ssm_scan import ssm_scan_pallas
    from repro.kernels.ref import ssm_scan_ref
    b = 2
    dt = jnp.asarray(np.abs(rng.normal(size=(b, s, di))).astype(np.float32)
                     * 0.1)
    bi = jnp.asarray(rng.normal(size=(b, s, ds)).astype(np.float32))
    co = jnp.asarray(rng.normal(size=(b, s, ds)).astype(np.float32))
    xi = jnp.asarray(rng.normal(size=(b, s, di)).astype(np.float32))
    a = jnp.asarray(-np.abs(rng.normal(size=(di, ds))).astype(np.float32))
    y_k, h_k = ssm_scan_pallas(dt, bi, co, xi, a, chunk=chunk, bdi=64,
                               interpret=True)
    y_r, h_r = ssm_scan_ref(dt, bi, co, xi, a)
    np.testing.assert_allclose(np.asarray(y_k), np.asarray(y_r),
                               rtol=1e-4, atol=1e-4)
    np.testing.assert_allclose(np.asarray(h_k), np.asarray(h_r),
                               rtol=1e-4, atol=1e-4)
