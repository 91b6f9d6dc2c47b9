"""The Pallas kernels compile for a described TPU v5e (no chip attached).

Interpret mode on CPU checks what a kernel computes, not whether the TPU
compiler accepts its layouts: 1-D norm/price blocks and one-row DMAs passed
every interpret test and were refused here.  Each case compiles one kernel
with ``interpret=False`` at the widths the main path runs -- imagenet8
(m = k = 8192, d = 192) and finance (m = k = 4096, d = 12, below one
128-lane tile) -- and checks that the compiled program calls it.

The topology is described inside a fixture, never at import: only one
process may load the TPU library at a time, and every test worker imports
this file.
"""

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.kernels.auction import auction_phase_pallas, padded_cost
from repro.kernels.bid_top2 import bid_top2_pallas
from repro.kernels.cdist import cdist_pallas
from repro.kernels.gather import (bid_top2_gather_pallas, cdist_gather_pallas,
                                  gather_rows_pallas, row_table)
from repro.kernels.ops import _RESIDENT_VMEM_BYTES

WIDTHS = {"imagenet8": (8192, 192), "finance": (4096, 12)}


@pytest.fixture(scope="module")
def one_chip():
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache
    with pytest.MonkeyPatch.context() as mp:
        mp.setenv("TPU_LOG_DIR", "disabled")
        try:
            topo = topologies.get_topology_desc(platform="tpu",
                                                topology_name="v5e:2x2")
        except Exception as e:  # no TPU compiler in this installation
            pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
        # a described-chip compile can be written to the persistent cache
        # but not read back without a chip; keep the cache out of it
        was = jax.config.jax_enable_compilation_cache
        jax.config.update("jax_enable_compilation_cache", False)
        compilation_cache.reset_cache()
        yield SingleDeviceSharding(topo.devices[0])
        jax.config.update("jax_enable_compilation_cache", was)


def _compiled_text(fn, sharding, *shapes):
    args = [jax.ShapeDtypeStruct(s, dt, sharding=sharding)
            for s, dt in shapes]
    return jax.jit(fn).lower(*args).compile().as_text()


F32, I32 = jnp.float32, jnp.int32


def _cases(m, d):
    n = 4 * m  # gather source rows
    return {
        "cdist": (lambda x, c: cdist_pallas(x, c),
                  [((m, d), F32), ((m, d), F32)]),
        "bid_top2": (lambda x, c, p: bid_top2_pallas(x, c, p),
                     [((m, d), F32), ((m, d), F32), ((m,), F32)]),
        "gather_rows": (lambda x, i: gather_rows_pallas(row_table(x), i, d=d),
                        [((n, d), F32), ((m,), I32)]),
        "bid_top2_gather": (
            lambda x, i, c, p: bid_top2_gather_pallas(x, i, c, p),
            [((n, d), F32), ((m,), I32), ((m, d), F32), ((m,), F32)]),
        "cdist_gather": (lambda x, i, c: cdist_gather_pallas(x, i, c),
                         [((n, d), F32), ((m,), I32), ((m, d), F32)]),
    }


@pytest.mark.parametrize("width", sorted(WIDTHS))
@pytest.mark.parametrize("kernel", ["cdist", "bid_top2", "gather_rows",
                                    "bid_top2_gather", "cdist_gather"])
def test_kernel_compiles_for_v5e(kernel, width, one_chip):
    fn, shapes = _cases(*WIDTHS[width])[kernel]
    assert "tpu_custom_call" in _compiled_text(fn, one_chip, *shapes)


# the stacked dispatch (kernels.ops.bid_top2 on (G, m, d) stacks) vmaps the
# kernel into an extra grid dim: hierarchy level 2 of imagenet8 at K = 8192
# (64 groups of 128), and 16 groups at K = 512 on both widths
@pytest.mark.parametrize("G,m,d", [(64, 128, 192), (16, 512, 192),
                                   (16, 512, 12)])
def test_stacked_bid_top2_compiles_for_v5e(G, m, d, one_chip):
    fn = jax.vmap(lambda x, c, p: bid_top2_pallas(x, c, p))
    shapes = [((G, m, d), F32), ((G, m, d), F32), ((G, m), F32)]
    assert "tpu_custom_call" in _compiled_text(fn, one_chip, *shapes)


# the resident auction phase: mnist's flat 512-wide LAP (one instance) and
# a stack of 32-wide ones, one program per instance
@pytest.mark.parametrize("B,n", [(1, 512), (32, 32)])
def test_resident_auction_compiles_for_v5e(B, n, one_chip):
    def fn(c, p, e, a):
        return auction_phase_pallas(
            padded_cost(c), p, e, a, n=n, max_rounds=50 * n + 1000,
            vmem_limit_bytes=_RESIDENT_VMEM_BYTES)
    shapes = [((B, n, n), F32), ((B, n), F32), ((B,), F32), ((B, n), I32)]
    assert "tpu_custom_call" in _compiled_text(fn, one_chip, *shapes)
