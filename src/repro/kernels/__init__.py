"""Pallas TPU kernels for the compute hot-spots the paper optimizes:
the object<->centroid cost matrix (Fact 1 fast path), the auction
bidding reduction and the dense auction's VMEM-resident round loop
(auction.py).  ops.py holds the jit'd public wrappers, ref.py the
pure-jnp oracles used by the allclose tests."""

from repro.kernels.ops import bid_top2, cdist
from repro.kernels.ref import bid_top2_ref, cdist_ref, ssm_scan_ref
from repro.kernels.ssm_scan import ssm_scan_pallas

__all__ = ["bid_top2", "cdist", "bid_top2_ref", "cdist_ref",
           "ssm_scan_ref", "ssm_scan_pallas"]
