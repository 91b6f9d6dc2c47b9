"""Peaks of the chip, and the operations and bytes a kernel call needs.

The counts are of the work the algorithm asks of the kernel at the call's
own shapes: padding the kernel adds for its tiling is not counted, so a
share of the roofline never exceeds what the chip could have done.
"""

from __future__ import annotations

import json
import pathlib

PEAKS_FILE = pathlib.Path(__file__).resolve().parent / "peaks.json"

F32 = 4  # bytes


def peaks(device_kind: str) -> dict:
    """The published peaks of ``device_kind``; an unknown kind is an error."""
    table = json.loads(PEAKS_FILE.read_text())
    try:
        return table["devices"][device_kind]
    except KeyError:
        raise KeyError(f"no peaks for device kind {device_kind!r} in "
                       f"{PEAKS_FILE.name}; known: "
                       f"{sorted(table['devices'])}") from None


def bid_top2_work(G: int, m: int, k: int, d: int) -> tuple[float, float]:
    """(operations, bytes) of one stacked ``bid_top2`` call.

    Per group: the (m, k) tile of ``x . mu`` is 2*m*k*d operations; the
    kernel reads the m rows and k centroids (d floats each), the k norms
    and k prices, and writes (v1, j1, v2) per row.
    """
    ops = 2.0 * G * m * k * d
    nbytes = F32 * G * (m * d + k * d + 2 * k + 3 * m)
    return ops, nbytes


def gather_work(rows: int, d: int, dp: int) -> tuple[float, float]:
    """(operations, bytes) of one row-gather call: every row is read from
    the lane-padded (n, 1, dp) table and written as d floats, plus the
    row's int32 index."""
    return 0.0, float(F32 * rows * (dp + d + 1))


def roofline_share(ops: float, nbytes: float, seconds: float,
                   peak: dict) -> tuple[float, str]:
    """(percent of the roofline, bound) for work done in ``seconds``.

    The least time the chip could take is the larger of ops over the peak
    rate (bf16: the kernels' f32 dots at HIGHEST precision are counted
    against the published bf16 peak, so the share is an upper bound on how
    close they run) and bytes over HBM bandwidth.
    """
    t_ops = ops / peak["bf16_flop_per_s"]
    t_mem = nbytes / peak["hbm_byte_per_s"]
    bound = "compute" if t_ops >= t_mem else "bandwidth"
    return 100.0 * max(t_ops, t_mem) / seconds, bound
