"""Percent of the traced slice's busy device time spent in the resident
auction kernel (``resident_auction``, ``repro.kernels.auction``): each
event's device time over the union of all ops' intervals, summed over the
chips.  The kernel is bound by the vector unit, not by HBM or the MXU, so
it gets no roofline.  No kernel event in the slice gives nothing."""

from bench import trace


def read(run):
    calls = trace.kernel_calls(run.trace.all_ops(), "resident_auction")
    if not calls:
        return None
    busy_s = sum(trace.busy_ns(ops) for ops in run.trace.ops.values()) / 1e9
    return 100.0 * sum(dt for *_shapes, dt in calls) / busy_s
