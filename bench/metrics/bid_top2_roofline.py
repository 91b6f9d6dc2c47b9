"""Share of the roofline that the ``bid_top2`` Pallas kernel reaches in the
traced slice: the least time its calls could take at the chip's peaks
(operations against the bf16 peak, bytes against HBM bandwidth; see
``bench/roofline.py``) over the device time of its events.  Each call's
(G, m, d) is read from its operand shapes in the trace; ABA's LAPs are
square, so k = m.  No kernel event in the slice gives nothing."""

from bench import roofline, trace


def read(run):
    calls = trace.kernel_calls(run.trace.all_ops(), "bid_top2")
    if not calls:
        return None
    ops = nbytes = secs = 0.0
    for _out, shapes, dt in calls:
        x = shapes[0]
        G, m, d = (1,) + x if len(x) == 2 else x
        o, b = roofline.bid_top2_work(G, m, m, d)
        ops, nbytes, secs = ops + o, nbytes + b, secs + dt
    return roofline.roofline_share(ops, nbytes, secs, run.peaks)[0]
