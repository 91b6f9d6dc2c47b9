"""Share of the HBM roofline that the row-gather Pallas ring reaches in
the traced slice: bytes it has to move (each row read from the lane-padded
(n, 1, dp) table and written out, plus its index; see
``bench/roofline.py``) over HBM bandwidth, against the device time of its
events.  No kernel event in the slice gives nothing."""

from bench import roofline, trace


def read(run):
    calls = trace.kernel_calls(run.trace.all_ops(), "gather")
    if not calls:
        return None
    ops = nbytes = secs = 0.0
    for out, shapes, dt in calls:
        rows, d = out[0][-2], out[0][-1]
        dp = shapes[0][-1]
        o, b = roofline.gather_work(rows, d, dp)
        ops, nbytes, secs = ops + o, nbytes + b, secs + dt
    return roofline.roofline_share(ops, nbytes, secs, run.peaks)[0]
