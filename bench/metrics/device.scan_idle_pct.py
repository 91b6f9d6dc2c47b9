"""Share of the traced slice in which no op ran on the device, averaged
over the cell's chips: 100 * (1 - union of leaf-op intervals / slice).

The slice lies inside the batch scan of one call (see ``run.py``), so this
is the idle share of the scan alone: gaps between calls, at a change of
level and around the centrality pass lie outside it."""


def read(run):
    return 100.0 * (1.0 - run.trace.mean_busy_s / run.trace.window_s)
