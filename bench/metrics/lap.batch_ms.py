"""Device milliseconds per sequential batch step in the traced slice.

Every batch LAP ends in one ``sort`` op, the argsort with which the
auction repairs its permutation (``_repair_permutation`` in
``repro.core.assignment``), on every route.  So the sort events mark the
batch steps: the time from the first to the last, over the steps between
them, is the device's time per step with all its work and gaps.  Fewer
than three marks in the slice give nothing."""

from bench import trace


def read(run):
    marks = trace.starts_of(run.trace.first_device_ops(), "sort")
    if len(marks) < 3:
        return None
    return (marks[-1] - marks[0]) / (len(marks) - 1) / 1e6
