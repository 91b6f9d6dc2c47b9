"""Auction rounds per batch LAP, all eps phases together, from the
solver's telemetry of one cold solve (its compiled twin, run after the
window).  Routes that report no telemetry give nothing."""

NEEDS = ("telemetry",)


def read(run):
    if run.telemetry is None:
        return None
    rounds = run.telemetry["rounds"]
    if rounds.ndim == 1:
        rounds = rounds[None]
    return float(rounds.sum()) / rounds.shape[0]
