"""Compilations JAX reported inside the measured window (jax.monitoring:
backend compiles and persistent-cache loads).  Set-up warms every shape
the window uses, so this is 0 unless a call retraces."""


def read(run):
    return float(run.compiles_in_window)
