"""Executables compiled, or fetched from the persistent compilation
cache, while the window ran (``jax.monitoring`` events)."""


def read(run):
    return float(run.compiles_in_window)
