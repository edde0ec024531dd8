"""Seconds from the start of the process to the start of the window:
device start, weights, latency profile, frames, and the warm-up of every
shape the window uses (with compilation where the cache lacks it)."""


def read(run):
    return float(run.setup_s)
