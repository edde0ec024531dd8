"""Seconds a serverless function is billed per 1000 patches: the sum over
invocations of the wall time from submit to delivered completion."""
import math


def read(run):
    spans = [r.t_done - r.t_start for r in run.invocations]
    patches = sum(r.patches for r in run.invocations)
    if not patches or any(math.isnan(s) for s in spans):
        return None
    return 1e3 * sum(spans) / patches
