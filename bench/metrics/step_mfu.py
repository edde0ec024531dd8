"""Whole step: the trunk module's operations of every invocation, over the
billed seconds of those invocations (submit to delivered completion)
times the bf16 peak.  It stays a bound when a later change takes a kernel
off the path.  Read over the invocations outside the trace, since
tracing slows the host path that most of those seconds are."""
from bench.metrics._invocations import untraced


def read(run):
    inv = untraced(run) if run.peak is not None else []
    span = sum(r.t_done - r.t_start for r in inv)
    if not inv or span <= 0:
        return None
    flops = sum(run.trunk.flops(run.arch, r) for r in inv)
    return 100.0 * flops / (span * run.peak["bf16_flops_per_s"])
