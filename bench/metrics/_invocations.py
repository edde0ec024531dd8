"""Which invocations and patches each kind of reader reads."""


def traced(run):
    """Invocations submitted while the profiler ran: their device work is
    all inside the trace."""
    if run.trace is None or run.peak is None:
        return []
    return [r for r in run.invocations if r.traced]


def untraced(run):
    """Invocations submitted while the profiler was off: host-clock
    readers take these, since profiling slows the host path."""
    return [r for r in run.invocations if not r.traced]


def before_trace(run):
    """Mask of the patches whose deadline passed before the profiler
    started (every patch of an untraced run): per-patch readers take
    these, since stopping the trace stalls the host for seconds and
    every patch waiting then."""
    return run.deadline < run.t_trace
