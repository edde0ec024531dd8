"""Per-invocation rows of the program's span recorder, for the readers of
its spans and counters.

A run holds the recorder as ``run.telemetry`` (a
``repro.core.telemetry.Telemetry`` that recorded the whole window) only
in a traced run (``--trace 1``); without one every such reader gives
None.  The recorder numbers invocations from 0 in the order the engine
dispatched them, as the harness numbers ``InvRecord.ordinal``.  Readers
take the invocations outside the profiler's trace, since profiling
slows the host path."""
from bench.metrics._invocations import untraced


def rows(run, key: str) -> list:
    """The recorder's rows of the untraced invocations that hold ``key``."""
    tel = getattr(run, "telemetry", None)
    if tel is None:
        return []
    per = tel.invocations()
    out = []
    for rec in untraced(run):
        row = per.get(rec.ordinal)
        if row is not None and key in row:
            out.append(row)
    return out
