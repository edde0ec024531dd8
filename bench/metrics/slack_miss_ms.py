"""Invoker latency estimate: 95th percentile over invocations of the
billed duration (submit to delivered completion) minus the
``Invocation.t_slack`` the invoker fired against, over the invocations
outside the trace."""
import numpy as np

from bench.metrics._invocations import untraced


def read(run):
    miss = [r.t_done - r.t_start - r.t_slack for r in untraced(run)]
    miss = [m for m in miss if np.isfinite(m)]
    if not miss:
        return None
    return 1e3 * float(np.percentile(miss, 95))
