"""Share of the window's patches delivered by their deadline, on the
wall clock; a patch never delivered counts as a miss.  Whether a patch
makes its deadline turns on its invocation's firing time against its
execution time (the invoker fires at the deadline less ``t_slack``), so
it swings with the host path's timing: a per-layer reading, beside the
tail.  A traced run reads it over the patches due before the profiler
started."""
import numpy as np

from bench.metrics._invocations import before_trace


def read(run):
    keep = before_trace(run)
    if not keep.any():
        return None
    met = np.nan_to_num(run.t_done[keep], nan=np.inf) <= run.deadline[keep]
    return 100.0 * float(np.mean(met))
