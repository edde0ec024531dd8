"""95th percentile, over every patch of the window, of the wall instant
its completion was delivered minus its generation time.  None when a
patch inside that tail was never delivered.  A traced run reads it over
the patches due before the profiler started."""
import numpy as np

from bench.metrics._invocations import before_trace


def read(run):
    keep = before_trace(run)
    lat = np.sort(np.nan_to_num(run.t_done[keep] - run.t_gen[keep],
                                nan=np.inf))
    if not len(lat):
        return None
    value = float(np.percentile(lat, 95))
    return 1e3 * value if np.isfinite(value) else None
