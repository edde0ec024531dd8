"""Engine event loop: 95th percentile over invocations of the engine time
its submit began less the instant the invoker fired at (attributes
``t_launch`` and ``t_fire`` of ``tangram.engine.dispatch``): how late a
busy host launches what the invoker planned."""
import numpy as np

from bench.metrics._telemetry import rows


def read(run):
    lags = [r["t_launch"] - r["t_fire"] for r in rows(run, "t_launch")]
    if not lags:
        return None
    return 1e3 * float(np.percentile(lags, 95))
