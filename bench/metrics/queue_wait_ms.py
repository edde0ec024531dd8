"""Invoker: 95th percentile over patches of the instant the invoker fired
their invocation at less the patch's arrival instant (attributes
``t_fire`` and ``arrivals`` of ``tangram.engine.dispatch``)."""
import numpy as np

from bench.metrics._telemetry import rows


def read(run):
    waits = [r["t_fire"] - t for r in rows(run, "t_fire")
             for t in r["arrivals"]]
    if not waits:
        return None
    return 1e3 * float(np.percentile(waits, 95))
