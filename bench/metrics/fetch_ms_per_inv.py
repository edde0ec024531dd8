"""Executor host path: mean over invocations of the copies of the device's
outputs to the host (span ``tangram.executor.fetch``)."""
from bench.metrics._telemetry import rows


def read(run):
    rs = rows(run, "fetch_s")
    if not rs:
        return None
    return 1e3 * sum(r["fetch_s"] for r in rs) / len(rs)
