"""Executor host path: mean over invocations of the crop gather and the
slot packing (spans ``tangram.executor.gather`` and ``.pack``)."""
from bench.metrics._telemetry import rows


def read(run):
    rs = rows(run, "pack_s")
    if not rs:
        return None
    return 1e3 * sum(r["gather_s"] + r["pack_s"] for r in rs) / len(rs)
