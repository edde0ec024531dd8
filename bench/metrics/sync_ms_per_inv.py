"""Host-device transfer: mean over invocations of the executor's join on
its device values (span ``tangram.executor.sync``), which holds the copy
of the slots to the device, its layout transposes and the kernels."""
from bench.metrics._telemetry import rows


def read(run):
    rs = rows(run, "sync_s")
    if not rs:
        return None
    return 1e3 * sum(r["sync_s"] for r in rs) / len(rs)
