"""Host-device transfer: bytes copied to the device (slots and records)
and back (every array fetched) per patch, in MB, over the untraced
invocations (attributes ``bytes_to_device`` of
``tangram.executor.launch``, ``bytes_from_device`` of ``.finalize`` and
``patches`` of ``tangram.engine.dispatch``)."""
from bench.metrics._telemetry import rows


def read(run):
    rs = [r for r in rows(run, "bytes_from_device") if "patches" in r]
    patches = sum(r["patches"] for r in rs)
    if not patches:
        return None
    moved = sum(r["bytes_to_device"] + r["bytes_from_device"] for r in rs)
    return moved / 1e6 / patches
