"""Host-device transfer: the patches' own pixels over the pixels of the
padded slot array sent to the device (attributes ``live_pixels`` and
``slot_pixels`` of ``tangram.executor.launch``)."""
from bench.metrics._telemetry import rows


def read(run):
    rs = rows(run, "slot_pixels")
    slots = sum(r["slot_pixels"] for r in rs)
    if not slots:
        return None
    return 100.0 * sum(r["live_pixels"] for r in rs) / slots
