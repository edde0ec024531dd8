"""Stitch and unstitch programs: the bytes the algorithm needs (live patch
pixels read, every canvas pixel written; placed pixels read back and
written to their slots) over HBM bandwidth, against the device time of
those XLA modules, for the invocations in the trace.  Bytes bound it."""
from bench import work
from bench.metrics._invocations import traced


def read(run):
    inv = traced(run)
    ks = run.trace["kernel_s"] if inv else {}
    t_dev = ks.get("stitch", 0.0) + ks.get("unstitch", 0.0)
    if not inv or t_dev <= 0:
        return None
    m = run.arch["canvas"]
    nbytes = sum(work.stitch_bytes(r.live_pixels, r.canvases, m)
                 + work.unstitch_bytes(r.live_pixels) for r in inv)
    return 100.0 * nbytes / run.peak["hbm_bytes_per_s"] / t_dev
