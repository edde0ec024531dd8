"""Stitch and unstitch programs: the bytes the algorithm needs (live patch
pixels read, every canvas pixel written; placed pixels read back and
written to their slots) over HBM bandwidth, against the device time of
those XLA modules, for the invocations in the trace.  Bytes bound it.

Each program's bytes count only where its modules ran in the trace: the
unfused path stitches on the host and runs the unstitch alone, so the
stitch's bytes there have no device time to go with."""
from bench import work
from bench.metrics._invocations import traced


def read(run):
    inv = traced(run)
    ks = run.trace["kernel_s"] if inv else {}
    ran = {k for k in ("stitch", "unstitch") if ks.get(k, 0.0) > 0}
    if not ran:
        return None
    m = run.arch["canvas"]
    nbytes = 0.0
    if "stitch" in ran:
        nbytes += sum(work.stitch_bytes(r.live_pixels, r.canvases, m)
                      for r in inv)
    if "unstitch" in ran:
        nbytes += sum(work.unstitch_bytes(r.live_pixels) for r in inv)
    t_dev = sum(ks[k] for k in ran)
    return 100.0 * nbytes / run.peak["hbm_bytes_per_s"] / t_dev
