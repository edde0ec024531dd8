"""Trunk program: least time of its work on the chip (the larger of the
trunk module's count of operations over the bf16 peak and of bytes over
the HBM peak; at these sizes operations bound it) over the device time of
the trunk's XLA modules, for the invocations in the trace."""
from bench import work
from bench.metrics._invocations import traced


def read(run):
    inv = traced(run)
    t_dev = run.trace["kernel_s"].get("trunk", 0.0) if inv else 0.0
    if not inv or t_dev <= 0:
        return None
    least = sum(work.roofline_seconds(
        run.trunk.flops(run.arch, r), run.trunk.bytes(run.arch, r),
        run.peak)[0] for r in inv)
    return 100.0 * least / t_dev
