"""Bytes the stitch programs' algorithm needs, from shapes alone, and the
least time of a count on the chip.

Kept with the benchmark so that no change to the program can move them.
The trunk's operations and bytes depend on its family, and sit in its
module (``bench/trunks/``).
"""
from __future__ import annotations


def stitch_bytes(patch_pixels: int, batch: int, canvas: int) -> float:
    """Stitch: read each live patch pixel once, write every canvas pixel
    (float32 RGB).  Padding of the slots is not counted, whatever the
    implementation moves."""
    return float(patch_pixels * 3 * 4 + batch * canvas * canvas * 3 * 4)


def unstitch_bytes(patch_pixels: int) -> float:
    """Unstitch: read each placed pixel from the canvases, write it back
    to its slot (float32 RGB)."""
    return float(2 * patch_pixels * 3 * 4)


def roofline_seconds(flops: float, nbytes: float, peak: dict) -> tuple:
    """Least time on the chip, and which bound sets it."""
    t_compute = flops / peak["bf16_flops_per_s"]
    t_memory = nbytes / peak["hbm_bytes_per_s"]
    return (t_compute, "compute") if t_compute >= t_memory \
        else (t_memory, "memory")
