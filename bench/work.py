"""Operations and bytes each kernel's algorithm needs, from shapes alone.

Kept with the benchmark so that no change to the program can move them.
The program's own ``core.latency.detector_flops`` counts the MLP twice
(``2 * d * d_ff * 2`` is doubled again), which overstates the ViT-B/32
detector by 1.54x; a roofline or MFU built on it could read over 100%.

A matrix product of (m, k) by (k, n) counts 2*m*k*n operations.  Only
the products are counted: layer norms, softmax and GELU add well under
2% at the served widths (``tests/bench/test_bench_work.py`` holds the
count against XLA's own cost analysis).
"""
from __future__ import annotations


def trunk_flops(arch: dict) -> float:
    """Forward operations of the ViT detector on one canvas."""
    side = arch["canvas"] // arch["patch"]
    s = side * side
    d, d_ff = arch["d_model"], arch["d_ff"]
    embed = 2 * s * (3 * arch["patch"] ** 2) * d
    proj = 2 * s * d * d * 4                 # q, k, v, out
    attn = 2 * s * s * d * 2                 # scores and context
    mlp = 2 * s * d * d_ff * 2
    head = 2 * s * d * 5
    return float(embed + arch["n_layers"] * (proj + attn + mlp) + head)


def trunk_params(arch: dict) -> int:
    side = arch["canvas"] // arch["patch"]
    d, d_ff = arch["d_model"], arch["d_ff"]
    per_layer = 4 * d * d + 2 * d * d_ff + d_ff + d + 4 * d
    return (arch["n_layers"] * per_layer + 3 * arch["patch"] ** 2 * d + d
            + side * side * d + 2 * d + 5 * d + 5)


def trunk_bytes(arch: dict, batch: int, param_bytes: int = 2) -> float:
    """Least HBM traffic of one call: weights once, the float32 canvases
    in, the float32 (objectness, box) grids out."""
    side = arch["canvas"] // arch["patch"]
    canvases = batch * arch["canvas"] ** 2 * 3 * 4
    outputs = batch * side * side * 5 * 4
    return float(trunk_params(arch) * param_bytes + canvases + outputs)


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
