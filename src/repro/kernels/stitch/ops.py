"""Jit'd public entries for canvas stitch/unstitch + host-side packing.

The device side is batched end-to-end: ``stitch_canvases`` assembles a
whole multi-canvas batch in one call, ``unstitch_patches`` gathers every
placement back out, and ``route_detections`` maps canvas-space detector
outputs to per-frame boxes via the same :class:`BatchPlan` records.  The
unfused serve path stitches and unstitches on the host instead
(``stitch_plan_host``, ``unstitch_plan_host``); the device versions are
their references.
"""
from __future__ import annotations

import functools
from typing import Dict, List, Sequence, Tuple

import jax
import numpy as np

from repro.core.partitioning import Patch
from repro.core.stitching import BatchPlan
from repro.kernels.stitch.fused_embed import (stitch_embed_pallas,
                                              unstitch_decode_pallas)
from repro.kernels.stitch.ref import (stitch_embed_reference,
                                      stitch_reference,
                                      unstitch_decode_reference,
                                      unstitch_reference)
from repro.kernels.stitch.stitch import stitch_pallas, unstitch_pallas


def pallas_impl(platform: str | None = None) -> str:
    """The ``impl`` that runs the Pallas kernels on ``platform`` (default:
    ``jax.default_backend()``): compiled on TPU, interpreted on CPU.  Any
    other backend is an error, never a silent fallback."""
    platform = platform or jax.default_backend()
    if platform == "tpu":
        return "pallas"
    if platform == "cpu":
        return "pallas_interpret"
    raise ValueError(f"no Pallas stitch kernels for platform {platform!r}")


@functools.partial(jax.jit, static_argnames=("m", "n", "impl"))
def stitch_canvases(patch_pixels, records, m: int, n: int,
                    impl: str = "xla"):
    """Assemble a batch of canvases from padded patch slots.

    impl: "xla" (reference), "pallas" (TPU kernel),
          "pallas_interpret" (kernel body on CPU, for tests).
    """
    if impl == "xla":
        return stitch_reference(patch_pixels, records, m, n)
    return stitch_pallas(patch_pixels, records, m, n,
                         interpret=(impl == "pallas_interpret"))


@functools.partial(jax.jit,
                   static_argnames=("num_patches", "hmax", "wmax", "impl"))
def unstitch_patches(canvases, records, num_patches: int, hmax: int,
                     wmax: int, impl: str = "xla"):
    """Inverse of :func:`stitch_canvases`: canvases -> padded patch slots."""
    if impl == "xla":
        return unstitch_reference(canvases, records, num_patches, hmax, wmax)
    return unstitch_pallas(canvases, records, num_patches, hmax, wmax,
                           interpret=(impl == "pallas_interpret"))


@functools.partial(jax.jit,
                   static_argnames=("m", "n", "patch", "block_rows", "impl"))
def stitch_embed(patch_pixels, records, kernel, bias, m: int, n: int,
                 patch: int, block_rows: int = None, impl: str = "xla"):
    """Fused stitch -> patchify -> patch-embed: slots to (B, seq, d)
    tokens without materializing the canvas batch in HBM.

    impl: "xla" (reference), "pallas" (TPU kernel),
          "pallas_interpret" (kernel body on CPU, for tests).
    """
    if impl == "xla":
        return stitch_embed_reference(patch_pixels, records, kernel, bias,
                                      m, n, patch)
    return stitch_embed_pallas(patch_pixels, records, kernel, bias, m, n,
                               patch, block_rows=block_rows,
                               interpret=(impl == "pallas_interpret"))


@functools.partial(jax.jit,
                   static_argnames=("patch", "num_patches", "impl"))
def unstitch_decode(raw, records, patch: int, num_patches: int,
                    impl: str = "xla"):
    """Fused head decode + placement gather: raw (B, s, s, 5) head outputs
    to per-slot (num_patches, s, s, 5) decoded grids, no host round-trip
    through canvas-space (obj, boxes)."""
    if impl == "xla":
        return unstitch_decode_reference(raw, records, patch, num_patches)
    return unstitch_decode_pallas(raw, records, patch, num_patches,
                                  interpret=(impl == "pallas_interpret"))


def pack_plan_host(frame_pixels: Sequence[np.ndarray],
                   plan: BatchPlan) -> np.ndarray:
    """Host prep: copy patch crops into the plan's padded slot array.

    frame_pixels[i] is the (h, w, C) crop for queue patch i.  Returns
    patch_pixels (slot_capacity, hmax, wmax, C) float32, zero-padded —
    the pow2-bucketed capacity keeps jit shapes stable across invocations.
    The fused device path sends these slots (its kernel stitches them in
    VMEM); the unfused path sends :func:`stitch_plan_host`'s canvases.
    """
    c = frame_pixels[0].shape[-1] if frame_pixels else 3
    slots = np.zeros((plan.slot_capacity, plan.hmax, plan.wmax, c),
                     np.float32)
    for i, px in enumerate(frame_pixels):
        h, w = px.shape[:2]
        assert h <= plan.hmax and w <= plan.wmax, (h, w, plan.hmax, plan.wmax)
        slots[i, :h, :w] = px
    return slots


def stitch_plan_host(frame_pixels: Sequence[np.ndarray], plan: BatchPlan,
                     out: np.ndarray | None = None) -> np.ndarray:
    """Host stitch: copy patch crops straight onto the plan's canvases.

    frame_pixels[i] is the (h, w, C) crop for queue patch i.  Returns the
    (B, M, N, C) float32 canvas batch that :func:`stitch_canvases` builds
    on the device from :func:`pack_plan_host`'s slots, bit for bit: each
    valid record's slot (zero past its crop) lands at (y, x), clipped to
    its slot and the canvas, and the rest is zero.  ``out``, a (rows >=
    B, M, N, C) float32 buffer, is zeroed and stitched into in place of a
    new batch; its rows past B (a data-parallel mesh's pad) stay zero.
    """
    m, n = plan.canvas_m, plan.canvas_n
    if out is None:
        c = frame_pixels[0].shape[-1] if frame_pixels else 3
        out = np.zeros((plan.num_canvases, m, n, c), np.float32)
    else:
        assert out.shape[0] >= plan.num_canvases, (out.shape, plan)
        out.fill(0.0)
    for bi, slot, x, y, w, h in plan.placements():
        h = min(h, plan.hmax, m - y)
        w = min(w, plan.wmax, n - x)
        px = (frame_pixels[slot] if slot < len(frame_pixels)
              else out[bi, :0, :0])
        ph, pw = min(h, px.shape[0]), min(w, px.shape[1])
        if (ph, pw) != (h, w):
            # the slot's zero padding past a short crop (or an empty slot)
            out[bi, y:y + h, x:x + w] = 0.0
        out[bi, y:y + ph, x:x + pw] = px[:ph, :pw]
    return out


def unstitch_plan_host(canvases: np.ndarray, plan: BatchPlan,
                       patches: Sequence[Patch]) -> List[np.ndarray]:
    """Host unstitch: each patch's pixels, cut back out of the canvases.

    The mirror of :func:`stitch_plan_host`.  Returns, for queue patch i,
    a new contiguous (patch.h, patch.w, C) array: bit for bit
    ``unstitch_patches(canvases, records, ...)[i, :patch.h, :patch.w]``,
    so the valid record's (h, w) region at (y, x) of its canvas (placed
    inside the canvas by the packer), zero past it, and zero for a patch
    no valid record places.  A copy, not a view: a view would pin the
    whole batch, and its buffer is stitched into again.
    """
    c = canvases.shape[-1]
    out = [np.zeros((min(p.h, plan.hmax), min(p.w, plan.wmax), c),
                    canvases.dtype) for p in patches]
    for bi, slot, x, y, w, h in plan.placements():
        px = out[slot]
        h, w = min(h, px.shape[0]), min(w, px.shape[1])
        assert y + h <= plan.canvas_m and x + w <= plan.canvas_n, (x, y, w, h)
        px[:h, :w] = canvases[bi, y:y + h, x:x + w]
    return out


def route_detections(plan: BatchPlan, patches: Sequence[Patch],
                     obj: np.ndarray, boxes: np.ndarray,
                     obj_threshold: float = 0.5
                     ) -> Dict[int, List[Tuple[float, Tuple[float, ...]]]]:
    """Route canvas-space detector outputs back to their source frames.

    obj: (B, s, s) objectness, boxes: (B, s, s, 4) xyxy in canvas pixels.
    A detection belongs to the placement whose rectangle contains its
    decoded box center (cell centers would drop detections in placements
    narrower than one detector cell); its box is clipped to the placement
    and translated from canvas space to the patch's frame coordinates.
    Returns {frame_id: [(score, box_xyxy), ...]}.
    """
    obj = np.asarray(obj, np.float32)
    boxes = np.asarray(boxes, np.float32)
    b = obj.shape[0]
    bcx = (boxes[..., 0] + boxes[..., 2]) / 2     # (B, s, s) box centers
    bcy = (boxes[..., 1] + boxes[..., 3]) / 2

    out: Dict[int, List[Tuple[float, Tuple[float, ...]]]] = {}
    for bi, patch_idx, x, y, w, h in plan.placements():
        if bi >= b:
            continue
        patch = patches[patch_idx]
        hit = ((obj[bi] >= obj_threshold)
               & (bcx[bi] >= x) & (bcx[bi] < x + w)
               & (bcy[bi] >= y) & (bcy[bi] < y + h))
        if not hit.any():
            continue
        dx = patch.x0 - x
        dy = patch.y0 - y
        dests = out.setdefault(patch.frame_id, [])
        for score, bx in zip(obj[bi][hit], boxes[bi][hit]):
            # clip to the placement rect: pixels past it belong to a
            # neighboring placement (possibly another frame entirely)
            x0 = min(max(float(bx[0]), x), x + w)
            y0 = min(max(float(bx[1]), y), y + h)
            x1 = min(max(float(bx[2]), x), x + w)
            y1 = min(max(float(bx[3]), y), y + h)
            dests.append((float(score),
                          (x0 + dx, y0 + dy, x1 + dx, y1 + dy)))
    return out


def route_fused(plan: BatchPlan, patches: Sequence[Patch],
                fused: np.ndarray, obj_threshold: float = 0.5
                ) -> Dict[int, List[Tuple[float, Tuple[float, ...]]]]:
    """Route :func:`unstitch_decode` outputs back to their source frames.

    fused: (num_patches, s, s, 5) per-slot decoded grids.  The kernel
    already did the per-placement assignment, clipping, and translation
    to placement-local pixels, so routing reduces to thresholding each
    slot's grid and adding the patch's frame origin.  Emits detections in
    the same per-frame order as :func:`route_detections`.
    """
    fused = np.asarray(fused, np.float32)
    out: Dict[int, List[Tuple[float, Tuple[float, ...]]]] = {}
    for _, patch_idx, x, y, w, h in plan.placements():
        if patch_idx >= fused.shape[0]:
            continue
        grid = fused[patch_idx]
        hit = grid[..., 0] >= obj_threshold
        if not hit.any():
            continue
        patch = patches[patch_idx]
        dx = float(patch.x0)
        dy = float(patch.y0)
        dests = out.setdefault(patch.frame_id, [])
        for row in grid[hit]:
            dests.append((float(row[0]),
                          (float(row[1]) + dx, float(row[2]) + dy,
                           float(row[3]) + dx, float(row[4]) + dy)))
    return out
