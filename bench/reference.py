"""Plain reference of what the served path computes around the trunk,
independent of the program and of the model: it imports nothing of
``repro``.  The trunk's own reference is its module's ``forward``
(``bench/trunks/``), named by the configuration.

* ``stitch`` — canvases from the frames and the invocation's placements,
  in numpy;
* ``decode`` — objectness probability and xyxy boxes in canvas pixels;
* ``route`` — each detection to the placement that holds its box
  centre, clipped to it and moved to frame coordinates;
* ``placement_faults`` — placements that break the packing's guarantees.
"""
from __future__ import annotations

from typing import Dict, List, Sequence, Tuple

import numpy as np


def stitch(frames: Sequence[np.ndarray], patches, records: np.ndarray,
           canvas: int) -> np.ndarray:
    """(B, M, N, 3) float32 canvases: each placement's patch pixels at
    its (y, x), zero elsewhere."""
    b = records.shape[0]
    out = np.zeros((b, canvas, canvas, 3), np.float32)
    for bi in range(b):
        for valid, slot, x, y, w, h in records[bi]:
            if valid <= 0:
                continue
            p = patches[slot]
            out[bi, y:y + h, x:x + w] = frames[slot][p.y0:p.y0 + h,
                                                     p.x0:p.x0 + w]
    return out


def decode(raw: np.ndarray, canvas: int) -> Tuple[np.ndarray, np.ndarray]:
    """raw (B, s, s, 5) -> objectness probability (B, s, s) and boxes
    (B, s, s, 4) xyxy in canvas pixels."""
    raw = np.asarray(raw, np.float64)
    side = raw.shape[1]
    cell = canvas / side
    sig = lambda v: 1.0 / (1.0 + np.exp(-v))
    gy, gx = np.meshgrid(np.arange(side), np.arange(side), indexing="ij")
    cx = (gx + sig(raw[..., 1])) * cell
    cy = (gy + sig(raw[..., 2])) * cell
    w = np.exp(np.clip(raw[..., 3], -6, 6)) * cell
    h = np.exp(np.clip(raw[..., 4], -6, 6)) * cell
    boxes = np.stack([cx - w / 2, cy - h / 2, cx + w / 2, cy + h / 2], -1)
    return sig(raw[..., 0]), boxes


def route(records: np.ndarray, patches, obj: np.ndarray, boxes: np.ndarray,
          threshold: float) -> Dict[int, List[tuple]]:
    """{frame_id: [(score, (x0, y0, x1, y1)), ...]} in frame pixels."""
    obj = np.asarray(obj, np.float64)
    boxes = np.asarray(boxes, np.float64)
    bcx = (boxes[..., 0] + boxes[..., 2]) / 2
    bcy = (boxes[..., 1] + boxes[..., 3]) / 2
    out: Dict[int, List[tuple]] = {}
    for bi in range(records.shape[0]):
        for valid, slot, x, y, w, h in records[bi]:
            if valid <= 0:
                continue
            hit = ((obj[bi] >= threshold) & (bcx[bi] >= x) & (bcx[bi] < x + w)
                   & (bcy[bi] >= y) & (bcy[bi] < y + h))
            if not hit.any():
                continue
            p = patches[slot]
            dx, dy = p.x0 - x, p.y0 - y
            for score, bx in zip(obj[bi][hit], boxes[bi][hit]):
                x0 = min(max(bx[0], x), x + w) + dx
                y0 = min(max(bx[1], y), y + h) + dy
                x1 = min(max(bx[2], x), x + w) + dx
                y1 = min(max(bx[3], y), y + h) + dy
                out.setdefault(p.frame_id, []).append(
                    (float(score), (x0, y0, x1, y1)))
    return out


def placement_faults(records: np.ndarray, patches, canvas: int) -> int:
    """Placements that break the packing's guarantees: a patch placed
    other than once, a size that is not the patch's, a rectangle outside
    its canvas, or two rectangles of one canvas that overlap."""
    faults = 0
    seen = np.zeros(len(patches), int)
    for bi in range(records.shape[0]):
        rects = []
        for valid, slot, x, y, w, h in records[bi]:
            if valid <= 0:
                continue
            if not 0 <= slot < len(patches):
                faults += 1
                continue
            seen[slot] += 1
            p = patches[slot]
            if (w, h) != (p.x1 - p.x0, p.y1 - p.y0):
                faults += 1
            if x < 0 or y < 0 or x + w > canvas or y + h > canvas:
                faults += 1
            for (a, b2, c2, d2) in rects:
                if x < a + c2 and a < x + w and y < b2 + d2 and b2 < y + h:
                    faults += 1
            rects.append((x, y, w, h))
    return faults + int(np.sum(seen != 1))
