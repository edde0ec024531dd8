"""Plain reference of what the served path computes, independent of the
program: it imports nothing of ``repro``.

* ``stitch`` — canvases from the frames and the invocation's placements,
  in numpy;
* ``forward`` — the ViT detector in float32 at ``highest`` matmul
  precision: patchify (rows, columns, then each patch's pixels
  row-major with the channel last), linear patch embedding, learned
  position embedding, pre-norm encoder blocks (layer norm eps 1e-6,
  multi-head attention without biases, GELU MLP in its tanh form as in
  the ViT reference implementation), final layer norm, and a per-token
  linear head of 5 outputs (objectness and box) — the detector of the
  Tangram repository on a ViT trunk (Dosovitskiy et al.,
  arXiv:2010.11929);
* ``decode`` — objectness probability and xyxy boxes in canvas pixels;
* ``route`` — each detection to the placement that holds its box
  centre, clipped to it and moved to frame coordinates.

It reads the benchmark's own weights (``bench.model.make_weights``),
never the program's, and the tree's names (``trunk``, ``layers``, ...).
"""
from __future__ import annotations

from typing import Dict, List, Sequence, Tuple

import numpy as np

LN_EPS = 1e-6


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


def _layernorm(x, scale, bias):
    import jax.numpy as jnp

    mean = jnp.mean(x, -1, keepdims=True)
    var = jnp.mean(jnp.square(x - mean), -1, keepdims=True)
    return (x - mean) / jnp.sqrt(var + LN_EPS) * scale + bias


def _gelu(x):
    import jax.numpy as jnp

    return 0.5 * x * (1.0 + jnp.tanh(np.sqrt(2.0 / np.pi)
                                      * (x + 0.044715 * x ** 3)))


def forward(params, canvases, arch: dict, dtype="float32"):
    """(B, M, N, 3) canvases -> (B, side, side, 5) raw head outputs.

    ``dtype`` is the precision every matrix product's operands are
    rounded to (``float32``, or a lower one for a control); the products
    accumulate in float32 at ``highest`` precision."""
    import jax
    import jax.numpy as jnp

    def q(x):
        return x.astype(dtype).astype(jnp.float32)

    def mm(eq, a, b):
        return jnp.einsum(eq, q(a), q(b),
                          precision=jax.lax.Precision.HIGHEST,
                          preferred_element_type=jnp.float32)

    f32 = lambda t: jax.tree_util.tree_map(
        lambda a: jnp.asarray(a, jnp.float32), t)
    tp = f32(params["trunk"])
    head = f32(params["det_head"])
    p = arch["patch"]
    b, m, n, c = canvases.shape
    x = canvases.astype(jnp.float32).reshape(b, m // p, p, n // p, p, c)
    x = x.transpose(0, 1, 3, 2, 4, 5).reshape(b, (m // p) * (n // p),
                                              p * p * c)
    x = mm("bsi,id->bsd", x, tp["patch_embed"]["kernel"]) \
        + tp["patch_embed"]["bias"]
    x = x + tp["pos_embed"]
    n_heads = arch["n_heads"]
    head_dim = arch["d_model"] // n_heads
    layers = tp["layers"]
    for i in range(arch["n_layers"]):
        lp = jax.tree_util.tree_map(lambda a: a[i], layers)
        h = _layernorm(x, lp["ln1"]["scale"], lp["ln1"]["bias"])
        qh = mm("bsd,dhk->bshk", h, lp["attn"]["wq"])
        kh = mm("bsd,dhk->bshk", h, lp["attn"]["wk"])
        vh = mm("bsd,dhk->bshk", h, lp["attn"]["wv"])
        s = mm("bqhk,bthk->bhqt", qh, kh) / np.sqrt(head_dim)
        a = jax.nn.softmax(s, axis=-1)
        ctx = mm("bhqt,bthk->bqhk", a, vh)
        x = x + mm("bqhk,hkd->bqd", ctx, lp["attn"]["wo"])
        h = _layernorm(x, lp["ln2"]["scale"], lp["ln2"]["bias"])
        h = _gelu(mm("bsd,df->bsf", h, lp["mlp"]["fc1"]["kernel"])
                  + lp["mlp"]["fc1"]["bias"])
        x = x + mm("bsf,fd->bsd", h, lp["mlp"]["fc2"]["kernel"]) \
            + lp["mlp"]["fc2"]["bias"]
    x = _layernorm(x, tp["ln_f"]["scale"], tp["ln_f"]["bias"])
    raw = mm("bsd,do->bso", x, head["kernel"]) + head["bias"]
    return raw.reshape(b, m // p, n // p, 5)


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
