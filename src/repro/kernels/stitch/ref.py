"""Pure-jnp oracles for the canvas stitch / unstitch kernels.

Device-side canvas assembly: patches live in padded slots
``patch_pixels (P, Hmax, Wmax, C)`` with per-placement records
``records (B, K, 6) int32 = (valid, slot, x, y, w, h)`` — B canvases, at
most K placements per canvas.  Output: ``canvases (B, M, N, C)`` with each
patch's valid (h, w) region copied to (y, x); untouched pixels are zero.
Placements are guaranteed non-overlapping by the packer (property-tested),
so blend order is irrelevant.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp


def _record(records, i, k):
    """Fields (valid, slot, x, y, w, h) of flattened record ``i``, and its
    canvas index."""
    bi = i // k
    return bi, tuple(records[bi, i % k, j] for j in range(6))


def stitch_reference(patch_pixels: jnp.ndarray, records: jnp.ndarray,
                     m: int, n: int) -> jnp.ndarray:
    p_, hmax, wmax, c = patch_pixels.shape
    b, k, _ = records.shape
    out = jnp.zeros((b, m, n, c), patch_pixels.dtype)
    if b * k * p_ == 0:
        return out

    rows = jnp.arange(hmax)[:, None]
    cols = jnp.arange(wmax)[None, :]

    def place(i, out):
        bi, (valid, slot, x, y, w, h) = _record(records, i, k)
        img = jax.lax.dynamic_index_in_dim(patch_pixels, slot, axis=0,
                                           keepdims=False)
        # clamp the Hmax x Wmax window inside the canvas; shift the
        # valid-region mask by the clamp offset
        ys = jnp.clip(y, 0, m - hmax)
        xs = jnp.clip(x, 0, n - wmax)
        dy = y - ys
        dx = x - xs
        mask = ((rows >= dy) & (rows < dy + h)
                & (cols >= dx) & (cols < dx + w) & (valid > 0))
        window = jax.lax.dynamic_slice(out, (bi, ys, xs, 0),
                                       (1, hmax, wmax, c))[0]
        # the patch's (h, w) region starts at its slot origin (0, 0);
        # shift it to (dy, dx) inside the window
        shifted = jnp.roll(jnp.roll(img, dy, axis=0), dx, axis=1)
        blended = jnp.where(mask[..., None], shifted, window)
        return jax.lax.dynamic_update_slice(out, blended[None],
                                            (bi, ys, xs, 0))

    # a loop, not B*K unrolled copies: compile time stays flat in K
    return jax.lax.fori_loop(0, b * k, place, out)


def unstitch_reference(canvases: jnp.ndarray, records: jnp.ndarray,
                       num_patches: int, hmax: int, wmax: int) -> jnp.ndarray:
    """Inverse oracle: gather each placement's (h, w) region from its
    canvas back into a zero-padded (num_patches, hmax, wmax, C) slot array.
    Invalid records leave the output untouched."""
    b, m, n, c = canvases.shape
    _, k, _ = records.shape
    out = jnp.zeros((num_patches, hmax, wmax, c), canvases.dtype)
    if num_patches == 0 or b * k == 0:
        return out

    rows = jnp.arange(hmax)[:, None]
    cols = jnp.arange(wmax)[None, :]

    def gather(i, out):
        bi, (valid, slot, x, y, w, h) = _record(records, i, k)
        ys = jnp.clip(y, 0, m - hmax)
        xs = jnp.clip(x, 0, n - wmax)
        window = jax.lax.dynamic_slice(canvases, (bi, ys, xs, 0),
                                       (1, hmax, wmax, c))[0]
        shifted = jnp.roll(jnp.roll(window, -(y - ys), axis=0),
                           -(x - xs), axis=1)
        mask = (rows < h) & (cols < w) & (valid > 0)
        patch = jnp.where(mask[..., None], shifted, jnp.zeros_like(shifted))
        prev = jax.lax.dynamic_index_in_dim(out, slot, axis=0,
                                            keepdims=False)
        upd = jnp.where(valid > 0, patch, prev)
        return jax.lax.dynamic_update_slice(out, upd[None], (slot, 0, 0, 0))

    return jax.lax.fori_loop(0, b * k, gather, out)


def stitch_embed_reference(patch_pixels: jnp.ndarray, records: jnp.ndarray,
                           kernel: jnp.ndarray, bias: jnp.ndarray,
                           m: int, n: int, patch: int) -> jnp.ndarray:
    """Oracle for the fused stitch->patch-embed kernel: stitch, patchify
    (same layout as ``vit.patchify``), project.  Returns (B, seq, d)."""
    canvases = stitch_reference(patch_pixels, records, m, n)
    b, _, _, c = canvases.shape
    side_m, side_n = m // patch, n // patch
    x = canvases.reshape(b, side_m, patch, side_n, patch, c)
    x = x.transpose(0, 1, 3, 2, 4, 5)
    x = x.reshape(b, side_m * side_n, patch * patch * c)
    y = jnp.dot(x.astype(kernel.dtype), kernel,
                preferred_element_type=jnp.float32)
    return (y + bias.astype(jnp.float32)).astype(kernel.dtype)


def unstitch_decode_reference(raw: jnp.ndarray, records: jnp.ndarray,
                              patch: int, num_patches: int) -> jnp.ndarray:
    """Oracle for the fused decode+gather kernel.

    raw: (B, side_m, side_n, 5) raw head outputs.  Decodes objectness and
    boxes per grid cell (``detector.decode_boxes`` math with cell size =
    ``patch``), assigns each cell to the placement containing its decoded
    center, and scatters (obj, box clipped to the placement in
    placement-local xyxy pixels) to the placement's slot grid; non-hit
    cells are zero.  Returns (num_patches, side_m, side_n, 5) float32.
    """
    b, side_m, side_n, _ = raw.shape
    _, k, _ = records.shape
    out = jnp.zeros((num_patches, side_m, side_n, 5), jnp.float32)
    if num_patches == 0 or b * k == 0:
        return out

    cell = float(patch)
    gy, gx = jnp.meshgrid(jnp.arange(side_m), jnp.arange(side_n),
                          indexing="ij")

    def gather(i, out):
        bi, (valid, slot, x, y, w, h) = _record(records, i, k)
        r = jax.lax.dynamic_index_in_dim(raw, bi, axis=0,
                                         keepdims=False).astype(jnp.float32)
        obj = jax.nn.sigmoid(r[..., 0])
        cx = (gx + jax.nn.sigmoid(r[..., 1])) * cell
        cy = (gy + jax.nn.sigmoid(r[..., 2])) * cell
        bw = jnp.exp(jnp.clip(r[..., 3], -6, 6)) * cell
        bh = jnp.exp(jnp.clip(r[..., 4], -6, 6)) * cell
        x0, y0 = x.astype(jnp.float32), y.astype(jnp.float32)
        wf, hf = w.astype(jnp.float32), h.astype(jnp.float32)
        hit = ((valid > 0)
               & (cx >= x0) & (cx < x0 + wf)
               & (cy >= y0) & (cy < y0 + hf))
        dec = jnp.stack([
            obj,
            jnp.clip(cx - bw / 2, x0, x0 + wf) - x0,
            jnp.clip(cy - bh / 2, y0, y0 + hf) - y0,
            jnp.clip(cx + bw / 2, x0, x0 + wf) - x0,
            jnp.clip(cy + bh / 2, y0, y0 + hf) - y0,
        ], axis=-1)
        val = jnp.where(hit[..., None], dec, jnp.zeros_like(dec))
        prev = jax.lax.dynamic_index_in_dim(out, slot, axis=0,
                                            keepdims=False)
        upd = jnp.where(valid > 0, val, prev)
        return jax.lax.dynamic_update_slice(out, upd[None], (slot, 0, 0, 0))

    return jax.lax.fori_loop(0, b * k, gather, out)
