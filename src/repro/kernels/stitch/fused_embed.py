"""Fused Pallas TPU kernels for the device hot path.

Two fusions kill the HBM round-trips that bracket the detector:

``stitch_embed_pallas`` — stitch -> patchify -> patch-embed in one launch.
Grid (B canvases, K placement slots): the patch-slot stream is pipelined
HBM->VMEM by the slot BlockSpec (one slot per step, selected by the
record's slot id), and each canvas is assembled in a VMEM scratch buffer
with the same aligned-window rotate+blend as ``stitch_pallas``.  At the
canvas's last placement step the resident canvas is patchified and
multiplied by the patch-embed projection in place, so the kernel emits
the (B, seq, d) token batch directly and the (B, M, N, C) canvas batch
never materializes in HBM.

The resident canvas is stored as 128-lane column blocks,
``(C, N/128, M, 128)``: the TPU's strided row loads need a 128-wide base.
The embed contracts one (channel, row-in-patch) pair at a time: a strided
load picks that pixel row of every token row, ``(rows, N)``; a transpose
and a tile-aligned reshape regroup it to ``(side_n * rows, patch)`` —
one token per row, its ``patch`` columns on the lanes — and a matmul with
that pair's ``(patch, d)`` slice of the projection accumulates the tokens.
No in-kernel reshape ever splits the lane axis.

``unstitch_decode_pallas`` — head decode + placement gather in one launch.
The detector's raw head outputs are decoded in-kernel (sigmoid objectness,
cell-relative centers, exp box sizes — the same math as
``detector.decode_boxes``) and each placement's hits are scattered
straight to its patch slot.  A decoded center always lies inside its own
grid cell (both offsets are sigmoids), so masking on center-in-placement
over the full grid is exact and the canvas-space (obj, boxes) tensors are
never materialized or round-tripped through the host.  Head channels are
moved to the front outside the kernel, so every in-kernel array is a
(side_m, side_n) plane and every mask has the rank of the data it selects.

Boxes are stored clipped to the placement rectangle and translated to
placement-local pixels; ``ops.route_fused`` only adds each patch's frame
origin.  Invalid records park on the dummy slot past the real patches,
exactly like ``unstitch_pallas``.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels.stitch.stitch import (LANES, placement_mask, rotate,
                                         slots_channel_major, sublane_tile,
                                         vmem_limit, window_origin,
                                         window_shape)

#: default token rows per embed matmul chunk (overridable per call)
DEFAULT_BLOCK_ROWS = 8


def _stitch_embed_kernel(records_ref,        # SMEM (B, K, 6) int32
                         patch_ref,          # VMEM (1, C, hw, ww)
                         w_ref,              # VMEM (C, patch, patch, d)
                         bias_ref,           # VMEM (1, d)
                         out_ref,            # VMEM (1, seq, d)
                         canvas_ref,         # VMEM (C, N/L, M, L) f32
                         *, m: int, n: int, lane: int, hw: int, ww: int,
                         patch: int, block_rows: int):
    b = pl.program_id(0)
    k = pl.program_id(1)
    c = canvas_ref.shape[0]

    @pl.when(k == 0)
    def _init():
        canvas_ref[...] = jnp.zeros_like(canvas_ref)

    valid = records_ref[b, k, 0]
    ys, dy = window_origin(records_ref[b, k, 3], m, hw,
                           sublane_tile(jnp.float32))
    xs, dx = window_origin(records_ref[b, k, 2], n, ww, LANES)
    w = records_ref[b, k, 4]
    h = records_ref[b, k, 5]

    @pl.when(valid > 0)
    def _place():
        mask = placement_mask((hw, ww), dy, dx, h, w)
        q0 = xs // lane
        for ci in range(c):
            shifted = rotate(patch_ref[0, ci], dy, dx)
            for qq in range(ww // lane):
                cols = slice(qq * lane, (qq + 1) * lane)
                window = canvas_ref[ci, q0 + qq, pl.ds(ys, hw), :]
                canvas_ref[ci, q0 + qq, pl.ds(ys, hw), :] = jnp.where(
                    mask[:, cols], shifted[:, cols], window)

    @pl.when(k == pl.num_programs(1) - 1)
    def _embed():
        side_m, side_n = m // patch, n // patch
        d = out_ref.shape[-1]
        for i0 in range(0, side_m, block_rows):
            rows = min(block_rows, side_m - i0)
            acc = jnp.zeros((side_n * rows, d), jnp.float32)
            for ci in range(c):
                def row_in_patch(r, acc, ci=ci, i0=i0, rows=rows):
                    # pixel row r of token rows i0..i0+rows: (rows, N)
                    a = jnp.concatenate(
                        [canvas_ref[ci, q, pl.ds(i0 * patch + r, rows,
                                                 stride=patch), :]
                         for q in range(n // lane)], axis=1)
                    # -> (side_n * rows, patch): token (j, i), column s
                    x = a.T.reshape(side_n, patch, rows)
                    x = jnp.swapaxes(x, 1, 2).reshape(side_n * rows, patch)
                    return acc + jnp.dot(x.astype(w_ref.dtype), w_ref[ci, r],
                                         preferred_element_type=jnp.float32)
                acc = jax.lax.fori_loop(0, patch, row_in_patch, acc)
            # token rows come out column-major (j, i): reorder to (i, j)
            y = jnp.swapaxes(acc.reshape(side_n, rows, d), 0, 1)
            y = y.reshape(rows * side_n, d) + bias_ref[...].astype(jnp.float32)
            out_ref[0, pl.ds(i0 * side_n, rows * side_n), :] = (
                y.astype(out_ref.dtype))


def stitch_embed_pallas(patch_pixels: jnp.ndarray, records: jnp.ndarray,
                        kernel: jnp.ndarray, bias: jnp.ndarray,
                        m: int, n: int, patch: int,
                        *, block_rows: int | None = None,
                        interpret: bool = False) -> jnp.ndarray:
    """Fused stitch -> patchify -> patch-embed.

    patch_pixels: (P, Hmax, Wmax, C); records: (B, K, 6) int32
    (valid, slot, x, y, w, h); kernel: (patch*patch*C, d); bias: (d,).
    Returns embedded tokens (B, seq, d) with seq = (m//patch)*(n//patch),
    numerically equivalent to
    ``dense(patch_embed, patchify(stitch(...), patch))``.
    """
    p_, hmax, wmax, c = patch_pixels.shape
    b, k, _ = records.shape
    d = kernel.shape[-1]
    assert hmax <= m and wmax <= n, "patch slot larger than canvas"
    assert m % patch == 0 and n % patch == 0, (m, n, patch)
    assert kernel.shape[0] == patch * patch * c, (kernel.shape, patch, c)
    side_m, side_n = m // patch, n // patch
    seq = side_m * side_n
    if b == 0 or k == 0 or p_ == 0:
        # empty packing: the embed of an all-zero canvas is just the bias
        return jnp.broadcast_to(bias.astype(kernel.dtype), (b, seq, d))

    block_rows = min(block_rows or DEFAULT_BLOCK_ROWS, side_m)
    lane = LANES if n % LANES == 0 else n
    # the canvas is assembled in float32 (the rotate is 32-bit), which
    # holds every slot dtype exactly
    hw, ww = window_shape(m, n, hmax, wmax, jnp.float32)
    slots = slots_channel_major(patch_pixels, hw, ww)
    # (patch*patch*C, d) rows are ordered (r, s, c) as in vit.patchify;
    # the kernel takes one (c, r) slice of shape (patch, d) at a time
    w4 = kernel.reshape(patch, patch, c, d).transpose(2, 0, 1, 3)
    body = functools.partial(
        _stitch_embed_kernel, m=m, n=n, lane=lane, hw=hw, ww=ww,
        patch=patch, block_rows=block_rows)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=(b, k),
        in_specs=[
            pl.BlockSpec((1, c, hw, ww),
                         lambda bi, ki, recs: (recs[bi, ki, 1], 0, 0, 0)),
            pl.BlockSpec((c, patch, patch, d),
                         lambda bi, ki, recs: (0, 0, 0, 0)),
            pl.BlockSpec((1, d), lambda bi, ki, recs: (0, 0)),
        ],
        out_specs=pl.BlockSpec((1, seq, d), lambda bi, ki, recs: (bi, 0, 0)),
        scratch_shapes=[pltpu.VMEM((c, n // lane, m, lane), jnp.float32)],
    )
    isz = np.dtype(patch_pixels.dtype).itemsize
    wsz = np.dtype(kernel.dtype).itemsize
    return pl.pallas_call(
        body,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((b, seq, d), kernel.dtype),
        compiler_params=pltpu.CompilerParams(vmem_limit_bytes=vmem_limit(
            c * m * n * 4, 2 * c * hw * ww * isz, 2 * kernel.size * wsz,
            2 * seq * d * wsz, 2 * side_n * block_rows * d * 4,
            4 * hw * ww * 4)),
        interpret=interpret,
    )(records, slots, w4, bias.reshape(1, d))


def _unstitch_decode_kernel(records_ref,     # SMEM (B, K, 6) int32
                            raw_ref,         # VMEM (1, 5, side_m, side_n)
                            out_ref,         # VMEM (1, 5, side_m, side_n)
                            *, patch: int, side_m: int, side_n: int):
    b = pl.program_id(0)
    k = pl.program_id(1)

    # an invalid record gets an empty rectangle, so nothing hits
    valid = records_ref[b, k, 0] > 0
    x0 = records_ref[b, k, 2].astype(jnp.float32)
    y0 = records_ref[b, k, 3].astype(jnp.float32)
    w = jnp.where(valid, records_ref[b, k, 4], 0).astype(jnp.float32)
    h = jnp.where(valid, records_ref[b, k, 5], 0).astype(jnp.float32)

    cell = float(patch)
    obj = jax.nn.sigmoid(raw_ref[0, 0].astype(jnp.float32))
    gy = jax.lax.broadcasted_iota(jnp.int32, (side_m, side_n), 0)
    gx = jax.lax.broadcasted_iota(jnp.int32, (side_m, side_n), 1)
    cx = (gx.astype(jnp.float32)
          + jax.nn.sigmoid(raw_ref[0, 1].astype(jnp.float32))) * cell
    cy = (gy.astype(jnp.float32)
          + jax.nn.sigmoid(raw_ref[0, 2].astype(jnp.float32))) * cell
    bw = jnp.exp(jnp.clip(raw_ref[0, 3].astype(jnp.float32), -6, 6)) * cell
    bh = jnp.exp(jnp.clip(raw_ref[0, 4].astype(jnp.float32), -6, 6)) * cell

    # center-in-placement assignment over the full grid (sigmoid offsets
    # keep every center inside its own cell, so no cell outside the
    # placement can hit), then clip to the rect and shift to
    # placement-local pixels — the same math route_detections applies
    # on the host to decode_boxes outputs
    hit = (cx >= x0) & (cx < x0 + w) & (cy >= y0) & (cy < y0 + h)
    dec = (obj,
           jnp.clip(cx - bw / 2, x0, x0 + w) - x0,
           jnp.clip(cy - bh / 2, y0, y0 + h) - y0,
           jnp.clip(cx + bw / 2, x0, x0 + w) - x0,
           jnp.clip(cy + bh / 2, y0, y0 + h) - y0)
    for ch, val in enumerate(dec):
        out_ref[0, ch] = jnp.where(hit, val, 0.0)


def unstitch_decode_pallas(raw: jnp.ndarray, records: jnp.ndarray,
                           patch: int, num_patches: int,
                           *, interpret: bool = False) -> jnp.ndarray:
    """Fused head decode + placement gather.

    raw: (B, side_m, side_n, 5) raw head outputs; records as in stitch.
    Returns (num_patches, side_m, side_n, 5) float32 per-slot grids:
    channel 0 is objectness probability at cells whose decoded center
    falls inside the slot's placement (0 elsewhere), channels 1:5 the
    decoded box clipped to the placement in placement-local xyxy pixels.
    Slots not referenced by any valid record are undefined, exactly as in
    :func:`unstitch_pallas` — the packer places every queued patch once.
    """
    b, side_m, side_n, ch = raw.shape
    _, k, _ = records.shape
    assert ch == 5, raw.shape
    if num_patches == 0 or b == 0 or k == 0:
        return jnp.zeros((num_patches, side_m, side_n, ch), jnp.float32)

    body = functools.partial(_unstitch_decode_kernel, patch=patch,
                             side_m=side_m, side_n=side_n)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=(b, k),
        in_specs=[
            pl.BlockSpec((1, ch, side_m, side_n),
                         lambda bi, ki, recs: (bi, 0, 0, 0)),
        ],
        # invalid records park on the dummy slot, as in unstitch_pallas
        out_specs=pl.BlockSpec(
            (1, ch, side_m, side_n),
            lambda bi, ki, recs: (jnp.where(recs[bi, ki, 0] > 0,
                                            recs[bi, ki, 1], num_patches),
                                  0, 0, 0)),
    )
    out = pl.pallas_call(
        body,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((num_patches + 1, ch, side_m, side_n),
                                       jnp.float32),
        interpret=interpret,
    )(records, raw.transpose(0, 3, 1, 2))
    return out[:num_patches].transpose(0, 2, 3, 1)
