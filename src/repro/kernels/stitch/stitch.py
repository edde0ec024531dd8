"""Pallas TPU kernels: batched canvas stitch (scatter) and unstitch (gather).

TPU adaptation of Tangram's host-side cv2 canvas assembly (DESIGN.md §2):
instead of compositing on the host and DMA'ing finished canvases, the
function instance DMAs compact patch slots HBM->VMEM and assembles a whole
*batch* of canvases in one kernel launch, no host round-trip.  The inverse
kernel gathers each placement's pixels back out of the canvases so
per-patch detector outputs can be routed to their source frames.

Layout.  Inside the kernels every image is channel-major, ``(C, rows,
cols)``, so the pixel columns sit on the 128-lane axis; the public
wrappers take and return the detector's NHWC arrays and transpose at the
boundary.  (An NHWC block would put C=3 on the lanes and pad every pixel
to 128 lanes: a 1024² f32 canvas would need 512 MiB of VMEM.)

Placement.  A record places a (h, w) patch at pixel (y, x) — offsets the
TPU cannot address in VMEM, which only takes dynamic row offsets that are
multiples of the sublane tile and column offsets that are multiples of
128.  So each placement is blended through an *aligned window*: its
origin is (y, x) rounded down to those multiples (clamped inside the
canvas) and its static size is the slot grown by one alignment step
(:func:`window_shape`), so the placement always fits inside it at the
residual offset (dy, dx).  The slot is padded to the window size outside
the kernel, rotated by (dy, dx) with ``pltpu.roll`` (a dynamic rotate,
32-bit only — pixels are rotated as float32, which is exact for the 8- and
16-bit payloads too) and blended under the placement mask.  Unstitch
rotates the window back by (-dy, -dx) and masks to (h, w).

Grid: (B canvases, C channels, K placement slots).  The canvas block of a
(b, c) pair stays resident in VMEM across its K placement steps
(accumulation pattern); the slot block streams one (window) slot channel
per step, selected by the record's slot id via scalar prefetch.  Records
ride in SMEM.  Unstitch inverts the mapping: the canvas block is the
resident input and the slot is the output block, scattered to
``records[b, k, 1]``.  Invalid records are parked on a dummy slot appended
past the real patches so they can never clobber live output; the dummy is
sliced off on return.

VMEM budget at the serving shape (canvas 1024², 512² slots, f32): the
canvas block is 4 MiB and the slot window 520x640 = 1.3 MiB, both
double-buffered, plus a few window-sized temporaries.  The TPU compiler
accepts stitch with 12 MiB and unstitch with 13 MiB of scoped VMEM, and
the fused stitch->embed with 27 MiB (smallest limits that compile for a
v5e); the last is above the 16 MiB default, so every call states its own
limit (:func:`vmem_limit`), computed from its buffers with headroom.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

LANES = 128


def _round_up(x: int, m: int) -> int:
    return -(-x // m) * m


def sublane_tile(dtype) -> int:
    """Rows per VMEM tile for ``dtype``: 8 for 32-bit, 16 for 16-bit,
    32 for 8-bit (the alignment of dynamic row offsets)."""
    return 8 * max(1, 4 // np.dtype(dtype).itemsize)


def window_shape(m: int, n: int, hmax: int, wmax: int, dtype):
    """Static (rows, cols) of the aligned window a placement is blended
    through: the slot grown by one alignment step, capped at the canvas.
    A canvas axis that is not a whole number of tiles gets a window the
    size of the axis (origin always 0)."""
    sub = sublane_tile(dtype)
    hw = m if m % sub else min(_round_up(hmax + sub - 1, sub), m)
    ww = n if n % LANES else min(_round_up(wmax + LANES - 1, LANES), n)
    return hw, ww


def window_origin(pos, extent: int, win: int, align: int):
    """Aligned window origin for a placement starting at ``pos`` along an
    axis of ``extent``; returns (origin, pos - origin).  The residual is
    reduced mod ``win`` so garbage positions of invalid records still give
    an in-range rotate."""
    if win == extent:
        return 0, jax.lax.rem(pos, win)
    start = jnp.minimum((pos // align) * align, extent - win)
    return pl.multiple_of(start, align), jax.lax.rem(pos - start, win)


def placement_mask(shape, dy, dx, h, w):
    """Boolean (rows, cols) mask of the rectangle [dy, dy+h) x [dx, dx+w)."""
    rows = jax.lax.broadcasted_iota(jnp.int32, shape, 0)
    cols = jax.lax.broadcasted_iota(jnp.int32, shape, 1)
    return (rows >= dy) & (rows < dy + h) & (cols >= dx) & (cols < dx + w)


def rotate(img, dy, dx):
    """Rotate a 2-D image by (dy, dx) (``jnp.roll`` semantics) as float32:
    the TPU's dynamic rotate handles 32-bit data only."""
    img = pltpu.roll(img.astype(jnp.float32), dy, 0)
    return pltpu.roll(img, dx, 1)


def slots_channel_major(patch_pixels, hw: int, ww: int):
    """(P, Hmax, Wmax, C) slots -> (P, C, hw, ww), zero-padded to the
    window size."""
    _, hmax, wmax, _ = patch_pixels.shape
    x = patch_pixels.transpose(0, 3, 1, 2)
    return jnp.pad(x, ((0, 0), (0, 0), (0, hw - hmax), (0, ww - wmax)))


def vmem_limit(*buffer_bytes: int) -> int:
    """Scoped-VMEM limit for a call: the listed buffers plus half again
    for Mosaic's own temporaries and 4 MiB of headroom."""
    return int(sum(buffer_bytes) * 1.5) + (4 << 20)


def _stitch_kernel(records_ref,          # SMEM (B, K, 6) int32
                   patch_ref,            # VMEM (1, 1, hw, ww)
                   out_ref,              # VMEM (1, 1, M, N)
                   *, m: int, n: int, hw: int, ww: int, sub: int):
    b = pl.program_id(0)
    k = pl.program_id(2)

    @pl.when(k == 0)
    def _init():
        out_ref[...] = jnp.zeros_like(out_ref)

    valid = records_ref[b, k, 0]
    ys, dy = window_origin(records_ref[b, k, 3], m, hw, sub)
    xs, dx = window_origin(records_ref[b, k, 2], n, ww, LANES)
    w = records_ref[b, k, 4]
    h = records_ref[b, k, 5]

    @pl.when(valid > 0)
    def _place():
        shifted = rotate(patch_ref[0, 0], dy, dx).astype(out_ref.dtype)
        mask = placement_mask((hw, ww), dy, dx, h, w)
        window = out_ref[0, 0, pl.ds(ys, hw), pl.ds(xs, ww)]
        out_ref[0, 0, pl.ds(ys, hw), pl.ds(xs, ww)] = (
            jnp.where(mask, shifted, window))


def stitch_pallas(patch_pixels: jnp.ndarray, records: jnp.ndarray,
                  m: int, n: int, *, interpret: bool = False) -> jnp.ndarray:
    """patch_pixels: (P, Hmax, Wmax, C); records: (B, K, 6) int32
    (valid, slot, x, y, w, h) -> canvases (B, M, N, C)."""
    p_, hmax, wmax, c = patch_pixels.shape
    b, k, _ = records.shape
    assert hmax <= m and wmax <= n, "patch slot larger than canvas"
    dtype = patch_pixels.dtype
    if b == 0 or k == 0 or p_ == 0:
        # empty packing: a zero canvas batch, no degenerate kernel launch
        return jnp.zeros((b, m, n, c), dtype)

    hw, ww = window_shape(m, n, hmax, wmax, dtype)
    slots = slots_channel_major(patch_pixels, hw, ww)
    kernel = functools.partial(_stitch_kernel, m=m, n=n, hw=hw, ww=ww,
                               sub=sublane_tile(dtype))
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=(b, c, k),
        in_specs=[
            # one slot channel per step, selected by the record's slot id
            pl.BlockSpec((1, 1, hw, ww),
                         lambda bi, ci, ki, recs: (recs[bi, ki, 1], ci, 0, 0)),
        ],
        out_specs=pl.BlockSpec((1, 1, m, n),
                               lambda bi, ci, ki, recs: (bi, ci, 0, 0)),
    )
    isz = np.dtype(dtype).itemsize
    out = pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((b, c, m, n), dtype),
        compiler_params=pltpu.CompilerParams(vmem_limit_bytes=vmem_limit(
            2 * m * n * isz, 2 * hw * ww * isz, 4 * hw * ww * 4)),
        interpret=interpret,
    )(records, slots)
    return out.transpose(0, 2, 3, 1)


def _unstitch_kernel(records_ref,        # SMEM (B, K, 6) int32
                     canvas_ref,         # VMEM (1, 1, M, N)
                     out_ref,            # VMEM (1, 1, hw, ww)
                     *, m: int, n: int, hw: int, ww: int, sub: int):
    b = pl.program_id(0)
    k = pl.program_id(2)

    valid = records_ref[b, k, 0]
    ys, dy = window_origin(records_ref[b, k, 3], m, hw, sub)
    xs, dx = window_origin(records_ref[b, k, 2], n, ww, LANES)
    w = records_ref[b, k, 4]
    h = jnp.where(valid > 0, records_ref[b, k, 5], 0)

    window = canvas_ref[0, 0, pl.ds(ys, hw), pl.ds(xs, ww)]
    # the placement starts at (dy, dx) inside the aligned window; rotate
    # it back to the slot origin and zero everything outside (h, w)
    shifted = rotate(window, jax.lax.rem(hw - dy, hw),
                     jax.lax.rem(ww - dx, ww))
    mask = placement_mask((hw, ww), 0, 0, h, w)
    out_ref[0, 0] = jnp.where(mask, shifted, 0.0).astype(out_ref.dtype)


def unstitch_pallas(canvases: jnp.ndarray, records: jnp.ndarray,
                    num_patches: int, hmax: int, wmax: int,
                    *, interpret: bool = False) -> jnp.ndarray:
    """Inverse of :func:`stitch_pallas`: gather each placement back out.

    canvases: (B, M, N, C); records: (B, K, 6) int32 as in stitch.
    Returns patch slots (num_patches, hmax, wmax, C) with each slot's
    (h, w) region copied from its placement and the padding zeroed.
    Slots not referenced by any valid record are undefined — the packer
    places every queued patch exactly once, so this never happens for
    real plans.
    """
    b, m, n, c = canvases.shape
    _, k, _ = records.shape
    assert hmax <= m and wmax <= n, "patch slot larger than canvas"
    dtype = canvases.dtype
    if num_patches == 0 or b == 0 or k == 0:
        return jnp.zeros((num_patches, hmax, wmax, c), dtype)

    hw, ww = window_shape(m, n, hmax, wmax, dtype)
    kernel = functools.partial(_unstitch_kernel, m=m, n=n, hw=hw, ww=ww,
                               sub=sublane_tile(dtype))
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=(b, c, k),
        in_specs=[
            pl.BlockSpec((1, 1, m, n),
                         lambda bi, ci, ki, recs: (bi, ci, 0, 0)),
        ],
        # scatter each placement to its slot; invalid records park on the
        # dummy slot at index num_patches so they cannot clobber live data
        out_specs=pl.BlockSpec(
            (1, 1, hw, ww),
            lambda bi, ci, ki, recs: (jnp.where(recs[bi, ki, 0] > 0,
                                                recs[bi, ki, 1], num_patches),
                                      ci, 0, 0)),
    )
    isz = np.dtype(dtype).itemsize
    out = pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((num_patches + 1, c, hw, ww), dtype),
        compiler_params=pltpu.CompilerParams(vmem_limit_bytes=vmem_limit(
            2 * m * n * isz, 2 * hw * ww * isz, 4 * hw * ww * 4)),
        interpret=interpret,
    )(records, canvases.transpose(0, 3, 1, 2))
    return out[:num_patches, :, :hmax, :wmax].transpose(0, 2, 3, 1)
