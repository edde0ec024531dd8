"""Per-kernel allclose vs the pure-jnp oracles: shape/dtype sweeps
(interpret mode executes the kernel body on CPU)."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import gmm
from repro.core.partitioning import Patch
from repro.core.stitching import build_batch_plan, stitch
from repro.kernels.attention import ops as attn_ops
from repro.kernels.attention.ref import decode_reference, mha_reference
from repro.kernels.gmm import ops as gmm_ops
from repro.kernels.stitch import ops as stitch_ops
from repro.kernels.stitch.ref import stitch_reference, unstitch_reference
from repro.kernels.stitch.stitch import stitch_pallas, unstitch_pallas


# ------------------------------------------------------------ attention ----

ATTN_CASES = [
    # (B, S, H, Kv, D, causal, dtype, bq, bk)
    (1, 128, 4, 4, 64, True, jnp.float32, 64, 64),
    (2, 256, 8, 2, 64, True, jnp.float32, 128, 64),
    (2, 256, 8, 8, 32, False, jnp.float32, 64, 128),
    (1, 512, 4, 1, 128, True, jnp.float32, 128, 128),
    (2, 128, 4, 4, 64, True, jnp.bfloat16, 64, 64),
]


@pytest.mark.parametrize("b,s,h,kv,d,causal,dtype,bq,bk", ATTN_CASES)
def test_flash_attention_matches_ref(b, s, h, kv, d, causal, dtype, bq, bk):
    rng = np.random.default_rng(0)
    q = jnp.asarray(rng.normal(size=(b, s, h, d)), dtype)
    k = jnp.asarray(rng.normal(size=(b, s, kv, d)), dtype)
    v = jnp.asarray(rng.normal(size=(b, s, kv, d)), dtype)
    ref = mha_reference(q, k, v, causal=causal)
    out = attn_ops.flash_attention(q, k, v, causal=causal, block_q=bq,
                                   block_kv=bk, interpret=True)
    tol = 2e-2 if dtype == jnp.bfloat16 else 2e-5
    np.testing.assert_allclose(np.asarray(out, np.float32),
                               np.asarray(ref, np.float32), atol=tol, rtol=tol)


def test_fused_qkv_matches_split():
    """Fused (d, H+2Kv, dh) projection == separate wq/wk/wv."""
    import jax
    from repro.models import attention as A
    rng = np.random.default_rng(3)
    d, H, Kv, dh = 32, 4, 2, 8
    x = jnp.asarray(rng.normal(size=(2, 16, d)), jnp.float32)
    wq = jnp.asarray(rng.normal(size=(d, H, dh)), jnp.float32)
    wk = jnp.asarray(rng.normal(size=(d, Kv, dh)), jnp.float32)
    wv = jnp.asarray(rng.normal(size=(d, Kv, dh)), jnp.float32)
    split = A._qkv({"wq": wq, "wk": wk, "wv": wv}, x, Kv, jnp.float32)
    fused = A._qkv({"wqkv": jnp.concatenate([wq, wk, wv], axis=1)}, x, Kv,
                   jnp.float32)
    for a, b in zip(split, fused):
        # fp32 reduction order differs between the fused/split matmuls
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), rtol=2e-5,
                                   atol=2e-5)


def test_flash_attention_segments():
    rng = np.random.default_rng(1)
    b, s, h, d = 2, 256, 4, 64
    q = jnp.asarray(rng.normal(size=(b, s, h, d)), jnp.float32)
    k = jnp.asarray(rng.normal(size=(b, s, h, d)), jnp.float32)
    v = jnp.asarray(rng.normal(size=(b, s, h, d)), jnp.float32)
    seg = jnp.asarray(np.repeat([0, 1, 2, 3], s // 4)[None].repeat(b, 0))
    ref = mha_reference(q, k, v, causal=True, segment_ids=seg)
    out = attn_ops.flash_attention(q, k, v, causal=True, segment_ids=seg,
                                   block_q=64, block_kv=64, interpret=True)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), atol=2e-5)


@pytest.mark.parametrize("pos", [0, 1, 63, 64, 200, 511])
@pytest.mark.parametrize("kv", [1, 4])
def test_flash_decode_pos_sweep(pos, kv):
    rng = np.random.default_rng(2)
    b, h, d, smax = 2, 8, 64, 512
    q = jnp.asarray(rng.normal(size=(b, 1, h, d)), jnp.float32)
    k = jnp.asarray(rng.normal(size=(b, smax, kv, d)), jnp.float32)
    v = jnp.asarray(rng.normal(size=(b, smax, kv, d)), jnp.float32)
    ref = decode_reference(q, k, v, pos)
    out = attn_ops.flash_decode(q, k, v, pos, block_kv=128, interpret=True)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), atol=2e-5)


# --------------------------------------------------------------- stitch ----

@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize("m,n,hmax,wmax", [(64, 64, 32, 32),
                                           (128, 64, 64, 32),
                                           (64, 128, 64, 64)])
def test_stitch_kernel_random_packings(dtype, m, n, hmax, wmax):
    """Drive the kernel with REAL packer output (non-overlap guaranteed)."""
    rng = np.random.default_rng(int(m + n))
    patches = [Patch(0, 0, int(rng.integers(8, wmax + 1)),
                     int(rng.integers(8, hmax + 1))) for _ in range(9)]
    canvases = stitch(patches, m, n)
    plan = build_batch_plan(patches, canvases, m, n)
    assert plan.hmax <= hmax and plan.wmax <= wmax
    crops = [np.asarray(rng.normal(size=(p.h, p.w, 3)), np.float32)
             for p in patches]
    slots = jnp.asarray(stitch_ops.pack_plan_host(crops, plan), dtype)
    records = jnp.asarray(plan.records)
    ref = stitch_reference(slots, records, m, n)
    out = stitch_pallas(slots, records, m, n, interpret=True)
    np.testing.assert_array_equal(np.asarray(out, np.float32),
                                  np.asarray(ref, np.float32))


def test_stitch_kernel_empty_canvas():
    slots = jnp.zeros((1, 16, 16, 3), jnp.float32)
    records = jnp.zeros((2, 4, 6), jnp.int32)
    out = stitch_pallas(slots, records, 32, 32, interpret=True)
    assert out.shape == (2, 32, 32, 3)
    assert float(jnp.abs(out).sum()) == 0.0


def test_stitch_kernel_zero_patch_packing():
    """A plan built from an empty queue yields a zero canvas batch without
    launching a degenerate (zero-extent) kernel grid."""
    plan = build_batch_plan([], [], 32, 32)
    assert plan.num_canvases == 0 and plan.num_patches == 0
    slots = jnp.zeros((1, plan.hmax, plan.wmax, 3), jnp.float32)
    out = stitch_pallas(slots, jnp.asarray(plan.records), 32, 32,
                        interpret=True)
    assert out.shape == (0, 32, 32, 3)
    # zero-slot records (K = 0) must also short-circuit, not launch
    out = stitch_pallas(jnp.zeros((1, 8, 8, 3), jnp.float32),
                        jnp.zeros((2, 0, 6), jnp.int32), 32, 32,
                        interpret=True)
    assert out.shape == (2, 32, 32, 3)
    assert float(jnp.abs(out).sum()) == 0.0


def test_stitch_kernel_single_patch_packing():
    rng = np.random.default_rng(11)
    patches = [Patch(0, 0, 12, 9)]
    canvases = stitch(patches, 32, 32)
    plan = build_batch_plan(patches, canvases, 32, 32)
    crops = [np.asarray(rng.normal(size=(9, 12, 3)), np.float32)]
    slots = jnp.asarray(stitch_ops.pack_plan_host(crops, plan))
    records = jnp.asarray(plan.records)
    out = stitch_pallas(slots, records, 32, 32, interpret=True)
    assert out.shape == (1, 32, 32, 3)
    np.testing.assert_array_equal(np.asarray(out[0, :9, :12]), crops[0])
    assert float(jnp.abs(out[0, 9:]).sum()) == 0.0
    assert float(jnp.abs(out[0, :, 12:]).sum()) == 0.0


@pytest.mark.parametrize("platform,impl", [("tpu", "pallas"),
                                           ("cpu", "pallas_interpret")])
def test_pallas_impl_follows_the_platform(platform, impl):
    """Compiled kernels on TPU, the interpreter only on CPU."""
    assert stitch_ops.pallas_impl(platform) == impl


def test_pallas_impl_refuses_other_platforms():
    assert stitch_ops.pallas_impl() == "pallas_interpret"   # CPU tests
    with pytest.raises(ValueError):
        stitch_ops.pallas_impl("gpu")


def test_stitch_jit_wrapper_impls_agree():
    rng = np.random.default_rng(5)
    slots = jnp.asarray(rng.normal(size=(3, 16, 16, 3)), jnp.float32)
    records = jnp.asarray([[[1, 0, 0, 0, 16, 16], [1, 1, 16, 16, 8, 8],
                            [0, 0, 0, 0, 0, 0]]], jnp.int32)
    a = stitch_ops.stitch_canvases(slots, records, 32, 32, impl="xla")
    b = stitch_ops.stitch_canvases(slots, records, 32, 32,
                                   impl="pallas_interpret")
    np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


# ------------------------------------------------------------- unstitch ----

@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize("seed", [0, 1, 2, 3])
def test_unstitch_round_trip_property(dtype, seed):
    """stitch -> unstitch is the identity on patch slot contents for any
    real packer output (placements are non-overlapping by construction)."""
    m = n = 64
    rng = np.random.default_rng(seed)
    n_patches = int(rng.integers(1, 12))
    patches = [Patch(0, 0, int(rng.integers(4, 33)), int(rng.integers(4, 33)))
               for _ in range(n_patches)]
    canvases = stitch(patches, m, n)
    plan = build_batch_plan(patches, canvases, m, n)
    crops = [np.asarray(rng.normal(size=(p.h, p.w, 3)), np.float32)
             for p in patches]
    slots = jnp.asarray(stitch_ops.pack_plan_host(crops, plan), dtype)
    records = jnp.asarray(plan.records)
    stitched = stitch_pallas(slots, records, m, n, interpret=True)
    back = unstitch_pallas(stitched, records, plan.num_patches,
                           plan.hmax, plan.wmax, interpret=True)
    # exact round trip: both directions only move pixels, never blend
    # (slots rows past num_patches are pow2-bucket padding, all zero)
    np.testing.assert_array_equal(
        np.asarray(back, np.float32),
        np.asarray(slots[:plan.num_patches], np.float32))


def test_unstitch_kernel_matches_reference():
    m, n = 64, 128
    rng = np.random.default_rng(21)
    patches = [Patch(0, 0, int(rng.integers(8, 49)), int(rng.integers(8, 49)))
               for _ in range(7)]
    canvases = stitch(patches, m, n)
    plan = build_batch_plan(patches, canvases, m, n)
    batch = jnp.asarray(rng.normal(size=(plan.num_canvases, m, n, 3)),
                        jnp.float32)
    records = jnp.asarray(plan.records)
    ref = unstitch_reference(batch, records, plan.num_patches,
                             plan.hmax, plan.wmax)
    out = unstitch_pallas(batch, records, plan.num_patches,
                          plan.hmax, plan.wmax, interpret=True)
    np.testing.assert_array_equal(np.asarray(out), np.asarray(ref))


def test_unstitch_jit_wrapper_impls_agree():
    rng = np.random.default_rng(23)
    batch = jnp.asarray(rng.normal(size=(1, 32, 32, 3)), jnp.float32)
    records = jnp.asarray([[[1, 0, 0, 0, 16, 16], [1, 1, 16, 16, 8, 8],
                            [0, 0, 0, 0, 0, 0]]], jnp.int32)
    a = stitch_ops.unstitch_patches(batch, records, 2, 16, 16, impl="xla")
    b = stitch_ops.unstitch_patches(batch, records, 2, 16, 16,
                                    impl="pallas_interpret")
    np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


def _routing_plan():
    """One 64x64 canvas, two 32x32 placements from different frames."""
    from repro.core.stitching import BatchPlan
    records = np.asarray([[(1, 0, 0, 0, 32, 32),
                           (1, 1, 32, 0, 32, 32)]], np.int32)
    plan = BatchPlan(canvas_m=64, canvas_n=64, num_canvases=1,
                     num_patches=2, slots_per_canvas=2, hmax=32, wmax=32,
                     records=records)
    patches = [Patch(100, 100, 132, 132, frame_id=1),
               Patch(200, 50, 232, 82, frame_id=2)]
    return plan, patches


def test_route_detections_frame_assignment_and_translation():
    plan, patches = _routing_plan()
    obj = np.zeros((1, 4, 4), np.float32)
    boxes = np.zeros((1, 4, 4, 4), np.float32)
    # box center (12, 12) -> placement A -> frame 1 at (100, 100)
    obj[0, 0, 0] = 0.9
    boxes[0, 0, 0] = (4, 4, 20, 20)
    # box center (50, 20) -> placement B -> frame 2 at (200, 50)
    obj[0, 1, 2] = 0.8
    boxes[0, 1, 2] = (40, 10, 60, 30)
    # below threshold: dropped even though it lies inside placement A
    obj[0, 1, 0] = 0.2
    boxes[0, 1, 0] = (4, 20, 20, 30)
    routed = stitch_ops.route_detections(plan, patches, obj, boxes)
    assert set(routed) == {1, 2}
    (s1, b1), = routed[1]
    assert s1 == pytest.approx(0.9)
    assert b1 == pytest.approx((104, 104, 120, 120))
    (s2, b2), = routed[2]
    assert s2 == pytest.approx(0.8)
    assert b2 == pytest.approx((208, 60, 228, 80))


def test_route_detections_clips_spill_and_keeps_subcell_placements():
    plan, patches = _routing_plan()
    obj = np.zeros((1, 4, 4), np.float32)
    boxes = np.zeros((1, 4, 4, 4), np.float32)
    # box center (32, 12) is on placement B's edge; the box spills 8px
    # into placement A and must be clipped to B before translation
    obj[0, 0, 1] = 0.9
    boxes[0, 0, 1] = (24, 4, 40, 20)
    routed = stitch_ops.route_detections(plan, patches, obj, boxes)
    assert set(routed) == {2}
    (_, b2), = routed[2]
    assert b2 == pytest.approx((200, 54, 208, 70))

    # a placement narrower than one detector cell (cell = 16px here)
    # still receives detections: routing is by decoded box center
    from repro.core.stitching import BatchPlan
    narrow = BatchPlan(canvas_m=64, canvas_n=64, num_canvases=1,
                       num_patches=1, slots_per_canvas=1, hmax=10, wmax=10,
                       records=np.asarray([[(1, 0, 44, 20, 10, 10)]],
                                          np.int32))
    npatches = [Patch(300, 400, 310, 410, frame_id=7)]
    obj = np.zeros((1, 4, 4), np.float32)
    boxes = np.zeros((1, 4, 4, 4), np.float32)
    obj[0, 1, 2] = 0.95
    boxes[0, 1, 2] = (45, 21, 53, 29)     # center (49, 25) inside 10x10 rect
    routed = stitch_ops.route_detections(narrow, npatches, obj, boxes)
    assert set(routed) == {7}
    (_, b7), = routed[7]
    assert b7 == pytest.approx((301, 401, 309, 409))


def test_unstitch_empty():
    batch = jnp.zeros((1, 32, 32, 3), jnp.float32)
    out = unstitch_pallas(batch, jnp.zeros((1, 0, 6), jnp.int32), 0, 8, 8,
                          interpret=True)
    assert out.shape == (0, 8, 8, 3)


# ------------------------------------------------------------------ gmm ----

@pytest.mark.parametrize("h,w,bh,bw", [(8, 128, 8, 128), (16, 256, 8, 128),
                                       (32, 512, 8, 256)])
def test_gmm_kernel_matches_oracle(h, w, bh, bw):
    rng = np.random.default_rng(7)
    s_ref = s_pal = gmm.init_state(h, w)
    for i in range(4):
        frame = jnp.asarray(rng.random((h, w)), jnp.float32)
        s_ref, fg_ref = gmm_ops.gmm_update(s_ref, frame, impl="xla")
        s_pal, fg_pal = gmm_ops.gmm_update(s_pal, frame,
                                           impl="pallas_interpret",
                                           block_h=bh, block_w=bw)
        for key in ("w", "mu", "var"):
            np.testing.assert_allclose(np.asarray(s_ref[key]),
                                       np.asarray(s_pal[key]), atol=1e-6)
        np.testing.assert_array_equal(np.asarray(fg_ref), np.asarray(fg_pal))


def test_gmm_background_convergence():
    """Static background absorbed; moving object flagged as foreground."""
    h, w = 16, 128
    state = gmm.init_state(h, w)
    bg = jnp.full((h, w), 0.5, jnp.float32)
    for _ in range(30):
        state, fg = gmm.update_jit(state, bg)
    assert int(fg.sum()) == 0
    frame = bg.at[4:8, 10:30].set(0.95)
    _, fg = gmm.update_jit(state, frame)
    assert int(fg[4:8, 10:30].sum()) >= 0.9 * (4 * 20)
    assert int(fg.sum()) <= 4 * 20 * 1.5
