"""The unfused path's host stitch and unstitch (``stitch_ops.stitch_plan_host``,
``stitch_ops.unstitch_plan_host``) against the device kernels they replace:
the canvas batch and each patch's evidence are the same bit for bit, and an
unfused ``DeviceExecutor`` serves what the slots -> device-stitch ->
device-unstitch pipeline served."""
import dataclasses

import jax.numpy as jnp
import numpy as np
import pytest

from repro.core.engine import DeviceExecutor
from repro.core.invoker import Invocation
from repro.core.partitioning import Patch
from repro.core.stitching import BatchPlan, build_batch_plan, stitch
from repro.kernels.stitch import ops as stitch_ops

M = N = 64
#: the tiny detector's canvas side
C = 128


def device_stitch(crops, plan):
    """The device path it replaces: pow2-padded slots, stitched by the
    XLA reference."""
    slots = jnp.asarray(stitch_ops.pack_plan_host(crops, plan))
    return np.asarray(stitch_ops.stitch_canvases(
        slots, jnp.asarray(plan.records), plan.canvas_m, plan.canvas_n,
        impl="xla"))


def assert_bitwise(a, b):
    assert a.shape == b.shape and a.dtype == b.dtype == np.float32
    assert np.array_equal(a.view(np.uint32), b.view(np.uint32))


def random_case(seed):
    """Patches over several canvases, some records invalidated, one crop
    missing (its frame gone: zeros) and one cut short by its frame's edge."""
    rng = np.random.default_rng(seed)
    n = int(rng.integers(6, 20))
    patches = []
    for i in range(n):
        w, h = int(rng.integers(4, 40)), int(rng.integers(4, 40))
        patches.append(Patch(0, 0, w, h, frame_id=i))
    # a canvas-filling patch: its placement touches all four edges
    patches.append(Patch(0, 0, N, M, frame_id=n))
    plan = build_batch_plan(patches, stitch(patches, M, N), M, N)
    records = plan.records.copy()
    live = np.argwhere(records[..., 0] > 0)
    for bi, k in live[rng.random(len(live)) < 0.2]:
        records[bi, k, 0] = 0
    plan = dataclasses.replace(plan, records=records)
    crops = [rng.normal(size=(p.h, p.w, 3)).astype(np.float32)
             for p in patches]
    gone = int(rng.integers(0, n))
    crops[gone] = np.zeros((patches[gone].h, patches[gone].w, 3), np.float32)
    short = int(rng.integers(0, n))
    crops[short] = crops[short][: max(1, patches[short].h // 2)]
    return crops, plan, patches


@pytest.mark.parametrize("seed", range(8))
def test_host_stitch_equals_device_stitch_bitwise(seed):
    crops, plan, _ = random_case(seed)
    assert plan.num_canvases > 1
    assert_bitwise(stitch_ops.stitch_plan_host(crops, plan),
                   device_stitch(crops, plan))


def device_unstitch(canvases, plan, patches):
    """The device path it replaces: the XLA reference gather into
    pow2-padded slots, each patch's (h, w) corner cut out."""
    slots = np.asarray(stitch_ops.unstitch_patches(
        jnp.asarray(canvases), jnp.asarray(plan.records), plan.slot_capacity,
        plan.hmax, plan.wmax, impl="xla"))
    return [slots[i, :p.h, :p.w] for i, p in enumerate(patches)]


@pytest.mark.parametrize("seed", range(8))
def test_host_unstitch_equals_device_unstitch_bitwise(seed):
    """On noise canvases (so a cut one pixel off shows), with records
    invalidated (those patches come back zero), a short crop and a
    canvas-filling placement flush with every edge."""
    _, plan, patches = random_case(seed)
    rng = np.random.default_rng(100 + seed)
    canvases = rng.normal(size=(plan.num_canvases, M, N, 3)).astype(
        np.float32)
    host = stitch_ops.unstitch_plan_host(canvases, plan, patches)
    placed = {slot for _, slot, *_ in plan.placements()}
    assert len(placed) < len(patches)               # some come back zero
    assert len(host) == len(patches)
    for i, (a, b) in enumerate(zip(host,
                                   device_unstitch(canvases, plan, patches))):
        assert a.flags.c_contiguous and a.base is None
        assert_bitwise(a, b)
        assert a.any() == (i in placed)


def test_host_stitch_at_the_canvas_edges_into_a_used_buffer():
    """Placements flush with each corner, an invalid record and a record
    whose slot holds no crop, stitched into a buffer a earlier batch
    left dirty: the pad rows after the plan's own come out zero too."""
    rng = np.random.default_rng(11)
    sizes = [(16, 24), (8, 8), (24, 16), (32, 32), (8, 16)]
    records = np.zeros((2, 4, 6), np.int32)
    records[0, 0] = (1, 0, 0, 0, 16, 24)                  # top left
    records[0, 1] = (1, 1, N - 8, 0, 8, 8)                # top right
    records[0, 2] = (1, 2, 0, M - 16, 24, 16)             # bottom left
    records[1, 0] = (1, 3, N - 32, M - 32, 32, 32)        # bottom right
    records[1, 1] = (0, 4, 0, 0, 8, 16)                   # invalid
    records[1, 2] = (1, 5, 0, 0, 8, 8)                    # no crop: zeros
    plan = BatchPlan(canvas_m=M, canvas_n=N, num_canvases=2, num_patches=6,
                     slots_per_canvas=4, hmax=32, wmax=32, records=records)
    crops = [rng.normal(size=(h, w, 3)).astype(np.float32)
             for w, h in sizes]
    used = np.full((5, M, N, 3), 7.0, np.float32)
    host = stitch_ops.stitch_plan_host(crops, plan, out=used)
    assert host is used
    assert_bitwise(host[:2], device_stitch(crops, plan))
    assert not host[2:].any()
    np.testing.assert_array_equal(host[1, M - 32:, N - 32:], crops[3])
    patches = [Patch(0, 0, w, h) for w, h in sizes] + [Patch(0, 0, 8, 8)]
    back = stitch_ops.unstitch_plan_host(host, plan, patches)
    for a, b in zip(back, device_unstitch(host[:2], plan, patches)):
        assert_bitwise(a, b)
    for i in (0, 1, 2, 3):                          # flush with the edges
        assert_bitwise(back[i], crops[i])
    assert not back[4].any() and not back[5].any()  # invalid; no crop


def test_host_stitch_of_an_empty_plan():
    plan = build_batch_plan([], [], M, N)
    out = stitch_ops.stitch_plan_host([], plan)
    assert out.shape == (0, M, N, 3) and out.dtype == np.float32


# ------------------------------------------------ executor against oracle ----

@pytest.fixture(scope="module")
def tiny():
    from repro.launch.serve import build_detector

    cfg, params, serve_fn, rules = build_detector(canvas=C)
    rng = np.random.default_rng(3)
    frames = {fid: np.asarray(rng.normal(size=(C, 2 * C, 3)), np.float32)
              for fid in (0, 1)}
    patches = [Patch(10, 10, 74, 74, frame_id=0),
               Patch(80, 20, 120, 60, frame_id=0),
               Patch(0, 0, 48, 48, frame_id=1),
               Patch(128, 64, 192, 128, frame_id=1),
               Patch(220, 100, 280, 140, frame_id=1),    # past the frame
               Patch(30, 40, 90, 100, frame_id=2),       # frame never added
               Patch(0, 0, C, C, frame_id=0)]
    return dict(params=params, serve_fn=serve_fn, rules=rules,
                frames=frames, patches=patches)


def oracle(tiny, inv, obj_threshold):
    """The slots -> device-stitch -> device-unstitch pipeline, as the
    executor ran it before the host stitch and unstitch: the trunk's
    objectness, routed detections, per-frame evidence and the canvas
    batch."""
    plan = inv.batch_plan()
    crops = []
    for p in inv.patches:
        f = tiny["frames"].get(p.frame_id)
        crops.append(np.zeros((p.h, p.w, 3), np.float32) if f is None
                     else f[p.y0:p.y1, p.x0:p.x1])
    slots = jnp.asarray(stitch_ops.pack_plan_host(crops, plan))
    records = jnp.asarray(plan.records)
    canvases = stitch_ops.stitch_canvases(slots, records, C, C)
    obj, boxes = tiny["serve_fn"](tiny["params"], canvases)
    patch_out = np.asarray(stitch_ops.unstitch_patches(
        canvases, records, plan.slot_capacity, plan.hmax, plan.wmax))
    dets = stitch_ops.route_detections(plan, inv.patches, np.asarray(obj),
                                       np.asarray(boxes), obj_threshold)
    pix = {}
    for i, p in enumerate(inv.patches):
        pix.setdefault(p.frame_id, []).append(patch_out[i, :p.h, :p.w])
    return np.asarray(obj), dets, pix, np.asarray(canvases)


def assert_same_evidence(pix, want_pix):
    assert set(pix) == set(want_pix)
    for fid in want_pix:
        assert len(pix[fid]) == len(want_pix[fid])
        for a, b in zip(pix[fid], want_pix[fid]):
            assert_bitwise(a, b)


@pytest.mark.parametrize("how", ["xla", "pallas", "mesh"])
def test_unfused_executor_serves_what_device_stitch_served(tiny, how,
                                                           monkeypatch):
    """Two invocations of three canvases each, the second stitched into
    the host batch the first left: each routes and serves what the
    oracle does, including the evidence of a patch past its frame, of
    one whose frame was never added and of a placement flush with the
    canvas's edges.  The first's evidence still holds after the second
    reused its buffer, which was given back only once the evidence was
    copied out; only ``obj`` and ``boxes`` come back from the device."""
    from repro.launch.mesh import make_serve_mesh

    full = tiny["patches"]
    # two whole canvases, then one patch
    few = [full[-1], Patch(C, 0, 2 * C, C, frame_id=1), full[2]]
    invs = []
    for patches in (full, few):
        canvases = stitch(patches, C, C)
        assert len(canvases) == 3
        invs.append(Invocation(0.0, list(canvases), list(patches), 0.0,
                               "timer"))
    obj = oracle(tiny, invs[0], 0.5)[0]
    threshold = float(np.quantile(obj, 0.9))        # routes a tenth

    kw = {"use_pallas": how == "pallas"}
    if how == "mesh":
        kw.update(mesh=make_serve_mesh(1), rules=tiny["rules"])
    seen = []

    def serve_fn(params, canvases):
        seen.append(np.asarray(canvases))
        return tiny["serve_fn"](params, canvases)

    ex = DeviceExecutor(serve_fn, tiny["params"], C, C,
                        obj_threshold=threshold, **kw)
    unstitch = stitch_ops.unstitch_plan_host
    free_at_unstitch = []

    def watched(canvases, *a, **k):
        free_at_unstitch.append(any(
            b is canvases for b in ex._free_canvases.get(canvases.shape, [])))
        return unstitch(canvases, *a, **k)
    monkeypatch.setattr(stitch_ops, "unstitch_plan_host", watched)
    for fid in tiny["frames"]:
        ex.add_frame(fid, tiny["frames"][fid],
                     sum(1 for inv in invs for p in inv.patches
                         if p.frame_id == fid))
    bufs, served = [], []
    for inv in invs:
        obj, want_dets, want_pix, want_canvases = oracle(tiny, inv,
                                                         threshold)
        before = ex.bytes_from_device
        dets, pix = ex.resolve(ex.submit(inv)).outputs
        assert_bitwise(seen[-1], want_canvases)
        assert dets == want_dets
        assert_same_evidence(pix, want_pix)
        served.append((pix, want_pix))
        # obj (B, s, s) and boxes (B, s, s, 4), float32 over the batch
        # rows the device ran
        assert ex.bytes_from_device - before == (
            obj.nbytes * 5 * len(seen[-1]) // obj.shape[0])
        bufs.append(list(ex._free_canvases[(3, C, C, 3)]))
    assert len(bufs[0]) == 1 and bufs[1][0] is bufs[0][0]   # reused
    assert free_at_unstitch == [False, False]
    for pix, want_pix in served:                    # nothing overwritten
        assert_same_evidence(pix, want_pix)
    # the patch past its frame comes back zero past the frame's edge, the
    # patch whose frame was never added all zero, and the canvas-filling
    # patch as the frame's own pixels
    first = served[0][0]
    frame1 = tiny["frames"][1]
    past = first[1][2]                              # rows 100:140, cols 220:280
    assert past.shape == (40, 60, 3)
    assert_bitwise(past[:C - 100, :2 * C - 220].copy(), frame1[100:, 220:])
    rest = past.copy()
    rest[:C - 100, :2 * C - 220] = 0.0
    assert not rest.any()
    assert first[2][0].shape == (60, 60, 3) and not first[2][0].any()
    assert_bitwise(first[0][2], tiny["frames"][0][:C, :C])
    assert (ex.n_host_stitched, ex.n_fused) == (2, 0)
    assert ex.n_sharded == 0                        # one device
