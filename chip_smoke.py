#!/usr/bin/env python3
"""Smoke test of the serve path on a TPU: the quickest proof that the
system still starts and computes on the chip.

Run from the root of a checkout, on a machine with a TPU:

    python chip_smoke.py               # one chip
    python chip_smoke.py --four-chip   # only the four-chip phase

Everything runs in this one process: the chip belongs to the process that
first touches JAX, so no phase starts a child.  Any platform other than
TPU is a failure, never a fallback.

One-chip phases:

1. device — platform, kind and count of the local devices.
2. kernels — the four stitch kernels, compiled (``impl="pallas"``), on one
   seeded packer-built plan at canvas 1024 with slots up to 512 pixels,
   each compared with its XLA reference at the tolerances of
   ``tests/test_kernels.py`` and ``tests/test_fused_kernels.py``.
3. serve — ``repro.launch.serve.main`` three times with the full-width
   ``tangram`` detector (ViT-B/32 trunk, bf16, 1024² canvas): the XLA
   path, ``--use-pallas-stitch`` and ``--fuse``.  The SLO is set so long
   that every invocation fires on a full canvas budget, so the plans do
   not depend on measured latency and all three runs see the same
   canvases.  The weights are random (seeded by the model name), and the
   untrained trunk's objectness sits near 0.3 on these frames, so the
   runs route at ``THRESHOLD`` instead of the default 0.5, which would
   route nothing and leave nothing to compare.  ``--use-pallas-stitch``
   no longer changes the served path (the unfused path stitches and cuts
   the evidence on the host), so that run must route exactly the XLA
   run's detections.  The fused run computes
   the patch embedding in another summation order, so its bf16 tokens
   differ by an ulp here and there: each of its detections and each of
   the XLA run's must have a partner in the other run within
   ``SCORE_TOL`` and ``BOX_TOL``, except one within ``MARGIN`` of the
   threshold, which may flip.  Every run must end with no frame held.

``--four-chip`` runs one phase instead: the same serve data-parallel over
all four chips (``make_serve_mesh()``), on a one-device mesh
(``--devices 1``) and as four one-chip workers (``--workers 4``); routed
detections must agree across the three, and the four-chip run must split
its canvas batches over the devices.

The last line of standard output is one JSON object,
``{"ok": true, "device": {"platform": ..., "kind": ..., "count": ...}}``;
it is printed only when every phase passed.
"""
from __future__ import annotations

import argparse
import json
import sys
import time
import traceback
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parent / "src"))

CANVAS = 1024
#: objectness a cell needs to be routed in the serve runs
THRESHOLD = 0.25
#: across runs that differ in summation order, a detection may lack a
#: partner only within this much of the threshold (the fused run's scores
#: differed from the XLA run's by at most 0.0046 on a TPU v5e) ...
MARGIN = 0.01
#: ... and partners agree in score and in box corners (pixels) within these
SCORE_TOL = 0.02
BOX_TOL = 4.0


def log(msg: str):
    print(msg, flush=True)


def device_phase(count: int) -> dict:
    import jax

    devices = jax.devices()
    info = {"platform": devices[0].platform, "kind": devices[0].device_kind,
            "count": len(devices)}
    log(f"device: {info}")
    if info["platform"] != "tpu":
        raise RuntimeError(f"no TPU: JAX runs on {info['platform']}")
    if info["count"] < count:
        raise RuntimeError(f"need {count} chips, found {info['count']}")
    return info


def kernel_phase(seed: int):
    import jax.numpy as jnp
    import numpy as np

    from repro.core.partitioning import Patch
    from repro.core.stitching import build_batch_plan, stitch
    from repro.kernels.stitch import ops

    impl = ops.pallas_impl()
    if impl != "pallas":
        raise RuntimeError(f"kernels would run as {impl!r}")
    m = CANVAS
    rng = np.random.default_rng(seed)
    patches = [Patch(0, 0, int(rng.integers(8, m // 2 + 1)),
                     int(rng.integers(8, m // 2 + 1)), frame_id=i % 3)
               for i in range(24)]
    plan = build_batch_plan(patches, stitch(patches, m, m), m, m)
    crops = [rng.normal(size=(p.h, p.w, 3)).astype(np.float32)
             for p in patches]
    slots = jnp.asarray(ops.pack_plan_host(crops, plan))
    records = jnp.asarray(plan.records)
    live = plan.num_patches
    log(f"kernels: plan of {live} patches on {plan.num_canvases} canvases "
        f"{m}x{m}, slots {tuple(slots.shape)}, records "
        f"{tuple(records.shape)}")

    def check(name, got, want, atol=0.0, rtol=0.0):
        got, want = np.asarray(got, np.float32), np.asarray(want, np.float32)
        err = float(np.max(np.abs(got - want))) if got.size else 0.0
        log(f"  {name}: shape {got.shape}, max |pallas - xla| = {err!r}")
        if atol == 0.0 and rtol == 0.0:
            np.testing.assert_array_equal(got, want, err_msg=name)
        else:
            np.testing.assert_allclose(got, want, atol=atol, rtol=rtol,
                                       err_msg=name)

    canvases = ops.stitch_canvases(slots, records, m, m, impl="xla")
    check("stitch", ops.stitch_canvases(slots, records, m, m, impl=impl),
          canvases)
    args = (canvases, records, plan.slot_capacity, plan.hmax, plan.wmax)
    check("unstitch", ops.unstitch_patches(*args, impl=impl)[:live],
          ops.unstitch_patches(*args, impl="xla")[:live])

    patch, d = 32, 768
    kernel = jnp.asarray(rng.normal(size=(patch * patch * 3, d)) * 0.05,
                         jnp.bfloat16)
    bias = jnp.asarray(rng.normal(size=(d,)), jnp.bfloat16)
    args = (slots, records, kernel, bias, m, m, patch)
    check("stitch_embed", ops.stitch_embed(*args, impl=impl),
          ops.stitch_embed(*args, impl="xla"), atol=2e-2, rtol=2e-2)

    side = m // patch
    raw = jnp.asarray(rng.normal(size=(plan.num_canvases, side, side, 5)),
                      jnp.float32)
    args = (raw, records, patch, plan.slot_capacity)
    check("unstitch_decode", ops.unstitch_decode(*args, impl=impl)[:live],
          ops.unstitch_decode(*args, impl="xla")[:live],
          atol=1e-5, rtol=1e-5)


def serve(label: str, frames: int, *extra: str) -> dict:
    from repro.launch import serve as serve_lib

    argv = ["--model", "tangram", "--full-width", "--frames", str(frames),
            "--slo", "1000", "--obj-threshold", str(THRESHOLD), *extra]
    log(f"serve [{label}]: {' '.join(argv)}")
    t0 = time.perf_counter()
    out = serve_lib.main(argv)
    log(f"serve [{label}]: {out['patches']} patches, {out['invocations']} "
        f"invocations ({out['fused']} fused, {out['sharded']} sharded), "
        f"{out['detections']} routed detections, {out['violations']} "
        f"violations, {out['frames_held']} frames held, "
        f"{time.perf_counter() - t0:.1f} s with compiles")
    if out["frames_held"] != 0:
        raise RuntimeError(f"{label}: {out['frames_held']} frames held")
    if out["patches"] == 0 or out["detections"] == 0:
        raise RuntimeError(f"{label}: nothing served or routed")
    return out


def match_routed(label: str, ref: dict, got: dict):
    """Every detection of either run has a partner in the other run's
    frame within ``BOX_TOL`` and ``SCORE_TOL``; only a detection within
    ``MARGIN`` of the threshold may lack one, since the other run's score
    for that cell may fall on the other side of it."""
    worst_s = worst_b = 0.0
    paired = flipped = 0
    for a_name, a, b in (("ref", ref, got), ("got", got, ref)):
        for fid, dets in a.items():
            pool = b.get(fid, [])
            for score, box in dets:
                # nearest partner, box and score scaled by their tolerances
                best = min(((max(abs(x - y) for x, y in zip(box, bb)),
                             abs(score - s)) for s, bb in pool),
                           key=lambda d: max(d[0] / BOX_TOL,
                                             d[1] / SCORE_TOL),
                           default=None)
                if (best is not None and best[0] <= BOX_TOL
                        and best[1] <= SCORE_TOL):
                    paired += 1
                    worst_b = max(worst_b, best[0])
                    worst_s = max(worst_s, best[1])
                elif abs(score - THRESHOLD) < MARGIN:
                    flipped += 1
                else:
                    raise RuntimeError(
                        f"{label}: {a_name} detection {score:.4f} "
                        f"{box} of frame {fid} has no partner "
                        f"(nearest: {best})")
    log(f"  {label}: {paired} detections paired, {flipped} within {MARGIN} "
        f"of the threshold unpaired; max score diff {worst_s!r}, max box "
        f"diff {worst_b!r} px")


def serve_phase(frames: int):
    xla = serve("xla", frames)
    pallas = serve("pallas-stitch", frames, "--use-pallas-stitch")
    fused = serve("fused", frames, "--fuse")
    if pallas["routed"] != xla["routed"]:
        raise RuntimeError("pallas-stitch routed other detections than xla")
    log(f"  pallas-stitch == xla: {pallas['detections']} routed detections, "
        f"identical")
    if fused["fused"] != fused["invocations"]:
        raise RuntimeError("--fuse left invocations unfused")
    log(f"  fused routed {fused['detections']} detections, xla "
        f"{xla['detections']}")
    match_routed("fused vs xla", xla["routed"], fused["routed"])


def four_chip_phase(frames: int):
    import jax.numpy as jnp

    from repro.core.engine import shard_canvases
    from repro.launch.mesh import make_serve_mesh
    from repro.sharding import ShardingConfig

    mesh = make_serve_mesh()
    x, split = shard_canvases(jnp.zeros((4, CANVAS, CANVAS, 3), jnp.float32),
                              mesh, ShardingConfig.make().rules)
    log(f"four-chip: canvas batch sharding {x.sharding}")
    for shard in x.addressable_shards:
        log(f"  {shard.device}: rows {shard.index[0]}, "
            f"shape {tuple(shard.data.shape)}")
    if not split or len({s.device for s in x.addressable_shards}) != 4:
        raise RuntimeError("canvas batch not split over 4 devices")

    dp = serve("data-parallel x4", frames)
    one = serve("one device", frames, "--devices", "1")
    workers = serve("4 workers", frames, "--workers", "4")
    if dp["sharded"] == 0:
        raise RuntimeError("no invocation ran data-parallel")
    for label, run in (("data-parallel x4", dp), ("4 workers", workers)):
        same = ({f: sorted(v) for f, v in run["routed"].items()}
                == {f: sorted(v) for f, v in one["routed"].items()})
        log(f"  {label} vs one device: {run['detections']} vs "
            f"{one['detections']} routed detections"
            f"{', identical' if same else ''}")
        if not same:
            match_routed(f"{label} vs one device", one["routed"],
                         run["routed"])


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--four-chip", action="store_true",
                    help="run only the four-chip data-parallel and "
                         "worker-pool phase")
    ap.add_argument("--frames", type=int, default=64,
                    help="frames per serve run")
    ap.add_argument("--seed", type=int, default=0,
                    help="seed of the kernel phase's plan and data")
    args = ap.parse_args(argv)
    try:
        info = device_phase(4 if args.four_chip else 1)
        from repro.launch.compile_cache import enable_compile_cache

        log(f"compile cache: {enable_compile_cache()}")
    except Exception:
        traceback.print_exc()
        return 1
    if args.four_chip:
        phases = [("four-chip", lambda: four_chip_phase(args.frames))]
    else:
        phases = [("kernels", lambda: kernel_phase(args.seed)),
                  ("serve", lambda: serve_phase(args.frames))]
    failed = []
    for name, run in phases:
        # the phases are independent: a failed one does not hide the next
        t0 = time.perf_counter()
        try:
            run()
        except Exception:
            traceback.print_exc()
            failed.append(name)
        log(f"phase {name}: {'FAILED' if name in failed else 'ok'} "
            f"({time.perf_counter() - t0:.1f} s)")
    if failed:
        return 1
    print(json.dumps({"ok": True, "device": info}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
