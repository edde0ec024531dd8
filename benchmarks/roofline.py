"""§Roofline report + device hot-path kernel microbench suite.

Two halves:

* the legacy report (default, what ``benchmarks/run.py`` invokes): reads
  the dry-run JSON and prints the per-cell roofline terms.  The dry-run
  itself (launch/dryrun.py) needs the 512-device world and runs
  separately:
      PYTHONPATH=src python -m repro.launch.dryrun --all \
          --json out/dryrun_single_pod.json

* ``--kernels``: the device hot-path microbench (ROADMAP item 3).
  Times the three pipeline variants on one packer-built plan at canvas
  batch >= 8 — **unfused-fp** (stitch kernel -> jit detect -> unstitch
  kernel, the historical path), **fused** (stitch->patch-embed kernel ->
  trunk-from-tokens -> decode->gather kernel), and **fused-int8** (the
  fused path over int8-resident weights) — through
  ``core.latency.measure`` with its sync hook, so async dispatch never
  leaks out of the timed region.  Per-variant rows (mu/sigma,
  canvases/sec, end-to-end patches/sec, analytic stage-boundary bytes
  moved, resident weight bytes) land in ``BENCH_kernels.json`` at the
  repo root, next to ``BENCH_engine.json``.  Block shapes come from
  ``launch/hillclimb.py --cell kernel_blocks`` when that cell has run.

Usage:
    PYTHONPATH=src python -m benchmarks.roofline                # report
    PYTHONPATH=src python -m benchmarks.roofline --kernels --smoke
"""
from __future__ import annotations

import argparse
import json
import os
import pathlib
import sys
import time

import jax.numpy as jnp
import numpy as np

from benchmarks import common

JSON_PATH = os.path.join(os.path.dirname(__file__), "..", "out",
                         "dryrun_single_pod.json")

KERNELS_JSON = pathlib.Path(__file__).resolve().parent.parent / \
    "BENCH_kernels.json"


def load(path=JSON_PATH):
    if not os.path.exists(path):
        return None
    return json.load(open(path))


def print_table(data):
    cols = ("arch", "shape", "bottleneck", "t_compute_s", "t_memory_s",
            "t_collective_s", "useful_flops_ratio", "roofline_fraction")
    print(",".join(cols))
    for r in data["results"]:
        print(",".join(str(r[c]) for c in cols))
    worst = min(data["results"],
                key=lambda r: float(r["roofline_fraction"]))
    coll = [r for r in data["results"] if r["bottleneck"] == "collective"]
    return worst, coll


def kernel_microbench():
    """Interpret-mode kernel timings (CPU correctness path, not TPU perf)."""
    from repro.kernels.attention import ops as aops
    rng = np.random.default_rng(0)
    q = jnp.asarray(rng.normal(size=(1, 256, 4, 64)), jnp.float32)
    k = jnp.asarray(rng.normal(size=(1, 256, 2, 64)), jnp.float32)
    t0 = time.perf_counter()
    aops.flash_attention(q, k, k, causal=True, block_q=128, block_kv=128,
                         interpret=True).block_until_ready()
    return (time.perf_counter() - t0) * 1e6


def _build_bench_plan(m: int, n: int, min_canvases: int, seed: int = 11):
    """A packer-built plan with at least ``min_canvases`` canvases, plus
    packed slot pixels — the shared input for every variant."""
    from repro.core.partitioning import Patch
    from repro.core.stitching import build_batch_plan, stitch
    from repro.kernels.stitch import ops as stitch_ops

    rng = np.random.default_rng(seed)
    patches = []
    while True:
        patches.append(Patch(0, 0, int(rng.integers(48, n // 2 + 33)),
                             int(rng.integers(48, m // 2 + 33)),
                             frame_id=len(patches) % 5))
        canvases = stitch(patches, m, n)
        if len(canvases) >= min_canvases:
            break
    plan = build_batch_plan(patches, canvases, m, n)
    crops = [np.asarray(rng.normal(size=(p.h, p.w, 3)), np.float32)
             for p in patches]
    slots = stitch_ops.pack_plan_host(crops, plan)
    return plan, slots


def _weight_nbytes(params) -> int:
    import jax
    return int(sum(np.asarray(l).nbytes
                   for l in jax.tree_util.tree_leaves(params)))


def kernel_suite(smoke: bool = False, out_path=None) -> dict:
    """The before/after microbench: unfused-fp vs fused vs fused-int8.

    All three variants run the *kernel* implementations (Pallas,
    interpret mode on CPU — identical code path to a TPU launch) over
    the same plan and weights, timed through ``core.latency.measure``
    with ``jax.block_until_ready`` as the sync hook.  Interpret-mode
    wall clocks measure kernel work on this host, not TPU performance;
    the analytic ``bytes_moved`` column (stage-boundary HBM traffic of
    the stitch/embed/decode/gather stages) is host-independent and is
    what the fused path is built to shrink.
    """
    import jax

    from repro.core.latency import measure
    from repro.kernels.stitch import ops as stitch_ops
    from repro.launch.serve import build_detector
    from repro.models import detector as detector_lib

    jax.devices()   # lock in the platform before any lazy heavy imports
    m = n = 128
    min_canvases = 8
    plan, slots_np = _build_bench_plan(m, n, min_canvases)
    bcount = plan.num_canvases
    slots = jnp.asarray(slots_np)
    records = jnp.asarray(plan.records)
    impl = stitch_ops.pallas_impl()

    cfg_fp, params_fp, serve_fp, rules_fp = build_detector(canvas=m)
    cfg_q, params_q, serve_q, rules_q = build_detector(canvas=m,
                                                       quantize=True)
    patch = cfg_fp.patch
    side = m // patch
    seq = side * side
    d = cfg_fp.d_model

    try:
        from repro.launch.hillclimb import pick_block_rows
        block_rows = pick_block_rows(m, n, patch)
    except Exception:
        block_rows = None

    def tokens_fn(cfg, rules):
        return jax.jit(lambda p, t: detector_lib.forward_tokens(
            cfg, p, t, rules))

    tok_fp = tokens_fn(cfg_fp, rules_fp)
    tok_q = tokens_fn(cfg_q, rules_q)
    ek_fp, eb_fp = detector_lib.embed_params(cfg_fp, params_fp)
    ek_q, eb_q = detector_lib.embed_params(cfg_q, params_q)

    def unfused(_b):
        canvases = stitch_ops.stitch_canvases(slots, records, m, n,
                                              impl=impl)
        obj, boxes = serve_fp(params_fp, canvases)
        patch_out = stitch_ops.unstitch_patches(
            canvases, records, plan.slot_capacity, plan.hmax, plan.wmax,
            impl=impl)
        return obj, boxes, patch_out

    def fused(_b, _tok=None, _p=None, _ek=None, _eb=None):
        tokens = stitch_ops.stitch_embed(slots, records, _ek, _eb, m, n,
                                         patch, block_rows=block_rows,
                                         impl=impl)
        raw = _tok(_p, tokens)
        return stitch_ops.unstitch_decode(raw, records, patch,
                                          plan.slot_capacity, impl=impl)

    iters, warmup = (3, 1) if smoke else (10, 2)

    def run(fn):
        tbl = measure(fn, batch_sizes=(bcount,), iters=iters,
                      warmup=warmup, sync=jax.block_until_ready)
        return tbl.table[bcount]

    # analytic stage-boundary HBM traffic (f32): what crosses between
    # the stitch / detect-entry / detect-exit / gather stages.  The
    # trunk's internal traffic is identical across variants and
    # excluded; the weight column captures the int8 residency win.
    f32 = 4
    slot_bytes = plan.slot_capacity * plan.hmax * plan.wmax * 3 * f32
    canvas_bytes = bcount * m * n * 3 * f32
    token_bytes = bcount * seq * d * f32
    raw_bytes = bcount * side * side * 5 * f32
    grid_bytes = plan.slot_capacity * side * side * 5 * f32
    decoded_bytes = bcount * side * side * 5 * f32   # obj + 4 box coords
    unfused_bytes = (slot_bytes            # stitch reads slots
                     + canvas_bytes        # stitch writes canvases
                     + canvas_bytes        # patch-embed re-reads them
                     + decoded_bytes       # decode writes obj+boxes
                     + canvas_bytes        # unstitch re-reads canvases
                     + slot_bytes)         # unstitch writes patch slots
    fused_bytes = (slot_bytes              # fused stitch reads slots
                   + token_bytes           # ...and writes tokens directly
                   + raw_bytes             # decode+gather reads raw head
                   + grid_bytes)           # ...and writes slot grids

    rows = []
    for name, fn, wbytes, bytes_moved in (
            ("unfused-fp", unfused, _weight_nbytes(params_fp),
             unfused_bytes),
            ("fused",
             lambda b: fused(b, _tok=tok_fp, _p=params_fp, _ek=ek_fp,
                             _eb=eb_fp),
             _weight_nbytes(params_fp), fused_bytes),
            ("fused-int8",
             lambda b: fused(b, _tok=tok_q, _p=params_q, _ek=ek_q,
                             _eb=eb_q),
             _weight_nbytes(params_q), fused_bytes)):
        mu, sigma = run(fn)
        rows.append({
            "name": name, "canvas_batch": bcount,
            "patches": plan.num_patches,
            "mu_s": round(mu, 6), "sigma_s": round(sigma, 6),
            "canvases_per_s": round(bcount / mu, 1),
            "patches_per_s": round(plan.num_patches / mu, 1),
            "bytes_moved": int(bytes_moved),
            "weight_bytes": int(wbytes),
            "block_rows": block_rows,
        })
        print(f"{name:12s} mu={mu:.4f}s  {rows[-1]['canvases_per_s']:8.1f} "
              f"canvases/s  {rows[-1]['patches_per_s']:8.1f} patches/s  "
              f"{bytes_moved/1e6:6.2f} MB moved  "
              f"{wbytes/1e6:5.2f} MB weights")

    by = {r["name"]: r for r in rows}
    report = {
        "smoke": bool(smoke),
        "geometry": {"canvas_m": m, "canvas_n": n, "patch": patch,
                     "d_model": d, "canvas_batch": bcount,
                     "patches": plan.num_patches,
                     "slot_capacity": plan.slot_capacity,
                     "hmax": plan.hmax, "wmax": plan.wmax,
                     "impl": impl, "block_rows": block_rows},
        "rows": rows,
        "fused_speedup": round(by["unfused-fp"]["mu_s"]
                               / by["fused"]["mu_s"], 2),
        "bytes_reduction": round(1 - by["fused"]["bytes_moved"]
                                 / by["unfused-fp"]["bytes_moved"], 3),
        "int8_weight_reduction": round(
            1 - by["fused-int8"]["weight_bytes"]
            / by["unfused-fp"]["weight_bytes"], 3),
    }
    out = pathlib.Path(out_path) if out_path else KERNELS_JSON
    out.write_text(json.dumps(report, indent=2) + "\n")
    print(f"fused speedup {report['fused_speedup']}x, bytes moved "
          f"-{100*report['bytes_reduction']:.0f}%, int8 weights "
          f"-{100*report['int8_weight_reduction']:.0f}%")
    print(f"wrote {out}")
    return report


def main(argv=None):
    p = argparse.ArgumentParser()
    p.add_argument("--kernels", action="store_true",
                   help="run the device hot-path kernel microbench and "
                        "write BENCH_kernels.json")
    p.add_argument("--smoke", action="store_true",
                   help="short budgets for CI")
    p.add_argument("--out", default=None,
                   help="kernel microbench output path (default: "
                        "repo-root BENCH_kernels.json)")
    # benchmarks/run.py calls main() with no argv: parse an empty list
    # so its own CLI filter words never leak into this parser
    args = p.parse_args([] if argv is None else argv)

    if args.kernels:
        t0 = time.perf_counter()
        report = kernel_suite(smoke=args.smoke, out_path=args.out)
        us = (time.perf_counter() - t0) * 1e6
        common.emit("roofline_kernels", us,
                    f"fused_speedup={report['fused_speedup']}x "
                    f"bytes_reduction={report['bytes_reduction']}")
        return

    data = load()
    if data is None:
        common.emit("roofline", 0.0,
                    "missing out/dryrun_single_pod.json — run "
                    "repro.launch.dryrun --all first")
        return
    t0 = time.perf_counter()
    worst, coll = print_table(data)
    us = (time.perf_counter() - t0) * 1e6
    n_fit = sum(1 for r in data["results"] if r["fits_hbm"])
    common.emit(
        "roofline", us,
        f"cells={len(data['results'])} fits_hbm={n_fit} "
        f"worst_fraction={worst['arch']}x{worst['shape']}="
        f"{worst['roofline_fraction']} collective_bound={len(coll)}")


if __name__ == "__main__":
    main(sys.argv[1:])
