"""Shared pieces of the benchmark's own tests: CPU-sized cells.

The benchmark runs on a TPU; these tests drive its harness on the CPU at
a tiny size (2-layer trunks on 128-pixel canvases, 480x270 frames), one
cell per configuration of the benchmark, so that everything but the chip
is exercised in every test run.
"""
from __future__ import annotations

import sys
from pathlib import Path

import pytest

ROOT = Path(__file__).resolve().parents[2]
if str(ROOT) not in sys.path:
    sys.path.insert(0, str(ROOT))

#: the tiny cells' limits, set from CPU readings at these sizes (seeds
#: 2**31 + 101..103; tests/bench/test_bench_check.py holds both sides):
#: the bf16 program read an objectness gap of 0.0049-0.0062 and a box gap
#: of 0.0049-0.0097 on the tangram cell, 0.0042-0.0066 and 0.0056-0.0107
#: on the vit_s16 cell; the fp8 reference 0.053-0.079 and 0.063-0.127,
#: and 0.081-0.124 and 0.169-0.218
TINY_LIMITS = {"obj_gap": 0.015, "box_gap": 0.03}

#: each benchmark configuration's trunk at a CPU size, keeping what sets
#: its cells apart: ViT-S/16 has four times ViT-B/32's tokens a canvas
#: at half its width
TINY_ARCH = {
    "tangram": dict(canvas=128, patch=16, n_layers=2, d_model=64,
                    n_heads=4, d_ff=128),
    "vit_s16": dict(canvas=128, patch=8, n_layers=2, d_model=32,
                    n_heads=2, d_ff=64),
}

#: one tiny cell per configuration, on the traffic of its cell
TINY_CELLS = [
    {"name": "tangram.crowd.r80", "config": "tangram", "traffic": "crowd4k",
     "chips": 1},
    {"name": "vit_s16.sparse.r80", "config": "vit_s16",
     "traffic": "sparse4k", "chips": 1},
]


def tiny_config(name: str) -> dict:
    from bench import harness

    cfg = harness.load_config(name)
    cfg["arch"] = dict(TINY_ARCH[name], param_dtype="bfloat16",
                       compute_dtype="bfloat16")
    cfg["latency_profile"] = {"batch_sizes": [1, 2, 4], "iters": 2,
                              "warmup": 1}
    cfg["limits"] = dict(TINY_LIMITS)
    return cfg


def tiny_mix(name: str) -> dict:
    from bench import traffic

    mix = traffic.load_mix(name)
    mix.update(frame_w=480, frame_h=270, fps_per_camera=2.0, ring_frames=1)
    return mix


@pytest.fixture(scope="module", params=TINY_CELLS,
                ids=[c["config"] for c in TINY_CELLS])
def tiny_harness(request):
    """One tiny harness per test module and tiny cell (model, weights,
    latency table)."""
    from bench import harness

    cell = request.param
    return harness.Harness(tiny_config(cell["config"]),
                           tiny_mix(cell["traffic"]), 1, seed=3,
                           require_tpu=False, log=lambda msg: None)
