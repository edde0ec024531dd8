"""Shared pieces of the benchmark's own tests: a CPU-sized cell.

The benchmark runs on a TPU; these tests drive its harness on the CPU at
a tiny size (a 2-layer trunk on 128-pixel canvases, 480x270 frames), so
that everything but the chip is exercised in every test run.
"""
from __future__ import annotations

import sys
from pathlib import Path

import pytest

ROOT = Path(__file__).resolve().parents[2]
if str(ROOT) not in sys.path:
    sys.path.insert(0, str(ROOT))

#: the tiny cell's limits, set from CPU readings at this size: the bf16
#: program read an objectness gap of 0.0035-0.0047 and a box gap of
#: 0.0053-0.0077 on two seeds; the fp8 reference 0.038-0.063 and
#: 0.075-0.145 (tests/bench/test_bench_control.py holds both sides)
TINY_LIMITS = {"obj_gap": 0.015, "box_gap": 0.03}


def tiny_config(dtype: str = "bfloat16") -> dict:
    from bench import harness

    cfg = harness.load_config("tangram")
    cfg["arch"] = dict(canvas=128, patch=16, n_layers=2, d_model=64,
                       n_heads=4, d_ff=128, param_dtype=dtype,
                       compute_dtype=dtype)
    cfg["latency_profile"] = {"batch_sizes": [1, 2, 4], "iters": 2,
                              "warmup": 1}
    cfg["limits"] = dict(TINY_LIMITS)
    return cfg


def tiny_mix(name: str = "crowd4k") -> dict:
    from bench import traffic

    mix = traffic.load_mix(name)
    mix.update(frame_w=480, frame_h=270, fps_per_camera=2.0, ring_frames=1)
    return mix


TINY_CELL = {"name": "tangram.crowd.r80", "config": "tangram",
             "traffic": "crowd4k", "chips": 1}


@pytest.fixture(scope="module")
def tiny_harness():
    """One tiny harness per test module (model, weights, latency table)."""
    from bench import harness

    return harness.Harness(tiny_config(), tiny_mix(), 1, seed=3,
                           require_tpu=False, log=lambda msg: None)
