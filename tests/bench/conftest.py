"""Shared pieces of the benchmark's own tests: CPU-sized cells.

The benchmark runs on a TPU; these tests drive its harness on the CPU at
a tiny size (each configuration's ``tiny_arch``: 2-layer trunks on
128-pixel canvases, 480x270 frames), one cell per configuration of
``BENCHMARK.json``, so that everything but the chip is exercised in every
test run, and a configuration added as files is exercised with them.
"""
from __future__ import annotations

import json
import re
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


def tiny_cells(bench: dict) -> list:
    """One tiny cell per configuration, on the mix of its first cell with
    the rate taken off (``crowd4k.fps2.4`` -> ``crowd4k``): the tiny
    harness sets its own rate."""
    cells = []
    for c in bench["configs"]:
        w = next(w for w in bench["workloads"] if w["config"] == c["name"])
        cells.append({"name": w["name"], "config": c["name"],
                      "traffic": re.sub(r"\.fps[0-9.]+$", "", w["traffic"]),
                      "chips": 1})
    return cells


TINY_CELLS = tiny_cells(json.loads((ROOT / "BENCHMARK.json").read_text()))


def tiny_config(name: str) -> dict:
    from bench import harness

    cfg = harness.load_config(name)
    cfg["arch"] = dict(cfg["arch"], **cfg["tiny_arch"])
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
