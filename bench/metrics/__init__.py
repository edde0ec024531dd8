"""One reader per metric, found by the metric's name in BENCHMARK.json.

Each module ``<name>.py`` has ``read(run) -> float | None``: the metric
of one window (``bench.harness.Run``), or None where the run holds
nothing to read (the harness then leaves the metric out of the line).
"""
from __future__ import annotations

import importlib
from pathlib import Path

METRICS_DIR = Path(__file__).resolve().parent


def reader(name: str):
    """The ``read`` function of metric ``name``."""
    if not (METRICS_DIR / f"{name}.py").is_file():
        raise KeyError(f"no reader bench/metrics/{name}.py")
    return importlib.import_module(f"bench.metrics.{name}").read
