"""One module per trunk family, named by a configuration's ``trunk`` key.

Each module ``<trunk>.py`` holds what the benchmark knows of that trunk,
independent of the program (it imports nothing of ``repro``):

* ``forward(params, canvases, records, arch, dtype="float32")`` — the
  plain reference, called once an invocation: its (B, M, N, 3) canvases
  and the placements they were stitched from (``records``, (B, K, 6)) to
  (B, side, side, 5) raw head outputs (objectness and box per cell) as a
  numpy array, every matrix product's operands rounded to ``dtype`` (a
  lower one for a control) and accumulated in float32 at ``highest``
  precision;
* ``flops(arch, rec)`` and ``bytes(arch, rec)`` — the operations and the
  least HBM bytes of one invocation (``bench.harness.InvRecord``, which
  carries the plan's ``records``), so that a trunk that skips tokens
  counts only the work it needs, by its own rule of which tokens live;
* ``params(arch)`` — the number of parameters.

A new trunk family comes as a new module here and a configuration that
names it: the harness, the control and the readers go through the module.
"""
from __future__ import annotations

import importlib
from pathlib import Path

TRUNKS_DIR = Path(__file__).resolve().parent


def load(name: str):
    """The module of trunk family ``name``."""
    if (not isinstance(name, str) or not name.isidentifier()
            or name.startswith("_")
            or not (TRUNKS_DIR / f"{name}.py").is_file()):
        raise ValueError(f"no trunk module bench/trunks/{name}.py")
    return importlib.import_module(f"bench.trunks.{name}")
