#!/usr/bin/env python3
"""Readings that the correctness limits are set from, in one process on
the chip: for each seed, one window of the cell at its own load, the
program's gaps to the plain reference, and the same gaps for the
controls put in the program's place on the same invocations:

* ``int8`` — the program's own int8-weight path (``quant_weights``,
  ``models/quantize.py``) on the benchmark's weights;
* ``fp8`` — the configuration's trunk reference (its ``bench/trunks/``
  module's ``forward``) with every matrix product's operands rounded to
  float8 (e4m3).

    python3 bench/calibrate.py --workload tangram.crowd.r80 \\
        --seeds 11,12,13 --seconds 12

Prints one JSON line per seed.  The benchmark's own runs never run it.
"""
from __future__ import annotations

import argparse
import dataclasses
import json
import sys
from pathlib import Path

import numpy as np

ROOT = Path(__file__).resolve().parents[1]
sys.path.insert(0, str(ROOT))


def controls(h):
    """{name: (canvases, records) -> (obj, boxes)} for the harness's
    current weights."""
    import jax
    import jax.numpy as jnp

    from bench import reference
    from repro.models import detector as detector_lib
    from repro.models import quantize as quantize_lib

    cfg_q = dataclasses.replace(h.cfg, quant_weights=True)
    params_q = quantize_lib.quantize_params(
        detector_lib.param_specs(cfg_q), h.params)
    serve_q = jax.jit(lambda p, x: detector_lib.serve(cfg_q, p, x, h.rules))

    def int8(canvases, _records):
        outs = [serve_q(params_q, jnp.asarray(c[None])) for c in canvases]
        return (np.concatenate([np.asarray(o) for o, _ in outs]),
                np.concatenate([np.asarray(b) for _, b in outs]))

    def lowp(canvases, records):
        raw = h.trunk.forward(h.params, canvases, records, h.arch,
                              dtype=jnp.float8_e4m3fn)
        return reference.decode(raw, h.m)

    return {"int8": int8, "fp8": lowp}


def main(argv=None) -> int:
    p = argparse.ArgumentParser()
    p.add_argument("--workload", required=True)
    p.add_argument("--seeds", required=True)
    p.add_argument("--seconds", type=float, default=12.0)
    args = p.parse_args(argv)
    sys.path.insert(0, str(ROOT / "src"))
    from bench import harness, run, traffic

    bench = run.load_benchmark()
    cell = run.cell_of(bench, args.workload)
    run.device_check(cell["chips"])
    run.enable_cache()
    config = harness.load_config(cell["config"])
    mix = traffic.load_mix(cell["traffic"])
    seeds = [int(s) for s in args.seeds.split(",")]
    h = harness.Harness(config, mix, cell["chips"], seeds[0])
    for seed in seeds:
        h.reseed(seed)
        r, kept = h.window(seed, args.seconds)
        row = {"seed": seed, "program": h.check(kept, r)}
        objs = np.concatenate([harness.program_outputs(k, h.m)[0].ravel()
                               for k in kept.values()])
        row["obj_quantiles"] = np.quantile(objs, [0.5, 0.9, 0.99]).tolist()
        for name, fn in controls(h).items():
            row[name] = h.check(kept, None, control=fn)
        print(json.dumps(row), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
