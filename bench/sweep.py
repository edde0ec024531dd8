#!/usr/bin/env python3
"""Find a cell's sustained rate: windows at several frame rates, in one
process on the chip.

    python3 bench/sweep.py --config tangram --traffic crowd4k \\
        --scales 3,3.25,3.5 --seconds 30 --seeds 7,8,9

A rate is sustained when the backlog (patches that had arrived and were
not finished) does not grow by more than one invocation's worth (the
window's mean patches per invocation) from the window's second quarter
to its last.  The invoker holds up to one SLO of arrivals on purpose, so
the backlog is measured against the window once it has filled, not
against the empty start.
Attainment is not the criterion: with the invoker's table as it is,
attainment can rise with the rate, because memory-triggered fires come
earlier than timer fires.  Each rate runs one window per seed (the
seeds draw the cameras' phases); the sustained rate is the highest below
the first rate that some seed's window did not sustain.  Prints one JSON
line per window and, last, one with the sustained rate.
"""
from __future__ import annotations

import argparse
import json
import sys
import time
from pathlib import Path

ROOT = Path(__file__).resolve().parents[1]
sys.path.insert(0, str(ROOT))


def main(argv=None) -> int:
    p = argparse.ArgumentParser()
    p.add_argument("--config", required=True)
    p.add_argument("--traffic", required=True)
    p.add_argument("--scales", required=True)
    p.add_argument("--seconds", type=float, default=20.0)
    p.add_argument("--seeds", default="7")
    args = p.parse_args(argv)
    sys.path.insert(0, str(ROOT / "src"))
    from bench import harness, run, traffic

    run.device_check(1)
    run.enable_cache()
    config = harness.load_config(args.config)
    mix = traffic.load_mix(args.traffic)
    seeds = [int(s) for s in args.seeds.split(",")]
    h = harness.Harness(config, mix, 1, seeds[0])
    held = {}
    for scale in (float(s) for s in args.scales.split(",")):
        for seed in seeds:
            row = window(h, mix, seed, scale, args.seconds)
            held.setdefault(row["fps_per_camera"], []).append(
                row["sustained"])
            print(json.dumps(row), flush=True)
    sustained = None
    for fps in sorted(held):
        if not all(held[fps]):
            break
        sustained = fps
    print(json.dumps({"sustained_fps_per_camera": sustained,
                      "seeds": seeds}), flush=True)
    return 0


def window(h, mix, seed, scale, seconds) -> dict:
    from bench.metrics import reader

    t0 = time.perf_counter()
    r, _ = h.window(seed, seconds, fps_scale=scale, checked=False,
                    t_setup0=t0)
    per_inv = sum(x.patches for x in r.invocations) / max(
        len(r.invocations), 1)
    row = {"fps_per_camera": mix["fps_per_camera"] * scale, "seed": seed,
           "offered_patches_per_s": len(r.t_gen) / r.seconds,
           "invocations": len(r.invocations),
           "patches_per_invocation": per_inv,
           "backlog_growth": r.backlog_growth(),
           "sustained": r.backlog_growth() <= per_inv,
           "late_p99_ms": 1e3 * float(sorted(r.late_s)[
               int(0.99 * (len(r.late_s) - 1))]),
           "warm_s": r.setup_s}
    for name in ("slo_attainment", "p95_latency_ms", "billed_s_per_kpatch",
                 "patches_per_s", "canvas_fill", "host_ms_per_inv",
                 "slack_miss_ms", "compiles_in_window"):
        row[name] = reader(name)(r)
    return row


if __name__ == "__main__":
    sys.exit(main())
