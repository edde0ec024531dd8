#!/usr/bin/env python3
"""Run one benchmark cell once, on the chips of this machine.

    python3 bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

from the root of a checkout.  The cell names a configuration
(``bench/configs/<config>.json``) and a traffic mix
(``bench/traffic/<traffic>.json``) in ``BENCHMARK.json``; each metric is
read by ``bench/metrics/<metric>.py``.  The run sets up (timed as
``setup_s``), drives ``--seconds`` of open-loop arrivals at their wall
times, drains, compares a sample of the window's invocations with the
plain reference (``bench/reference.py`` around the configuration's trunk
module, ``bench/trunks/<trunk>.py``), and prints one JSON line as the
last line of standard output: ``correct``, ``attempted``, ``failed``,
``metrics`` (the cell's end-to-end metrics, or with ``--trace 1`` its
per-layer metrics), ``device``, with ``--trace 1`` ``breakdown``, and last
``checks``: each number compared, with its limit.

Anything but a TPU with the cell's chips is an error: the run exits
non-zero and prints no result.
"""
from __future__ import annotations

import time

T0 = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import math  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402

ROOT = Path(__file__).resolve().parents[1]
sys.path.insert(0, str(ROOT))


def parse(argv=None):
    p = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    p.add_argument("--workload", required=True)
    p.add_argument("--seed", type=int, required=True)
    p.add_argument("--seconds", type=float, required=True)
    p.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = p.parse_args(argv)
    if args.seed < 0 or args.seconds <= 0:
        p.error("--seed must be >= 0 and --seconds > 0")
    return args


def load_benchmark(root: Path = ROOT) -> dict:
    return json.loads((root / "BENCHMARK.json").read_text())


def cell_of(bench: dict, name: str) -> dict:
    for w in bench["workloads"]:
        if w["name"] == name:
            return w
    raise SystemExit(f"no workload {name!r} in BENCHMARK.json")


def metrics_of(bench: dict, cell: dict, trace: bool) -> list:
    """The cell's end-to-end metrics, or with a trace its per-layer ones."""
    group = bench["per_layer"] if trace else bench["end_to_end"]
    return [m for m in group
            if cell["name"] in m.get("workloads", [cell["name"]])]


def device_check(chips: int):
    """The chips this cell needs, or an error naming what was found."""
    import jax

    devices = jax.devices()
    platform = devices[0].platform
    if platform != "tpu":
        raise RuntimeError(f"this benchmark runs on a TPU; JAX found "
                           f"platform {platform!r} "
                           f"({devices[0].device_kind})")
    if len(devices) < chips:
        raise RuntimeError(f"the cell needs {chips} chips; JAX found "
                           f"{len(devices)}")
    return devices[:chips]


def enable_cache():
    """The program's compile cache (``$JAX_COMPILATION_CACHE_DIR``, else
    ``.jax_cache`` in the checkout), holding every program however short
    its compile, so that only a cell's first run compiles."""
    import jax

    from repro.launch.compile_cache import enable_compile_cache

    where = enable_compile_cache()
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", -1)
    return where


def result_line(bench, cell, config, mix, seed, seconds, trace,
                require_tpu=True, t0=T0, log=None):
    """Run the cell once and return the result dict (without printing)."""
    import jax

    from bench import harness as harness_lib
    from bench.metrics import reader

    log = log or (lambda msg: print(msg, file=sys.stderr, flush=True))
    devices = (device_check(cell["chips"]) if require_tpu
               else jax.devices()[:cell["chips"]])
    h = harness_lib.Harness(config, mix, cell["chips"], seed,
                            require_tpu=require_tpu, log=log)
    run, kept = h.window(seed, seconds, trace=bool(trace), t_setup0=t0)
    late = run.late_s
    log(f"generator: {len(late)} arrivals ingested, late by p50 "
        f"{1e3 * _q(late, 50):.3f} ms, p99 {1e3 * _q(late, 99):.3f} ms, "
        f"max {1e3 * float(late.max()):.3f} ms; backlog grew by "
        f"{run.backlog_growth():.1f} patches from the window's second quarter "
        f"to its last; {len(run.invocations)} invocations; "
        f"{run.compiles_in_window} compiles in the window")
    memory = [d.memory_stats() or {} for d in devices]
    peak_bytes = max(int(m.get("peak_bytes_in_use", 0)) for m in memory)
    device = {"platform": devices[0].platform,
              "kind": devices[0].device_kind, "count": len(devices),
              "memory_peak_bytes": peak_bytes}
    if trace:
        if run.trace is None:
            raise RuntimeError("the traced run took no trace")
        device["busy_s"] = run.trace["busy_s"]
        device["window_s"] = run.trace["window_s"]
        billed = lambda rs: (sum(r.t_done - r.t_start for r in rs)
                             / max(len(rs), 1))
        log(f"trace: {run.trace_bytes} bytes, kernels "
            f"{json.dumps(run.trace['kernel_s'])}, runs "
            f"{json.dumps(run.trace['kernel_runs'])}; mean billed seconds "
            f"of an invocation {billed([r for r in run.invocations if r.traced]):.4f} "
            f"with the profiler on, "
            f"{billed([r for r in run.invocations if not r.traced]):.4f} off")
    values = {}
    for m in metrics_of(bench, cell, bool(trace)):
        v = reader(m["name"])(run)
        if v is not None and math.isfinite(v):
            values[m["name"]] = {"value": v, "unit": m["unit"]}
    numbers = h.check(kept, run)
    correct, checks = judge(numbers, config)
    log(f"compared {numbers['compared_invocations']} invocations, "
        f"{numbers['compared_cells']} detector cells")
    failed = int(sum(1 for t in run.t_done if math.isnan(t)))
    out = {"correct": bool(correct), "attempted": int(len(run.t_gen)),
           "failed": failed, "metrics": values, "device": device}
    if trace:
        out["breakdown"] = run.trace["breakdown"]
    out["checks"] = checks
    return out


def judge(numbers: dict, config: dict) -> tuple:
    """``(correct, checks)``: every number compared with its limit, the
    gaps with the configuration's and the exact counts with 0."""
    from bench.harness import EXACT

    limits = dict(config["limits"], **{k: 0 for k in EXACT})
    checks = {name: {"value": numbers[name], "limit": limit}
              for name, limit in limits.items()}
    correct = numbers["compared_invocations"] > 0 and all(
        c["value"] <= c["limit"] for c in checks.values())
    return bool(correct), checks


def _q(x, q):
    import numpy as np

    return float(np.percentile(x, q)) if len(x) else float("nan")


def main(argv=None) -> int:
    args = parse(argv)
    bench = load_benchmark()
    cell = cell_of(bench, args.workload)
    from bench import harness, traffic

    config = harness.load_config(cell["config"])
    mix = traffic.load_mix(cell["traffic"])
    sys.path.insert(0, str(ROOT / "src"))
    try:
        device_check(cell["chips"])
    except RuntimeError as e:
        print(f"error: {e}", file=sys.stderr)
        return 2
    enable_cache()
    out = result_line(bench, cell, config, mix, args.seed, args.seconds,
                      args.trace)
    for name, c in out["checks"].items():
        print(f"check {name} {c['value']!r} limit {c['limit']!r}",
              file=sys.stderr)
    print(json.dumps(out), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
