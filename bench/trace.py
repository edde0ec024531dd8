"""Reduction of a profiler trace to device busy time, per-module device
time, and idle gaps named by what the host was doing.

``extract`` reads the ``.xplane.pb`` file the JAX profiler writes into
plain event lists; everything after it works on those lists, so the
reduction is checked on a small recorded fixture
(``tests/bench/fixtures/trace_small.json``).  Times are nanoseconds on
the trace's one clock.

Device planes are ``/device:TPU:<n>``; their ``XLA Modules`` line holds
one event per program run, named ``<module>(<fingerprint>)``, and their
``XLA Ops`` line one event per operation.  Host spans are the
``TraceAnnotation``s of the benchmark (``bench.*``) and of the serve
path's recorder (``tangram.*``, on in a traced run) on the main thread's
line of ``/host:CPU``.
Which module is which kernel comes from ``modules.json``.
"""
from __future__ import annotations

import bisect
import collections
import json
import re
from pathlib import Path
from typing import Dict, Iterable, List, Sequence, Tuple

MODULES_FILE = Path(__file__).resolve().parent / "modules.json"
#: host spans read from the trace: the benchmark's own and the serve
#: path's (``repro.core.telemetry``); the window span bounds the window
SPAN_PREFIX = ("bench.", "tangram.")
WINDOW_SPAN = "bench.window"


def load_modules() -> Dict[str, List[str]]:
    """{kernel: [module name, ...]}: which XLA modules each kernel is."""
    return json.loads(MODULES_FILE.read_text())


def extract(path) -> dict:
    """{"devices": {plane: {"modules": [...], "ops": [...]}}, "host": [...]}
    with each event as [name, start_ns, duration_ns]."""
    from jax.profiler import ProfileData

    data = ProfileData.from_file(str(path))
    out = {"devices": {}, "host": []}
    for plane in data.planes:
        if plane.name.startswith("/device:TPU:"):
            dev = out["devices"].setdefault(plane.name,
                                            {"modules": [], "ops": []})
            for line in plane.lines:
                key = {"XLA Modules": "modules", "XLA Ops": "ops"}.get(
                    line.name)
                if key:
                    dev[key].extend([e.name, e.start_ns, e.duration_ns]
                                    for e in line.events)
        elif plane.name == "/host:CPU":
            # the benchmark's spans are on the main thread's line, named
            # after the process (``python3``); worker threads' lines hold
            # millions of transfer events and are not read
            lines = list(plane.lines)
            main = [ln for ln in lines if ln.name.startswith("python")]
            for line in main or lines:
                out["host"].extend(
                    [e.name, e.start_ns, e.duration_ns] for e in line.events
                    if e.name.startswith(SPAN_PREFIX))
    return out


def module_name(event_name: str) -> str:
    """``jit_stitch_canvases(1023...)`` -> ``jit_stitch_canvases``."""
    return re.sub(r"\(\d+\)$", "", event_name)


def op_name(event_name: str) -> str:
    """``%add_add_fusion.2 = bf16[...] fusion(...)`` -> ``add_add_fusion``."""
    head = event_name.split(" = ", 1)[0].lstrip("%")
    return re.sub(r"\.\d+$", "", head)


def window(host: Sequence) -> Tuple[float, float]:
    spans = [(s, s + d) for n, s, d in host if n == WINDOW_SPAN]
    if not spans:
        raise ValueError("the trace holds no window span")
    return min(a for a, _ in spans), max(b for _, b in spans)


def clip(events: Iterable, lo: float, hi: float) -> List[Tuple[str, float, float]]:
    """Events cut to [lo, hi], as (name, start, end)."""
    out = []
    for name, s, d in events:
        a, b = max(s, lo), min(s + d, hi)
        if b > a:
            out.append((name, a, b))
    return out


def union(intervals: Iterable[Tuple[float, float]]) -> List[Tuple[float, float]]:
    merged: List[List[float]] = []
    for a, b in sorted(intervals):
        if merged and a <= merged[-1][1]:
            merged[-1][1] = max(merged[-1][1], b)
        else:
            merged.append([a, b])
    return [(a, b) for a, b in merged]


def self_times(events: Sequence[Tuple[str, float, float]]
               ) -> List[Tuple[str, float, float]]:
    """(name, start, own time) of nested (name, start, end) events: an
    operation's time less that of the operations inside it (a loop's
    body runs inside the loop)."""
    out: List[List] = []
    stack: List[Tuple[float, int]] = []        # (end, index into out)
    for name, s, e in sorted(events, key=lambda x: (x[1], -x[2])):
        while stack and stack[-1][0] <= s:
            stack.pop()
        if stack:
            out[stack[-1][1]][2] -= e - s
        out.append([name, s, e - s])
        stack.append((e, len(out) - 1))
    return [tuple(x) for x in out]


def module_at(modules: Sequence):
    """start_ns -> name of the module running then (``-`` if none)."""
    runs = sorted((s, s + d, module_name(n)) for n, s, d in modules)
    starts = [r[0] for r in runs]

    def owner(t: float) -> str:
        i = bisect.bisect_right(starts, t) - 1
        return runs[i][2] if i >= 0 and t < runs[i][1] else "-"

    return owner


def busy_ns(modules: Sequence, lo: float, hi: float) -> float:
    return sum(b - a for a, b in union((s, e) for _, s, e in
                                       clip(modules, lo, hi)))


def kernel_ns(modules: Sequence, table: Dict[str, List[str]], lo: float,
              hi: float) -> Dict[str, float]:
    """Device time of each kernel of ``table`` inside the window."""
    owner = {m: k for k, names in table.items() for m in names}
    out = {k: 0.0 for k in table}
    for name, s, e in clip(modules, lo, hi):
        k = owner.get(module_name(name))
        if k is not None:
            out[k] += e - s
    return out


def kernel_runs(modules: Sequence, table: Dict[str, List[str]], lo: float,
                hi: float) -> Dict[str, int]:
    """How many runs of each kernel's modules start inside the window."""
    owner = {m: k for k, names in table.items() for m in names}
    out = {k: 0 for k in table}
    for name, s, _d in modules:
        k = owner.get(module_name(name))
        if k is not None and lo <= s < hi:
            out[k] += 1
    return out


def idle_gaps(modules: Sequence, host: Sequence, lo: float, hi: float
              ) -> Dict[str, float]:
    """Nanoseconds of the window in which no module ran, split by the
    innermost host span open at each instant (``no span`` where none
    was)."""
    busy = union((s, e) for _, s, e in clip(modules, lo, hi))
    gaps, t = [], lo
    for a, b in busy:
        if a > t:
            gaps.append((t, a))
        t = max(t, b)
    if hi > t:
        gaps.append((t, hi))
    spans = [(s, s + d, n) for n, s, d in host if n != WINDOW_SPAN]
    out: Dict[str, float] = collections.defaultdict(float)
    for a, b in gaps:
        cuts = sorted({a, b} | {x for s, e, _ in spans for x in (s, e)
                                if a < x < b})
        for u, v in zip(cuts, cuts[1:]):
            open_ = [(s, n) for s, e, n in spans if s <= u and v <= e]
            out[max(open_)[1] if open_ else "no span"] += v - u
    return dict(out)


def reduce(trace: dict, table: Dict[str, List[str]]) -> dict:
    """Busy and kernel time averaged over the chips that ran, and the
    ``breakdown`` of the result line."""
    lo, hi = window(trace["host"])
    devices = [d for d in trace["devices"].values() if d["modules"]]
    if not devices:
        raise ValueError("the trace holds no device module events")
    n = len(devices)
    busy = sum(busy_ns(d["modules"], lo, hi) for d in devices) / n
    kernels = collections.Counter()
    runs = collections.Counter()
    ops = collections.Counter()
    gaps = collections.Counter()
    for d in devices:
        kernels.update(kernel_ns(d["modules"], table, lo, hi))
        runs.update(kernel_runs(d["modules"], table, lo, hi))
        owner = module_at(d["modules"])
        for name, start, dur in self_times(clip(d["ops"], lo, hi)):
            ops[f"{owner(start)}/{op_name(name)}"] += dur
        gaps.update(idle_gaps(d["modules"], trace["host"], lo, hi))
    return {
        "window_s": (hi - lo) / 1e9,
        "busy_s": busy / 1e9,
        "kernel_s": {k: v / n / 1e9 for k, v in kernels.items()},
        "kernel_runs": dict(runs),
        "breakdown": {
            "device_ops": [[k, v / n / 1e9] for k, v in ops.most_common(10)],
            "idle_gaps": [[k, v / n / 1e9] for k, v in gaps.most_common(10)],
        },
    }
