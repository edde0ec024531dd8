"""One benchmark cell: set-up, the open-loop window, and the check.

``Harness`` holds what a cell's runs share in one process (the model,
the weights, the latency table, the frames); ``Harness.window`` drives
one window of arrivals at their wall times through ``ServingEngine.offer``
and drains it with ``finish()``; ``Harness.check`` compares what the
window's own invocations produced with the plain reference.

Every patch is timed from its ``t_gen`` to the wall instant its
completion is delivered, which the harness takes itself in the
executor's ``on_complete``: the program's ``PatchOutcome.latency`` adds
the measured wall time to the timer instant the invoker planned, so a
late host never shows in it.
"""
from __future__ import annotations

import collections
import dataclasses
import json
import math
import shutil
import sys
import tempfile
import time
from pathlib import Path
from typing import Dict, List, Optional

import numpy as np

from bench import reference, traffic, trunks
from bench.model import (build_serve_fn, check_registry, fused_kwargs,
                         latency_table, make_weights, serve_defaults)

CONFIG_DIR = Path(__file__).resolve().parent / "configs"

#: invocations whose outputs are compared with the reference, besides the
#: one with the most patches
N_CHECKED = 12
#: seconds of arrivals a ``--trace 1`` run traces, at the window's end;
#: the trace holds millions of host events a second (the runtime's layout
#: transposes of the slot copies), so a whole window does not fit in
#: the host's memory
TRACE_SECONDS = 3.0
TRACE_WALL_SECONDS = 8.0
HOST_TRACER_LEVEL = 2
#: numbers of the check that must read exactly 0
EXACT = ("placement_faults", "evidence_mismatch", "route_mismatch",
         "undelivered")


def load_config(name: str) -> dict:
    cfg = json.loads((CONFIG_DIR / f"{name}.json").read_text())
    for key in ("name", "model", "trunk", "source", "arch", "max_canvases",
                "executor", "routed_share",
                "latency_profile", "limits"):
        if key not in cfg:
            raise ValueError(f"configuration {name!r} lacks {key!r}")
    trunks.load(cfg["trunk"])
    return cfg


@dataclasses.dataclass
class InvRecord:
    """One invocation of the window, as the harness saw it."""
    ordinal: int
    canvases: int
    patches: int
    used_area: int
    canvas_area: int
    t_slack: float
    live_pixels: int
    t_start: float = math.nan       # wall (engine seconds) submit began
    t_done: float = math.nan        # wall instant its completion arrived
    submit_s: float = 0.0
    resolve_s: float = 0.0
    sync_s: float = 0.0
    traced: bool = False            # submitted while the profiler ran
    records: Optional[np.ndarray] = None  # the plan's (B, K, 6) placements


@dataclasses.dataclass
class Run:
    """What one window left behind, for the metric readers."""
    seconds: float
    arch: dict
    trunk: object                   # the configuration's bench.trunks module
    peak: dict
    setup_s: float
    t_gen: np.ndarray               # per offered patch
    deadline: np.ndarray
    t_done: np.ndarray              # nan where never delivered
    invocations: List[InvRecord]
    compiles_in_window: int
    late_s: np.ndarray              # per arrival, how late it was ingested
    backlog: list                   # (t, patches arrived and unfinished)
    t_trace: float = math.inf       # engine time the profiler started
    trace: Optional[dict] = None
    trace_bytes: int = 0
    #: the program's span recorder over the whole window (traced runs)
    telemetry: Optional[object] = None

    def backlog_growth(self) -> float:
        """Mean backlog over the window's last quarter less that over its
        second quarter (patches): about 0 when the rate is sustained."""
        b = np.array([v for _, v in self.backlog], float)
        return float(b[-4:].mean() - b[:4].mean())


class Harness:
    def __init__(self, config: dict, mix: dict, chips: int, seed: int,
                 require_tpu: bool = True, log=None):
        import jax

        self.log = log or (lambda msg: print(msg, file=sys.stderr,
                                             flush=True))
        self.config, self.mix = config, mix
        self.arch = dict(config["arch"])
        self.trunk = trunks.load(config["trunk"])
        self.m = self.arch["canvas"]
        devices = jax.devices()
        self.devices = devices[:chips]
        if require_tpu:
            from bench.peaks import peaks

            self.peak = peaks(devices[0].device_kind)
            check_registry(config)
        else:
            self.peak = None
        self.phases = {}
        self.defaults = serve_defaults()
        t = time.perf_counter()
        self.cfg, self.serve_fn, self.rules = build_serve_fn(config)
        self.phases["build"] = time.perf_counter() - t
        t = time.perf_counter()
        self.reseed(seed)
        self.phases["weights"] = time.perf_counter() - t
        self.threshold = None
        from repro.launch.mesh import make_serve_mesh

        self.mesh = make_serve_mesh(len(self.devices))
        t = time.perf_counter()
        self.table = latency_table(self.serve_fn, self.params, self.cfg,
                                   self.mesh, self.rules,
                                   config["latency_profile"])
        self.phases["latency_table"] = time.perf_counter() - t
        self.log("latency table: " + json.dumps(
            {k: [round(v[0], 5), round(v[1], 6)]
             for k, v in self.table.table.items()}))
        self.ring = None

    def reseed(self, seed: int) -> None:
        """Weights drawn from ``seed`` (the executors made after use them),
        and the fused path's fields where ``ServeConfig`` turns it on."""
        import jax

        self.params = self.fused = None
        self.params = make_weights(self.cfg, seed)
        jax.block_until_ready(self.params)
        self.fused = fused_kwargs(self.cfg, self.params, self.rules,
                                  self.defaults["fuse"])

    # ------------------------------------------------------------ pieces ----

    def make_executor(self, spans=None, telemetry=None):
        """The executor as ``serve.main`` makes it for this deployment."""
        from repro.core.engine import make_executor

        d = self.defaults
        ex = make_executor(
            self.config["executor"], serve_fn=self.serve_fn,
            params=self.params, canvas_m=self.m, canvas_n=self.m,
            use_pallas=d["use_pallas"], fuse=d["fuse"], mesh=self.mesh,
            rules=self.rules, max_inflight=d["max_inflight"],
            obj_threshold=self.threshold, telemetry=telemetry,
            **self.fused)
        if spans is not None:
            spans.hook_sync(ex)
        return ex

    def make_pool(self):
        from repro.core.config import make_classify
        from repro.core.engine import uniform_pool

        return uniform_pool(self.m, self.m, self.table,
                            max_canvases=self.config["max_canvases"],
                            classify=make_classify(None))

    def arrivals(self, seed: int, seconds: float, fps_scale: float = 1.0):
        """Program-side arrivals of one window, and its traffic."""
        from repro.core.partitioning import Patch
        from repro.data.video import Arrival

        tr = traffic.generate(self.mix, seed, seconds, self.m,
                              fps_scale=fps_scale)
        out = []
        for t_arr, p in tr.arrivals:
            q = Patch(p.x0, p.y0, p.x1, p.y1, frame_id=p.frame_id,
                      camera_id=p.camera_id, t_gen=p.t_gen, slo=p.slo)
            out.append(Arrival(t_arr, q, traffic.patch_bytes(p)))
        return tr, out

    def replay(self, arrivals) -> list:
        """The window's invocations, from a replay on a virtual clock.

        Batching depends only on the arrivals and the latency table
        (timers fire at their scheduled engine times, arrivals are
        batched at their arrival times), so this is the grouping the
        window will make: its shapes are the ones to warm."""
        from repro.core.engine import Completion, ExecHandle, ServingEngine

        class Record:
            def __init__(self):
                self.invs = []

            def submit(self, inv):
                self.invs.append(inv)
                comp = Completion(inv, inv.t_submit)
                return ExecHandle(inv, t_finish=inv.t_submit, completion=comp)

            def resolve(self, handle):
                return handle.completion

        rec = Record()
        engine = ServingEngine(self.make_pool(), rec)
        for a in arrivals:
            engine.offer(a)
        engine.finish()
        return rec.invs

    def frames_for(self, tr) -> None:
        if self.ring is None:
            self.ring = traffic.render_ring(tr, self.mix["ring_frames"])

    def register_frames(self, executor, arrivals) -> None:
        counts = collections.Counter(a.patch.frame_id for a in arrivals)
        for fid, n in counts.items():
            executor.add_frame(fid, traffic.frame_pixels(self.ring, fid), n)

    def route_threshold(self, inv) -> float:
        """The objectness a cell needs to be routed: the quantile that
        routes the configuration's ``routed_share`` of the cells of one
        planned invocation.  Random weights put the cells' objectness
        around a level that differs from seed to seed; a fixed threshold
        would route none of them on one seed and all on another."""
        import jax.numpy as jnp

        from repro.core.engine import shard_canvases

        plan = inv.batch_plan()
        frames = [traffic.frame_pixels(self.ring, p.frame_id)
                  for p in inv.patches]
        canvases = reference.stitch(frames, inv.patches, plan.records, self.m)
        x, _ = shard_canvases(jnp.asarray(canvases), self.mesh, self.rules)
        obj = np.asarray(self.serve_fn(self.params, x)[0], np.float64)
        return float(np.quantile(obj, 1.0 - self.config["routed_share"]))

    def warm(self, invs) -> int:
        """Run one invocation of every shape the window will use."""
        import jax

        seen = {}
        for inv in invs:
            plan = inv.batch_plan()
            key = (plan.num_canvases, plan.slots_per_canvas,
                   plan.slot_capacity, plan.hmax, plan.wmax)
            seen.setdefault(key, inv)
        ex = self.make_executor()
        refs = collections.Counter(p.frame_id for inv in seen.values()
                                   for p in inv.patches)
        for fid, n in refs.items():
            ex.add_frame(fid, traffic.frame_pixels(self.ring, fid), n)
        for inv in seen.values():
            ex.on_complete(ex.resolve(ex.submit(inv)))
        jax.effects_barrier()
        return len(seen)

    # ------------------------------------------------------------ window ----

    def window(self, seed: int, seconds: float, trace: bool = False,
               fps_scale: float = 1.0, t_setup0: Optional[float] = None,
               checked: bool = True) -> tuple:
        """Set up and drive one window.  Returns ``(run, kept)``, where
        ``kept`` holds what the check compares."""
        import jax

        from repro.core.clock import WallClock
        from repro.core.engine import ServingEngine
        from repro.core.telemetry import Telemetry

        t = time.perf_counter()
        tr, arrivals = self.arrivals(seed, seconds, fps_scale)
        if not arrivals:
            raise ValueError("the window offers no patches")
        self.frames_for(tr)
        self.phases["traffic_and_frames"] = time.perf_counter() - t
        t = time.perf_counter()
        plan_invs = self.replay(arrivals)
        self.threshold = self.route_threshold(plan_invs[0])
        n_shapes = self.warm(plan_invs)
        self.phases["replay_and_warm"] = time.perf_counter() - t
        self.log(f"window: {len(arrivals)} patches, {len(plan_invs)} "
                 f"invocations planned, {n_shapes} shapes warmed, routing "
                 f"objectness >= {self.threshold:.6f}; set-up phases (s): "
                 + json.dumps({k: round(v, 3)
                               for k, v in self.phases.items()}))
        sample = set()
        if checked:
            rng = np.random.default_rng([int(seed), 1])
            k = min(N_CHECKED, len(plan_invs))
            sample = set(rng.choice(len(plan_invs), size=k,
                                    replace=False).tolist())
            sample.add(int(np.argmax([len(i.patches) for i in plan_invs])))
        planned = [_key(i) for i in plan_invs]

        spans = Spans()
        # a traced run records the program's own spans over the whole
        # window, for the per-layer readers; the untraced runs that give
        # the end-to-end numbers keep the recorder off, as served
        tel = Telemetry(enabled=True) if trace else None
        executor = self.make_executor(spans, telemetry=tel)
        self.register_frames(executor, arrivals)
        pool = self.make_pool()
        kept = {}
        state = _WindowState(arrivals, spans, sample, planned, kept)
        state.hook(executor, pool)
        compiles = _CompileCounter()
        setup_s = (time.perf_counter() - t_setup0) if t_setup0 else math.nan
        # a traced run traces from the window's last TRACE_SECONDS of
        # arrivals for at most TRACE_WALL_SECONDS, and stops before the
        # drain: the profiler slows the host path several times over, and
        # a backlog drained under it would not fit in the host's memory
        t_trace = seconds - min(TRACE_SECONDS, 0.5 * seconds)
        trace_dir, t_started, t_traced = None, math.inf, math.inf
        # the window's engine time starts with its clock
        clock = WallClock(sleep_fn=spans.sleep)
        state.clock = clock
        engine = ServingEngine(pool, executor, clock=clock, telemetry=tel)
        compiles.start()
        for a in arrivals:
            if trace and trace_dir is None and a.t_arrive >= t_trace:
                t_started, t_traced = time.perf_counter(), clock.now()
                trace_dir = spans.start_trace()
            elif (spans.tracing
                  and time.perf_counter() - t_started >= TRACE_WALL_SECONDS):
                spans.stop_trace()
            engine.offer(a)
        if spans.tracing:
            spans.stop_trace()
        engine.finish()
        jax.effects_barrier()
        compiles.stop()

        run = Run(
            seconds=seconds, arch=self.arch, trunk=self.trunk,
            peak=self.peak,
            setup_s=setup_s,
            t_gen=np.array([a.patch.t_gen for a in arrivals]),
            deadline=np.array([a.patch.deadline for a in arrivals]),
            t_done=np.array([state.done.get(id(a.patch), math.nan)
                             for a in arrivals]),
            invocations=state.records, compiles_in_window=compiles.count,
            late_s=np.array(state.late), t_trace=t_traced,
            backlog=[(t, state.backlog_at(t)) for t in
                     np.linspace(0.25 * seconds, seconds, 16)],
            telemetry=tel)
        if trace_dir is not None:
            run.trace, run.trace_bytes = spans.reduce(trace_dir)
        if state.mismatched:
            self.log(f"warning: {state.mismatched} invocations grouped "
                     f"otherwise than the replay planned")
        return run, kept

    # ------------------------------------------------------------- check ----

    def check(self, kept: dict, run: Run, control=None) -> Dict[str, float]:
        """Compare the kept invocations with the plain reference (the
        configuration's trunk module's ``forward``).

        ``control`` (a callable (canvases, records) -> (obj, boxes)) puts
        another computation in the program's place: the check is then of
        it."""
        obj_gap = box_gap = 0.0
        obj_sq = box_sq = 0.0
        faults = evidence = routes = missing = 0
        cells = 0
        for ordinal in sorted(kept):
            k = kept[ordinal]
            if control is None and (k["out"] is None or "routed" not in k):
                missing += 1        # no trunk output, or never delivered
                continue
            inv, plan = k["inv"], k["inv"].batch_plan()
            patches = inv.patches
            frames = [traffic.frame_pixels(self.ring, p.frame_id)
                      for p in patches]
            faults += reference.placement_faults(plan.records, patches, self.m)
            canvases = reference.stitch(frames, patches, plan.records, self.m)
            raw = self.trunk.forward(self.params, canvases, plan.records,
                                     self.arch)
            obj_ref, box_ref = reference.decode(raw, self.m)
            if control is not None:
                obj_p, box_p = control(canvases, plan.records)
            else:
                obj_p, box_p = program_outputs(k, self.m)
            obj_p = np.asarray(obj_p, np.float64)
            box_p = np.asarray(box_p, np.float64)
            d_obj = np.abs(obj_p - obj_ref)
            cell = self.m / raw.shape[1]
            size = np.maximum(box_ref[..., 2] - box_ref[..., 0],
                              box_ref[..., 3] - box_ref[..., 1])
            d_box = np.abs(box_p - box_ref) / (cell + size)[..., None]
            obj_gap = max(obj_gap, float(np.max(d_obj)))
            box_gap = max(box_gap, float(np.max(d_box)))
            obj_sq += float(np.sum(d_obj ** 2))
            box_sq += float(np.mean(d_box ** 2, -1).sum())
            cells += obj_ref.size
            if control is not None:
                continue
            want = reference.route(plan.records, patches, obj_p, box_p,
                                   self.threshold)
            routes += _route_mismatches(want, k["routed"])
            evidence += _evidence_mismatches(k["evidence"], patches, frames)
        failed = int(np.sum(np.isnan(run.t_done))) if run is not None else 0
        failed += missing
        return {"obj_gap": obj_gap, "box_gap": box_gap,
                "obj_rms": math.sqrt(obj_sq / max(cells, 1)),
                "box_rms": math.sqrt(box_sq / max(cells, 1)),
                "placement_faults": faults, "evidence_mismatch": evidence,
                "route_mismatch": routes, "undelivered": failed,
                "compared_invocations": len(kept), "compared_cells": cells}


def program_outputs(kept_inv: dict, m: int) -> tuple:
    """``(obj, boxes)`` on the canvases' cells, from what the timed path's
    trunk produced for a kept invocation: the decoded outputs of
    ``serve_fn`` on the unfused path, or the raw head outputs of
    ``tokens_fn`` on the fused one, decoded by the reference (the fused
    path's own decode and gather are judged by its routing)."""
    kind, out = kept_inv["out"]
    if kind == "raw":
        return reference.decode(np.asarray(out, np.float64), m)
    return tuple(np.asarray(x, np.float64) for x in out)


def _key(inv) -> tuple:
    return tuple((p.frame_id, p.x0, p.y0) for p in inv.patches)


def _route_mismatches(want: dict, got: dict) -> int:
    """Detections routed otherwise than the reference routes the
    program's own trunk outputs (score within 1e-6, box within 1e-3 px)."""
    bad = 0
    for fid in set(want) | set(got):
        a = sorted(want.get(fid, []))
        b = sorted(got.get(fid, []))
        if len(a) != len(b):
            bad += abs(len(a) - len(b))
        for (sa, ba), (sb, bb) in zip(a, b):
            if abs(sa - sb) > 1e-6 or max(abs(x - y) for x, y in
                                          zip(ba, bb)) > 1e-3:
                bad += 1
    return bad


def _evidence_mismatches(per_frame: dict, patches, frames) -> int:
    """Patches whose unstitched pixels differ from the frame's own."""
    queues = {fid: list(v) for fid, v in per_frame.items()}
    bad = 0
    for p, frame in zip(patches, frames):
        q = queues.get(p.frame_id)
        if not q:
            bad += 1
            continue
        px = q.pop(0)
        want = frame[p.y0:p.y1, p.x0:p.x1]
        if px.shape != want.shape or not np.array_equal(px, want):
            bad += 1
    return bad


class _CompileCounter:
    """Executables built or fetched from the persistent cache while on."""

    EVENTS = ("/jax/core/compile/backend_compile_duration",
              "/jax/compilation_cache/cache_retrieval_time_sec")

    def __init__(self):
        import jax

        self.count = 0
        self.on = False
        jax.monitoring.register_event_duration_secs_listener(self._event)

    def _event(self, name, _secs, **_kw):
        if self.on and name in self.EVENTS:
            self.count += 1

    def start(self):
        self.on = True

    def stop(self):
        self.on = False


class Spans:
    """Host spans around the executor's public boundaries, timed on the
    host clock and written into the profiler's trace when it runs."""

    def __init__(self):
        self.tracing = False
        self.clock = None
        self.current: Optional[InvRecord] = None
        self._window = None
        self._dir = None

    def span(self, name: str):
        import jax

        return jax.profiler.TraceAnnotation(f"bench.{name}")

    def sleep(self, dt: float):
        with self.span("sleep"):
            time.sleep(dt)

    def hook_sync(self, executor):
        import jax

        def sync(x):
            t0 = time.perf_counter()
            with self.span("sync"):
                jax.block_until_ready(x)
            if self.current is not None:
                self.current.sync_s += time.perf_counter() - t0

        executor.sync = sync

    def start_trace(self) -> str:
        import jax

        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0
        opts.host_tracer_level = HOST_TRACER_LEVEL
        self._dir = tempfile.mkdtemp(prefix="bench-trace-")
        jax.profiler.start_trace(self._dir, profiler_options=opts)
        self._window = self.span("window")
        self._window.__enter__()
        self.tracing = True
        return self._dir

    def stop_trace(self):
        import jax

        self._window.__exit__(None, None, None)
        jax.profiler.stop_trace()
        self.tracing = False

    def reduce(self, trace_dir: str) -> tuple:
        from bench import trace as trace_lib

        try:
            files = sorted(Path(trace_dir).rglob("*.xplane.pb"))
            if not files:
                raise ValueError("the profiler wrote no trace")
            size = files[-1].stat().st_size
            red = trace_lib.reduce(trace_lib.extract(files[-1]),
                                   trace_lib.load_modules())
            return red, size
        finally:
            shutil.rmtree(trace_dir, ignore_errors=True)


class _WindowState:
    """Wraps the executor's boundaries for one window: spans, completion
    instants, and the outputs the check compares."""

    def __init__(self, arrivals, spans, sample, planned, kept):
        self.clock, self.spans = None, spans
        self.sample, self.planned, self.kept = sample, planned, kept
        self.done: Dict[int, float] = {}
        self.records: List[InvRecord] = []
        self.late: List[float] = []
        self.mismatched = 0
        self.due = np.array([a.t_arrive for a in arrivals])
        self.last_out = None

    def hook(self, executor, pool):
        spans = self.spans
        submit, resolve = executor.submit, executor.resolve
        on_complete = executor.on_complete
        on_patch = pool.on_patch

        # the trunk's outputs, on either device path: ``serve_fn`` gives
        # decoded (obj, boxes) on the unfused path, ``tokens_fn`` the raw
        # head outputs on the fused one (``DeviceExecutor._launch``)
        def keep(kind, fn):
            def call(p, x):
                out = fn(p, x)
                self.last_out = (kind, out)
                return out
            return call

        executor.serve_fn = keep("decoded", executor.serve_fn)
        if executor.tokens_fn is not None:
            executor.tokens_fn = keep("raw", executor.tokens_fn)

        def submit_w(inv):
            plan = inv.batch_plan()
            rec = InvRecord(
                ordinal=len(self.records), canvases=len(inv.canvases),
                patches=len(inv.patches),
                used_area=sum(c.used_area for c in inv.canvases),
                canvas_area=sum(c.m * c.n for c in inv.canvases),
                t_slack=inv.t_slack,
                live_pixels=sum(p.w * p.h for p in inv.patches),
                traced=spans.tracing, records=plan.records)
            self.records.append(rec)
            inv._bench = rec
            if (rec.ordinal < len(self.planned)
                    and _key(inv) != self.planned[rec.ordinal]):
                self.mismatched += 1
            spans.current = rec
            rec.t_start = self.clock.now()
            t0 = time.perf_counter()
            with spans.span("submit"):
                handle = submit(inv)
            rec.submit_s += time.perf_counter() - t0
            if rec.ordinal in self.sample:
                self.kept[rec.ordinal] = {"inv": inv, "out": self.last_out}
            self.last_out = None
            spans.current = None
            return handle

        def resolve_w(handle):
            rec = getattr(handle.invocation, "_bench", None)
            spans.current = rec
            t0 = time.perf_counter()
            with spans.span("resolve"):
                comp = resolve(handle)
            if rec is not None:
                rec.resolve_s += time.perf_counter() - t0
            spans.current = None
            return comp

        def on_complete_w(comp):
            t = self.clock.now()
            inv = comp.invocation
            rec = getattr(inv, "_bench", None)
            if rec is not None:
                rec.t_done = t
                k = self.kept.get(rec.ordinal)
                if k is not None:
                    k["routed"], k["evidence"] = comp.outputs
            for p in inv.patches:
                self.done[id(p)] = t
            on_complete(comp)

        def on_patch_w(t, patch):
            self.late.append(self.clock.now() - t)
            return on_patch(t, patch)

        executor.submit, executor.resolve = submit_w, resolve_w
        executor.on_complete = on_complete_w
        pool.on_patch = on_patch_w

    def backlog_at(self, t_end: float) -> int:
        """Patches due by ``t_end`` (arrived) that had not completed then."""
        due = int(np.sum(self.due <= t_end))
        done = sum(1 for v in self.done.values() if v <= t_end)
        return due - done
