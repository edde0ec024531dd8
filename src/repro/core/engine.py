"""Unified event-driven serving engine: one control plane for the
simulated platform and the real jit'd detector.

The engine owns the event loop every serving scenario runs on.  *Engine
time* comes from a pluggable :mod:`~repro.core.clock` — a
:class:`~repro.core.clock.VirtualClock` (default) jumps between events so
simulation and replay run as fast as the host allows, while a
:class:`~repro.core.clock.WallClock` sleeps to each event so timers fire
at real wall times (live serving).  Three event kinds, always processed
in engine-time order:

* **arrivals** — bandwidth-shaped ``data.video.Arrival`` records fed via
  :meth:`ServingEngine.run` (a whole trace) or :meth:`ServingEngine.offer`
  (streaming);
* **invoker timers** — each batching policy exposes ``next_timer()``; the
  engine fires the policy *at the timer's scheduled time*, never
  deferring to the next arrival (a gap between arrivals that straddles
  ``t_remain`` no longer inflates ``t_submit``);
* **completions** — every dispatched invocation finishes some time after
  it was submitted.  ``t_finish`` is *not* known at dispatch: executors
  expose ``submit(inv) -> handle`` and the engine resolves the handle to
  a :class:`Completion` later — from the platform model (``SimExecutor``,
  finish time known as soon as the model is consulted), or by joining the
  device future (``AsyncDeviceExecutor``).  Completion delivery is where
  outcomes are recorded, executor bookkeeping (frame-store eviction) runs,
  and batcher feedback (``on_result``) fires — the feedback loop sees
  what actually happened, not what the model predicted at dispatch.

**Event ordering at timestamp ties** (pinned by regression test): when a
completion and a timer are scheduled at the same instant, the completion
is delivered first — feedback from finished work always lands before the
next batch is cut.  When two invokers in an :class:`InvokerPool` share a
timer instant, the first-registered class fires first (dict insertion
order, i.e. order of first arrival).  Async device completions carry no
scheduled time; they are delivered as soon as the device reports them
ready (harvested at every event-loop step), with finish times clamped
monotone per worker (each worker's serial queue finishes in submit
order; cross-worker streams interleave) and simultaneous readiness
tie-broken by ``(worker index, submit seq)``.

Scheduling policy and execution substrate are independent axes:

* a **batcher** turns arrivals into :class:`~repro.core.invoker.Invocation`
  batches.  :class:`~repro.core.invoker.SLOAwareInvoker` is the paper's
  Algorithm 2; :class:`InvokerPool` keys one invoker per SLO class (or any
  user classification) so tight-deadline patches never queue behind
  loose-deadline ones; ``core.adaptive.AdaptiveInvokerPool`` layers a
  completion-driven AIMD controller on top; the baselines in
  ``core.baselines`` are alternative batchers over the same loop.
* an **executor** runs a fired invocation: :class:`SimExecutor` submits to
  the serverless ``Platform`` model, :class:`DeviceExecutor` runs the real
  stitch -> (sharded) detect -> route pipeline synchronously,
  and :class:`AsyncDeviceExecutor` exploits JAX async dispatch — submit
  returns after the host-side stitch + jit dispatch, the device crunches
  in the background while the engine keeps ingesting arrivals and
  restitching, and the engine blocks only when the bounded in-flight
  queue is full or the trace is draining.  Invocation boundaries depend
  only on arrivals and the batcher, so the same trace produces identical
  patch->invocation groupings on all three.

Batcher protocol (duck-typed; ``SLOAwareInvoker`` already conforms):

    on_patch(t, patch) -> List[Invocation]   # may fire immediately
    poll(t)            -> Optional[Invocation]
    flush(t)           -> Optional[Invocation]  # engine loops until None
    next_timer()       -> float                 # inf when idle
    on_result(inv, t_finish)                    # optional feedback, called
                                                # at completion delivery

Executor protocol:

    submit(inv) -> ExecHandle       # dispatch; handle.t_finish set when
                                    # the finish time is already known
    resolve(handle) -> Completion   # join; blocks if work is in flight
    ready(handle) -> bool           # optional, async executors only
    max_inflight: int               # optional bound on unresolved handles
    on_complete(comp)               # optional, at completion delivery

Executors that only implement the legacy ``execute(inv) -> Completion``
are still accepted (the engine wraps them in a pre-resolved handle).
"""
from __future__ import annotations

import collections
import dataclasses
import heapq
import math
import time
from typing import Callable, Dict, List, Optional, Sequence

import numpy as np

from repro.core.clock import Clock, VirtualClock
from repro.core.framestore import FrameStore
from repro.core.invoker import Invocation, SLOAwareInvoker
from repro.core.partitioning import Patch
from repro.core.stitching import validate
from repro.core.telemetry import Telemetry
from repro.data.video import Arrival
from repro.serverless.platform import Platform


# ------------------------------------------------------------- outcomes ----

@dataclasses.dataclass
class PatchOutcome:
    patch: Patch
    t_arrive: float
    t_submit: float
    t_finish: float
    model: Optional[str] = None   # registry model that served the patch

    @property
    def latency(self) -> float:
        return self.t_finish - self.patch.t_gen

    @property
    def violated(self) -> bool:
        return self.t_finish > self.patch.deadline

    @property
    def wait(self) -> float:
        return self.t_submit - self.t_arrive


@dataclasses.dataclass
class Results:
    name: str
    outcomes: List[PatchOutcome]
    canvas_efficiencies: List[float]
    batch_sizes: List[int]
    patches_per_batch: List[int]
    bytes_sent: float
    total_cost: float
    invocations: int
    exec_seconds: float
    transmission_seconds: float
    mean_consolidation: float = 0.0   # patches per invocation (platform view)
    worker_stats: Optional[List[dict]] = None  # per-worker pool counters
                                      # (WorkerPoolExecutor.worker_stats())
    source_stats: Optional[dict] = None  # ingestion-side accounting
                                      # (repro.sources SourceStats.to_dict():
                                      # frames dropped/degraded under
                                      # backpressure, arrivals, bytes)
    model_stats: Optional[dict] = None  # per-model platform/cache counters
                                      # (Platform.model_stats() merged with
                                      # WorkerPoolExecutor.model_cache_stats())
    shard_stats: Optional[List[dict]] = None  # per-shard fleet rows
                                      # (ShardedEngine.shard_stats():
                                      # arrivals, utilization, violations,
                                      # backlog high water)

    @property
    def n_patches(self) -> int:
        return len(self.outcomes)

    @property
    def violation_rate(self) -> float:
        if not self.outcomes:
            return 0.0
        return sum(o.violated for o in self.outcomes) / len(self.outcomes)

    def class_violation_rate(self, classify: Callable[[Patch], object],
                             key: object) -> float:
        """Violation rate restricted to one SLO class (mixed-SLO studies)."""
        mine = [o for o in self.outcomes if classify(o.patch) == key]
        if not mine:
            return 0.0
        return sum(o.violated for o in mine) / len(mine)

    @property
    def mean_latency(self) -> float:
        if not self.outcomes:
            return 0.0
        return sum(o.latency for o in self.outcomes) / len(self.outcomes)

    @property
    def amortized_latency(self) -> float:
        """Total function execution time amortized per patch (Fig. 14)."""
        if not self.outcomes:
            return 0.0
        return self.exec_seconds / len(self.outcomes)

    def class_breakdown(self) -> dict:
        """Per-SLO-class outcome breakdown (keyed by the patch's SLO)."""
        by: Dict[object, List[PatchOutcome]] = {}
        for o in self.outcomes:
            by.setdefault(o.patch.slo, []).append(o)
        return {
            str(slo): {
                "patches": len(outs),
                "violations": sum(o.violated for o in outs),
                "violation_rate": round(
                    sum(o.violated for o in outs) / len(outs), 4),
                "mean_latency_s": round(
                    sum(o.latency for o in outs) / len(outs), 4),
            }
            for slo, outs in sorted(by.items(), key=lambda kv: str(kv[0]))
        }

    def model_breakdown(self) -> dict:
        """Per-model rows: outcome accounting (violations, latency) merged
        with the platform/cache counters in ``model_stats`` (batches,
        cold starts, weight loads, weight-cache hit rate) — the debugging
        surface for mixed-model runs."""
        by: Dict[str, List[PatchOutcome]] = {}
        for o in self.outcomes:
            if o.model is not None:
                by.setdefault(o.model, []).append(o)
        rows: Dict[str, dict] = {}
        for model, outs in sorted(by.items()):
            rows[model] = {
                "patches": len(outs),
                "violations": sum(o.violated for o in outs),
                "violation_rate": round(
                    sum(o.violated for o in outs) / len(outs), 4),
                "mean_latency_s": round(
                    sum(o.latency for o in outs) / len(outs), 4),
            }
        for model, st in sorted((self.model_stats or {}).items()):
            rows.setdefault(model, {}).update(st)
        return rows

    def summary(self) -> dict:
        out = {
            "name": self.name,
            "patches": self.n_patches,
            "violation_rate": round(self.violation_rate, 4),
            "mean_latency_s": round(self.mean_latency, 4),
            "cost_usd": round(self.total_cost, 6),
            "invocations": self.invocations,
            "bytes_mb": round(self.bytes_sent / 1e6, 3),
            "mean_canvas_eff": round(
                sum(self.canvas_efficiencies)
                / max(len(self.canvas_efficiencies), 1), 4),
            "amortized_latency_s": round(self.amortized_latency, 4),
            "mean_consolidation": round(self.mean_consolidation, 2),
            "class_violations": self.class_breakdown(),
        }
        models = self.model_breakdown()
        if models:
            out["models"] = models
        if self.worker_stats is not None:
            # horizon = span of delivered work; utilization is each
            # worker's busy time over it, so placement-policy skew shows
            # up directly in the benchmark JSON
            horizon = max((o.t_finish for o in self.outcomes), default=0.0)
            out["per_worker"] = [
                dict(ws, utilization=round(ws.get("busy_s", 0.0)
                                           / max(horizon, 1e-12), 4))
                for ws in self.worker_stats
            ]
        if self.source_stats is not None:
            out["source"] = self.source_stats
        if self.shard_stats is not None:
            out["per_shard"] = self.shard_stats
        return out


@dataclasses.dataclass
class Completion:
    """One finished invocation, delivered at ``t_finish`` engine time."""
    invocation: Invocation
    t_finish: float
    record: object = None     # platform ExecutionRecord (SimExecutor)
    outputs: object = None    # routed device outputs (DeviceExecutor)
    worker: int = 0           # pool worker that ran it (0 outside a pool)
    model: Optional[str] = None  # registry model that ran it (filled from
                              # the invocation at delivery when unset)


@dataclasses.dataclass
class ExecHandle:
    """An in-flight invocation, returned by ``Executor.submit``.

    ``t_finish`` is set when the executor already knows the finish time
    at submit (the platform model, or a sync device run) — the engine
    then schedules delivery on the event heap.  When ``None`` the work is
    genuinely in flight (async device futures) and the engine resolves
    the handle when it reports ready, the in-flight bound is hit, or the
    trace drains.

    ``worker`` is the pool worker index the invocation was placed on
    (:class:`~repro.core.workers.WorkerPoolExecutor`; 0 for single-device
    executors) and ``seq`` the engine's submit sequence number — together
    they are the pinned completion tie-break ``(worker, seq)`` that makes
    multi-worker delivery order reproducible when several handles report
    ready at the same harvest.
    """
    invocation: Invocation
    t_finish: Optional[float] = None
    completion: Optional[Completion] = None
    payload: object = None            # executor-private in-flight state
    worker: int = 0
    seq: int = -1
    model: Optional[str] = None       # invocation's model key (engine-set)
    load_s: float = 0.0               # weight-cache load cost still to be
                                      # added to t_finish at resolve (async
                                      # handles; 0 once applied)


# ----------------------------------------------------------- invoker pool ----

def slo_class(patch: Patch) -> float:
    """Default classification: one invoker per distinct SLO value."""
    return patch.slo


class InvokerPool:
    """Per-class SLO-aware invokers behind one batcher interface.

    ``classify`` maps a patch to its class key (default: its SLO value;
    pass e.g. ``lambda p: (p.slo, p.camera_id // 4)`` to also group
    cameras).  ``make_invoker(key)`` builds the class's invoker on first
    use, so each class can have its own canvas geometry and latency
    table.  Every fired ``Invocation`` is tagged with its class ``key``,
    and — when ``model_of`` is given — with the registry model name its
    class resolves to (``model_of(key)``), so executors, placement, and
    the platform model all see which network the batch runs.
    """

    def __init__(self, make_invoker: Callable[[object], SLOAwareInvoker],
                 classify: Callable[[Patch], object] = slo_class,
                 model_of: Optional[Callable[[object],
                                             Optional[str]]] = None):
        self.make_invoker = make_invoker
        self.classify = classify
        self.model_of = model_of
        self.invokers: Dict[object, SLOAwareInvoker] = {}

    def _invoker(self, key: object) -> SLOAwareInvoker:
        inv = self.invokers.get(key)
        if inv is None:
            inv = self.invokers[key] = self.make_invoker(key)
        return inv

    def _tag(self, fired, key):
        model = self.model_of(key) if self.model_of is not None else None
        for f in fired:
            f.key = key
            if f.model is None:
                f.model = model
        return fired

    def on_patch(self, t_now: float, patch: Patch) -> List[Invocation]:
        key = self.classify(patch)
        return self._tag(self._invoker(key).on_patch(t_now, patch), key)

    def queue_depth(self) -> int:
        """Patches currently queued (unfired) across every class — the
        pool half of the engine's ingestion-backpressure signal."""
        return sum(len(inv.queue) for inv in self.invokers.values())

    def next_timer(self) -> float:
        return min((inv.next_timer() for inv in self.invokers.values()),
                   default=math.inf)

    def poll(self, t_now: float) -> Optional[Invocation]:
        """Fire the due invoker with the earliest timer.

        Timer ties resolve to the *first-registered* class (dict
        insertion order = order of each class's first arrival) — pinned
        by a regression test so multi-class schedules are deterministic.
        """
        due = [(inv.next_timer(), key) for key, inv in self.invokers.items()
               if inv.next_timer() <= t_now]
        if not due:
            return None
        _, key = min(due, key=lambda x: x[0])
        fired = self.invokers[key].poll(t_now)
        if fired is not None:
            self._tag([fired], key)
        return fired

    def flush(self, t_now: float) -> Optional[Invocation]:
        for key, inv in self.invokers.items():
            fired = inv.flush(t_now)
            if fired is not None:
                self._tag([fired], key)
                return fired
        return None


def uniform_pool(canvas_m: int, canvas_n: int, latency, max_canvases: int = 8,
                 incremental: bool = True,
                 classify: Optional[Callable[[Patch], object]] = None,
                 model_of: Optional[Callable[[object],
                                             Optional[str]]] = None
                 ) -> InvokerPool:
    """Pool where every class shares one geometry/latency spec.

    ``classify=None`` gives the paper's single shared queue (every patch
    maps to one class); pass :func:`slo_class` for per-SLO pools.
    ``model_of`` tags fired invocations with their class's registry
    model name (see :class:`InvokerPool`).
    """
    return InvokerPool(
        lambda key: SLOAwareInvoker(canvas_m, canvas_n, latency,
                                    max_canvases, incremental=incremental),
        classify=classify or (lambda p: None), model_of=model_of)


# -------------------------------------------------------------- executors ----

class SimExecutor:
    """Executor over the discrete-event serverless ``Platform`` model.

    The model is consulted at submit, so the handle's finish time is
    known immediately and the engine schedules delivery on the event
    heap — the simulation analogue of "the device will interrupt us at
    t_finish".

    Multi-model serving: ``model_loads`` maps a registry model name to
    its weight-load seconds and ``model_tables`` to its latency table
    (both typically from :class:`~repro.core.models.ModelSpec`).  A
    model-tagged invocation is then submitted with its own execution
    profile and load cost, and the platform's per-model warm pools make
    an instance warm for model A cold for model B.  Untagged invocations
    (or an empty mapping) keep the historical single-model behaviour
    byte-for-byte.
    """

    def __init__(self, platform: Platform,
                 model_loads: Optional[Dict[str, float]] = None,
                 model_tables: Optional[Dict[str, object]] = None):
        self.platform = platform
        self.model_loads = model_loads or {}
        self.model_tables = model_tables or {}

    def submit(self, inv: Invocation) -> ExecHandle:
        size = (inv.cost_canvases if inv.cost_canvases is not None
                else len(inv.canvases))
        if inv.model is None:
            rec = self.platform.submit(inv.t_submit, size,
                                       n_patches=len(inv.patches))
        else:
            rec = self.platform.submit(
                inv.t_submit, size, n_patches=len(inv.patches),
                model=inv.model,
                model_load_s=self.model_loads.get(inv.model, 0.0),
                latency=self.model_tables.get(inv.model))
        comp = Completion(inv, rec.t_finish, record=rec, model=inv.model)
        return ExecHandle(inv, t_finish=rec.t_finish, completion=comp)

    def resolve(self, handle: ExecHandle) -> Completion:
        return handle.completion

    def execute(self, inv: Invocation) -> Completion:  # legacy shim
        return self.resolve(self.submit(inv))


def _leaf_ready(x) -> bool:
    """Duck-typed readiness: jax Arrays and future-likes expose
    ``is_ready()``; anything else (numpy, scalars) is ready by
    definition."""
    probe = getattr(x, "is_ready", None)
    if probe is None:
        return True
    try:
        return bool(probe())
    except TypeError:           # is_ready is a property on some types
        return bool(probe)


@dataclasses.dataclass
class ModelRuntime:
    """One servable model on the device path: the jit'd function, its
    params, and the canvas geometry / sharding it runs under.  The
    values of :class:`DeviceExecutor`'s ``models`` mapping (or zero-arg
    callables returning one, for lazy builds through the registry).

    The optional fused-path fields feed the fused device hot path
    (``kernels/stitch/fused_embed.py``): ``tokens_fn(params, tokens)``
    is the detector trunk minus the patch embed (``forward_tokens``),
    ``embed_kernel`` / ``embed_bias`` the full-precision patch-embed
    projection the fused stitch kernel applies in VMEM, and ``patch``
    the detector's patch size (fused token/grid geometry).  When they
    are absent a ``fuse=True`` executor falls back to the unfused
    pipeline for this model."""
    serve_fn: Callable
    params: object
    canvas_m: int
    canvas_n: int
    mesh: object = None
    rules: object = None
    tokens_fn: Optional[Callable] = None
    embed_kernel: object = None
    embed_bias: object = None
    patch: Optional[int] = None


class DeviceExecutor:
    """Executor over the real pipeline: stitch -> (data-parallel)
    detect -> per-frame routing and evidence, joined synchronously
    at submit (``t_finish`` = ``t_submit`` + measured wall execution, the
    same quantity the offline profiling table estimates, so SLO
    accounting stays consistent between simulation and device).

    The pipeline is split into :meth:`_launch` (host-side crop gather +
    stitch or slot packing + jit dispatch — *returns before the device
    finishes*, courtesy of JAX async dispatch) and :meth:`_finalize`
    (block on the device values, route detections, account).  This
    class joins the two back-to-back; :class:`AsyncDeviceExecutor` keeps
    them apart so device execution overlaps arrival ingestion.

    Owns the frame store: ``add_frame`` registers a frame's pixels with a
    reference count (how many patches were cut from it); the engine's
    completion event decrements the counts and evicts a frame once every
    patch cut from it has been routed, so long serving runs no longer
    leak every frame ever seen.

    ``sync`` joins dispatched device work (default
    ``jax.block_until_ready``); tests and benchmarks substitute a hook
    that also joins non-JAX future-likes.

    Which pixel buffer crosses to the device depends on the path.  The
    unfused path stitches the crops onto the (B, M, N, 3) float32 canvas
    batch on the host (``stitch_ops.stitch_plan_host``, into a buffer
    reused once the invocation that last used it is joined) and sends
    that batch, padded to the mesh's data axis; the trunk reads it on
    the device, and only its ``obj`` and ``boxes`` come back.  Each
    patch's evidence pixels are cut from the host batch
    (``stitch_ops.unstitch_plan_host``) before the buffer is reused.  The
    fused path (``fuse`` on a runtime with the fused fields) sends the
    pow2-padded patch slots (``stitch_ops.pack_plan_host``), which its
    stitch->embed kernel assembles into canvases in VMEM, and serves the
    evidence from those slots.  Both send the plan's records.

    ``obj_threshold`` is the objectness a grid cell needs to be routed
    as a detection.  ``fuse`` runs the fused stitch->embed and
    decode->gather kernels; ``stitch_ops.pallas_impl`` compiles them on
    TPU and interprets them on CPU.  ``use_pallas`` is accepted and no
    longer changes the served path: the unfused path runs no stitch
    kernel on the device.

    ``telemetry`` (a :class:`~repro.core.telemetry.Telemetry`, off by
    default) records the launch and finalize spans and their parts.  The
    counters are always on: ``n_host_stitched`` and ``n_fused`` (the
    invocations that took each path), ``bytes_to_device`` (the pixel
    buffer and the records), ``bytes_from_device`` (every array
    fetched), ``slot_pixels`` (the pixel buffer's pixels: rows x M x N
    of the canvas batch, or slot capacity x H x W of the slots) and
    ``live_pixels`` (the patches' own h x w).

    Multi-model serving: ``models`` maps a registry model name to a
    :class:`ModelRuntime` — or to a zero-arg callable returning one,
    resolved and cached on first use so unused models are never built.
    A model-tagged invocation runs its own jit'd function, params, and
    canvas geometry; untagged invocations (and tags missing from the
    mapping) run the default runtime built from the positional ctor
    arguments, which keeps every single-model call site unchanged.
    """

    def __init__(self, serve_fn, params, canvas_m: int, canvas_n: int, *,
                 use_pallas: bool = False, fuse: bool = False,
                 mesh=None, rules=None,
                 clock: Callable[[], float] = time.perf_counter,
                 sync: Optional[Callable[[object], None]] = None,
                 models: Optional[Dict[str, object]] = None,
                 tokens_fn: Optional[Callable] = None,
                 embed_kernel=None, embed_bias=None,
                 patch: Optional[int] = None,
                 obj_threshold: float = 0.5,
                 telemetry: Optional[Telemetry] = None):
        self.serve_fn = serve_fn
        self.params = params
        self.m, self.n = canvas_m, canvas_n
        self.fuse = fuse
        self.mesh = mesh
        self.rules = rules
        self.clock = clock
        self.sync = sync
        self.models = dict(models) if models else {}
        self.tokens_fn = tokens_fn
        self.embed_kernel = embed_kernel
        self.embed_bias = embed_bias
        self.patch = patch
        self.obj_threshold = obj_threshold
        self.telemetry = telemetry if telemetry is not None else Telemetry()
        self._runtimes: Dict[Optional[str], ModelRuntime] = {}
        # host canvas batches, by shape, whose last invocation is joined
        self._free_canvases: Dict[tuple, List[np.ndarray]] = {}
        self.store = FrameStore()
        self.n_invocations = 0
        self.n_fused = 0
        self.n_host_stitched = 0
        self.n_detections = 0
        self.n_sharded = 0
        self.evidence_bytes = 0
        self.bytes_to_device = 0
        self.bytes_from_device = 0
        self.slot_pixels = 0
        self.live_pixels = 0

    def _runtime(self, model: Optional[str]) -> ModelRuntime:
        """Resolve an invocation's model tag to its runtime (default
        runtime for ``None`` or unmapped tags); lazy entries are built
        once and cached."""
        rt = self._runtimes.get(model)
        if rt is not None:
            return rt
        entry = self.models.get(model) if model is not None else None
        if entry is None:
            rt = ModelRuntime(self.serve_fn, self.params, self.m, self.n,
                              mesh=self.mesh, rules=self.rules,
                              tokens_fn=self.tokens_fn,
                              embed_kernel=self.embed_kernel,
                              embed_bias=self.embed_bias, patch=self.patch)
        elif callable(entry):
            rt = entry()
        else:
            rt = entry
        self._runtimes[model] = rt
        return rt

    # ------------------------------------------------------- frame store ----
    # The store itself is the striped-lock FrameStore (concurrency-safe:
    # shard threads of the parallel fleet runtime share it); ``frames`` /
    # ``_refs`` stay available as point-in-time dict views so tests and
    # diagnostics that predate the store keep reading the same shapes.

    def add_frame(self, frame_id, pixels: np.ndarray, n_patches: int):
        """Register a frame the edge cut ``n_patches`` patches from.

        Frames that produced no patches are never referenced again and
        are not stored at all.
        """
        self.store.add(frame_id, pixels, n_patches)

    def on_complete(self, comp: Completion):
        """Completion event: release every routed patch's frame ref."""
        release = self.store.release
        for p in comp.invocation.patches:
            release(p.frame_id)

    @property
    def frames(self) -> Dict[object, np.ndarray]:
        return self.store.snapshot()

    @property
    def _refs(self) -> Dict[object, int]:
        return self.store.refs_snapshot()

    # --------------------------------------------------------- execution ----

    def _fuses(self, rt: ModelRuntime) -> bool:
        """Whether ``rt``'s invocations take the fused hot path: a
        ``fuse=True`` executor on a runtime that has the fused fields."""
        return (self.fuse and rt.tokens_fn is not None
                and rt.embed_kernel is not None and rt.patch is not None)

    def _launch(self, inv: Invocation) -> dict:
        """Host-side stitch + jit dispatch.  Everything here returns as
        soon as the work is *enqueued* on the device (JAX async
        dispatch); nothing blocks on device values.

        The pixels cross to the device as the fused path's padded slots,
        or, on the unfused path, as the canvas batch stitched here on
        the host (put straight onto the mesh's data sharding, if any)."""
        # imported here so the pure-simulation control plane never touches
        # the kernel/jit stack
        import jax.numpy as jnp

        from repro.kernels.stitch import ops as stitch_ops

        tel = self.telemetry
        t0 = self.clock()
        with tel.span("tangram.executor.launch") as launch:
            rt = self._runtime(inv.model)
            plan = inv.batch_plan()
            fused = self._fuses(rt)
            with tel.span("tangram.executor.gather"):
                crops = []
                store = self.store
                for patch in inv.patches:
                    frame = store.get(patch.frame_id)
                    if frame is None:
                        crops.append(np.zeros((patch.h, patch.w, 3),
                                              np.float32))
                    else:
                        crops.append(
                            frame[patch.y0:patch.y1, patch.x0:patch.x1])
            with tel.span("tangram.executor.pack",
                          layout="slots" if fused else "canvas"):
                if fused:
                    pixels = stitch_ops.pack_plan_host(crops, plan)
                else:
                    pixels = stitch_ops.stitch_plan_host(
                        crops, plan, out=self._canvas_buffer(plan, rt))
            with tel.span("tangram.executor.put"):
                if not fused and rt.mesh is not None:
                    pixels_d, sharded = shard_canvases(pixels, rt.mesh,
                                                       rt.rules)
                    self.n_sharded += bool(sharded)
                else:
                    pixels_d = jnp.asarray(pixels)
                records = jnp.asarray(plan.records)
            sent = pixels_d.nbytes + records.nbytes
            slot_px = pixels.shape[0] * pixels.shape[1] * pixels.shape[2]
            live_px = sum(p.h * p.w for p in inv.patches)
            self.bytes_to_device += sent
            self.slot_pixels += slot_px
            self.live_pixels += live_px
            launch.set(bytes_to_device=sent, slot_pixels=slot_px,
                       live_pixels=live_px)
            with tel.span("tangram.executor.enqueue"):
                out = self._enqueue(rt, plan, pixels_d, records, fused)
        out.update(plan=plan, t0=t0, inv=launch.inv)
        out["slots" if fused else "canvases"] = pixels
        return out

    def _canvas_buffer(self, plan, rt: ModelRuntime) -> np.ndarray:
        """A host canvas batch for ``plan``, its rows padded to the mesh's
        data axis: one a joined invocation left, or a new one.  Reuse
        matters: every first write to a new buffer's pages faults."""
        rows = plan.num_canvases
        if rt.mesh is not None:
            from repro.compat import shardingx

            rows += -rows % shardingx.mesh_axis_sizes(rt.mesh).get("data", 1)
        shape = (rows, plan.canvas_m, plan.canvas_n, 3)
        try:
            return self._free_canvases.get(shape, []).pop()
        except IndexError:
            return np.zeros(shape, np.float32)

    def _enqueue(self, rt: ModelRuntime, plan, pixels, records,
                 fused: bool) -> dict:
        """The jit calls of one invocation, on device-resident pixels
        (slots when ``fused``, else the canvas batch) and records: the
        device values ``_finalize`` joins."""
        from repro.kernels.stitch import ops as stitch_ops

        if fused:
            # fused hot path: stitch->patch-embed emits the token batch
            # directly (no canvas batch in HBM), the trunk runs from
            # tokens, and decode+gather lands straight in per-patch slot
            # grids — no host round-trip through canvas-space outputs.
            # The canvas batch never exists, so mesh sharding (which
            # pads canvases, not records) does not apply here.  The
            # fused path exists only as Pallas kernels.
            impl = stitch_ops.pallas_impl()
            tokens = stitch_ops.stitch_embed(
                pixels, records, rt.embed_kernel, rt.embed_bias,
                rt.canvas_m, rt.canvas_n, rt.patch, impl=impl)
            raw = rt.tokens_fn(rt.params, tokens)
            out = stitch_ops.unstitch_decode(
                raw, records, rt.patch, plan.slot_capacity, impl=impl)
            self.n_invocations += 1
            self.n_fused += 1
            return {"fused": out}
        # the evidence (each patch's pixels) is cut from the host canvas
        # batch at finalize, so only the detector's outputs come back
        obj, boxes = rt.serve_fn(rt.params, pixels)
        self.n_invocations += 1
        self.n_host_stitched += 1
        return {"obj": obj, "boxes": boxes}

    def _finalize(self, inv: Invocation, payload: dict) -> Completion:
        """Join the device values and do the host-side routing."""
        import jax

        from repro.kernels.stitch import ops as stitch_ops

        tel = self.telemetry
        sync = self.sync or jax.block_until_ready
        plan = payload["plan"]
        fused = "fused" in payload
        with tel.span("tangram.executor.finalize",
                      inv=payload["inv"]) as finalize:
            with tel.span("tangram.executor.sync"):
                sync(payload["fused"] if fused
                     else (payload["obj"], payload["boxes"]))
            with tel.span("tangram.executor.fetch"):
                if fused:
                    host = [np.asarray(payload["fused"])]
                else:
                    host = [np.asarray(payload[k]) for k in ("obj", "boxes")]
            got = sum(a.nbytes for a in host)
            self.bytes_from_device += got
            finalize.set(bytes_from_device=got)
            with tel.span("tangram.executor.route"):
                # the evidence is each patch's pixels, grouped by frame
                # alongside its detections: the box head has no
                # pixel-space output, so the input crops stand in for a
                # per-pixel head (e.g. segmentation).  Each path serves
                # them from the pixel buffer it holds on the host.
                if fused:
                    per_frame = stitch_ops.route_fused(
                        plan, inv.patches, host[0],
                        obj_threshold=self.obj_threshold)
                    slots = payload["slots"]
                    # copy: a view would pin the whole pow2-padded slots
                    evidence = [np.ascontiguousarray(slots[i, :p.h, :p.w])
                                for i, p in enumerate(inv.patches)]
                else:
                    per_frame = stitch_ops.route_detections(
                        plan, inv.patches, host[0], host[1],
                        obj_threshold=self.obj_threshold)
                    buf = payload["canvases"]
                    evidence = stitch_ops.unstitch_plan_host(
                        buf, plan, inv.patches)
                    # the evidence is copied out and the device has
                    # joined, so nothing reads the canvas batch any more
                    self._free_canvases.setdefault(buf.shape, []).append(buf)
                per_frame_pixels: Dict[object, List[np.ndarray]] = {}
                for patch, px in zip(inv.patches, evidence):
                    per_frame_pixels.setdefault(patch.frame_id, []).append(px)
        wall = self.clock() - payload["t0"]

        self.n_detections += sum(len(v) for v in per_frame.values())
        self.evidence_bytes += sum(
            a.nbytes for v in per_frame_pixels.values() for a in v)
        return Completion(inv, inv.t_submit + wall,
                          outputs=(per_frame, per_frame_pixels),
                          model=inv.model)

    def submit(self, inv: Invocation) -> ExecHandle:
        comp = self._finalize(inv, self._launch(inv))
        return ExecHandle(inv, t_finish=comp.t_finish, completion=comp)

    def resolve(self, handle: ExecHandle) -> Completion:
        if handle.completion is None:
            handle.completion = self._finalize(handle.invocation,
                                               handle.payload)
            handle.payload = None
        return handle.completion

    def execute(self, inv: Invocation) -> Completion:  # legacy shim
        return self.resolve(self.submit(inv))


class AsyncDeviceExecutor(DeviceExecutor):
    """Overlapped device execution: submit returns after the host-side
    stitch + jit *dispatch*, so the engine keeps ingesting arrivals and
    restitching while the device works through its queue.

    ``max_inflight`` bounds the number of unresolved handles the engine
    may hold (device memory for canvases + outputs is pinned per handle);
    when the bound is hit the engine retires an already-ready handle if
    there is one and otherwise blocks on the oldest.  A single device
    queue executes in order, so this executor's dispatches finish
    oldest-first and the engine's per-worker monotone clamp only smooths
    timer jitter; across a worker pool completions harvest out of order
    between workers.
    """

    def __init__(self, *args, max_inflight: int = 4, **kwargs):
        super().__init__(*args, **kwargs)
        if max_inflight < 1:
            raise ValueError(f"max_inflight must be >= 1, got {max_inflight}")
        self.max_inflight = max_inflight

    def submit(self, inv: Invocation) -> ExecHandle:
        return ExecHandle(inv, t_finish=None, payload=self._launch(inv))

    def ready(self, handle: ExecHandle) -> bool:
        if handle.completion is not None:
            return True
        p = handle.payload
        if "fused" in p:
            return _leaf_ready(p["fused"])
        return _leaf_ready(p["obj"]) and _leaf_ready(p["boxes"])


def shard_canvases(canvases, mesh, rules):
    """Lay the canvas batch out data-parallel over the serve mesh.

    The batch is padded to a multiple of the "data"-axis size (records
    never reference pad rows, so the detector output for them is simply
    ignored), then device_put with the batch axis split over "data".
    Pow2-style padding also stabilises jit static shapes: every batch
    compiles to a multiple of the axis size.  A host (numpy) batch that
    already has the pad rows (``DeviceExecutor`` stitches them in)
    crosses to the device once, straight onto the sharding.  Returns the
    sharded batch and whether the data axis actually split it (False on
    1 device).
    """
    import jax
    import jax.numpy as jnp

    from repro.compat import shardingx
    from repro.sharding import divisible_sharding

    n_data = shardingx.mesh_axis_sizes(mesh).get("data", 1)
    pad = (-canvases.shape[0]) % n_data
    if pad:
        canvases = jnp.concatenate(
            [canvases,
             jnp.zeros((pad,) + canvases.shape[1:], canvases.dtype)])
    sh = divisible_sharding(mesh, canvases.shape,
                            ("batch", None, None, None), rules)
    return jax.device_put(canvases, sh), bool(sh.spec) and n_data > 1


_EXECUTORS = {
    "sim": SimExecutor,
    "device": DeviceExecutor,
    "async_device": AsyncDeviceExecutor,
}


def make_executor(name: str, **cfg):
    """Executor-name -> instance (``sim`` | ``device`` | ``async_device``),
    mirroring ``make_placement`` / ``make_clock`` / ``make_source``.

    ``cfg`` forwards to the executor constructor: ``sim`` takes
    ``platform=`` (plus ``model_loads=`` / ``model_tables=``); the
    device executors take the pipeline arguments (``serve_fn, params,
    canvas_m, canvas_n, ...``, plus ``models=``).  ``max_inflight`` and
    the other-substrate model kwargs are accepted—and dropped—where they
    do not apply, so one config dict can drive any name.
    """
    from repro.core.registry import lookup

    cls = lookup("executor", _EXECUTORS, name)
    device_only = {"fuse", "tokens_fn", "embed_kernel", "embed_bias",
                   "patch", "obj_threshold", "telemetry"}
    if cls is SimExecutor:
        drop = {"max_inflight", "models"} | device_only
    elif cls is AsyncDeviceExecutor:
        drop = {"model_loads", "model_tables"}
    else:
        drop = {"max_inflight", "model_loads", "model_tables"}
    return cls(**{k: v for k, v in cfg.items() if k not in drop})


# ------------------------------------------------------------ event loop ----

class ServingEngine:
    """The one event loop.  Feed arrivals; timers and completions fire at
    their scheduled engine times; fired invocations run on the executor.

    ``clock`` defaults to a fresh :class:`VirtualClock` (simulation /
    replay).  Pass a :class:`~repro.core.clock.WallClock` for live
    serving: the engine then sleeps to each event instant instead of
    jumping, and in-flight async device work completes during those
    waits.

    ``ingestion_window`` bounds the backlog the engine is willing to
    accumulate, in patches: queued-but-unfired patches in the pool plus
    patches inside unresolved invocations.  The engine never refuses an
    offer — the bound is advisory, read by live sources
    (:mod:`repro.sources`) through :meth:`overloaded`, which respond by
    dropping frames or degrading RoI quality.  ``None`` (default)
    disables the signal: trace replay ingests everything, as before.

    ``telemetry`` (a :class:`~repro.core.telemetry.Telemetry`, off by
    default) records a ``tangram.engine.dispatch`` span around each
    call into the executor, with the instant the invoker fired at
    (``t_fire``), the engine time the call began (``t_launch``) and the
    patches' arrival instants.
    """

    def __init__(self, pool, executor, clock: Optional[Clock] = None,
                 check_invariants: bool = False,
                 ingestion_window: Optional[int] = None,
                 telemetry: Optional[Telemetry] = None):
        if ingestion_window is not None and ingestion_window < 1:
            raise ValueError(f"ingestion_window must be >= 1, got "
                             f"{ingestion_window}")
        self.pool = pool
        self.executor = executor
        self.clock = clock if clock is not None else VirtualClock()
        self.check_invariants = check_invariants
        self.ingestion_window = ingestion_window
        self.telemetry = telemetry if telemetry is not None else Telemetry()
        self.backlog_high_water = 0
        self.outcomes: List[PatchOutcome] = []
        self.invocations: List[Invocation] = []
        self.completions: List[Completion] = []
        # arrival bookkeeping lives in reused slots: _slot_patch holds the
        # strong patch ref (so an id() cannot be recycled while its entry
        # is live) and _slot_t the arrival time; delivered outcomes clear
        # the slot onto the free list for the next arrival.  The table
        # therefore stays sized to the *peak backlog*, not the trace
        # length, and ingestion does one list write per arrival instead
        # of growing two dicts.
        self._slot_patch: List[Optional[Patch]] = []
        self._slot_t: List[float] = []
        self._free_slots: List[int] = []
        self._slot_of: Dict[int, int] = {}    # id(patch) -> live slot
        self.arrivals_total = 0
        # incremental backlog counters: every offered patch increments
        # _queued, firing moves its count to _inflight_count, delivery
        # retires it — so backlog() is O(1) per read instead of walking
        # the pool queues plus every unresolved invocation on *each*
        # arrival (the per-event cost that capped fleet-scale ingestion)
        self._queued = 0
        self._inflight_count = 0
        # ready() is resolved once: the per-event getattr on the hot
        # path was measurable at fleet arrival rates
        self._ready_probe = getattr(executor, "ready", None)
        self._scheduled: List = []   # heap of (t_finish, seq, ExecHandle)
        self._inflight: collections.deque = collections.deque()
        self._event_seq = 0
        self._last_async_finish: Dict[int, float] = {}   # per worker
        self.inflight_high_water = 0

    @property
    def now(self) -> float:
        """Engine time of the last event processed."""
        return self.clock.now()

    # ----------------------------------------------------------- feeding ----

    def run(self, arrivals: Sequence[Arrival]) -> List[PatchOutcome]:
        """Drive a whole (sorted-by-``t_arrive``) arrival trace to empty."""
        self.offer_batch(arrivals)
        self.finish()
        return self.outcomes

    def serve(self, source) -> List[PatchOutcome]:
        """Pull loop over a :mod:`repro.sources` source.

        The source's event iterator receives *this engine* as its
        feedback handle: between frames it reads :meth:`overloaded` /
        :meth:`backlog` and throttles itself (drop / degrade).  With a
        trace source (backpressure ignored) this is event-for-event
        identical to :meth:`run` on the same arrivals — pinned by the
        boundary-identity test.
        """
        for arr in source.events(self):
            self.offer(arr)
        self.finish()
        return self.outcomes

    def offer(self, arrival: Arrival):
        """One arrival: first fire everything due strictly before it."""
        self.advance(arrival.t_arrive)
        self.clock.advance_to(arrival.t_arrive)
        self._ingest(arrival)

    def offer_batch(self, arrivals: Sequence[Arrival]):
        """Ingest a run of arrivals (sorted by ``t_arrive``) in one call.

        Semantically identical to :meth:`offer` in a loop — pinned by a
        regression test — but skips the per-arrival event probe
        (completion harvest + timer scan + heap peek) whenever no timer
        or scheduled completion is due before the arrival, which is the
        common case inside a fleet shard.  Arrivals fall back to the
        full :meth:`offer` path while async work is in flight, where the
        per-event harvest is load-bearing.
        """
        for arr in arrivals:
            if self._ready_probe is not None and self._inflight:
                self.offer(arr)
                continue
            t = arr.t_arrive
            if self._next_event() < t:
                self.advance(t)
            self.clock.advance_to(t)
            self._ingest(arr)

    def _ingest(self, arrival: Arrival):
        """Arrival bookkeeping + batcher feed (clock already advanced)."""
        patch = arrival.patch
        if self._free_slots:
            slot = self._free_slots.pop()
            self._slot_patch[slot] = patch
            self._slot_t[slot] = arrival.t_arrive
        else:
            slot = len(self._slot_patch)
            self._slot_patch.append(patch)
            self._slot_t.append(arrival.t_arrive)
        self._slot_of[id(patch)] = slot
        self.arrivals_total += 1
        self._queued += 1
        for inv in self.pool.on_patch(arrival.t_arrive, patch):
            self._dispatch(inv)
        backlog = self._queued + self._inflight_count
        if backlog > self.backlog_high_water:
            self.backlog_high_water = backlog
        if self.check_invariants:
            depth = getattr(self.pool, "queue_depth", None)
            if depth is not None:
                assert self._queued == depth(), (self._queued, depth())

    def _next_event(self) -> float:
        """Engine time of the next due timer or scheduled completion."""
        t = self.pool.next_timer()
        if self._scheduled:
            t_comp = self._scheduled[0][0]
            if t_comp < t:
                return t_comp
        return t

    # ------------------------------------------------- ingestion window ----

    def queued_patches(self) -> int:
        """Patches accepted but not yet fired (pool queues)."""
        return self._queued

    def inflight_patches(self) -> int:
        """Patches inside unresolved invocations (scheduled + in flight)."""
        return self._inflight_count

    def backlog(self) -> int:
        """Total unfinished patches — the backpressure quantity live
        sources compare against ``ingestion_window``.  O(1): maintained
        incrementally at offer / dispatch / delivery.  The counters
        assume the batcher contract that every offered patch eventually
        leaves through a fired invocation (true of every in-repo
        batcher); ``check_invariants`` cross-checks against the pool's
        authoritative queue depth on each arrival."""
        return self._queued + self._inflight_count

    def overloaded(self) -> bool:
        """True when the backlog has filled the ingestion window."""
        return (self.ingestion_window is not None
                and self.backlog() >= self.ingestion_window)

    def advance(self, t: float):
        """Process every timer/completion event scheduled before ``t``.

        Tie rule (regression-pinned): a completion and a timer at the
        same instant deliver the completion first.
        """
        while True:
            self._harvest_ready()
            t_timer = self.pool.next_timer()
            t_comp = self._scheduled[0][0] if self._scheduled else math.inf
            t_next = min(t_timer, t_comp)
            if t_next >= t:
                return
            self.clock.advance_to(t_next)
            if t_comp <= t_timer:
                self._deliver_scheduled()
            else:
                fired = self.pool.poll(t_timer)
                if fired is None:       # defensive: a policy may decline
                    return
                self._dispatch(fired)

    def finish(self, t_end: Optional[float] = None):
        """Drain timers at their scheduled times, flush stragglers, and
        deliver every remaining completion."""
        self.advance(math.inf)
        t = self.now if t_end is None else t_end
        while True:
            fired = self.pool.flush(t)
            if fired is None:
                break
            self._dispatch(fired)
        while self._inflight:
            self._resolve_one()
        while self._scheduled:
            self.clock.advance_to(self._scheduled[0][0])
            self._deliver_scheduled()

    # --------------------------------------------------------- internals ----

    def _dispatch(self, inv: Invocation):
        # canvas-less invocations are legitimate only for batchers that
        # bill via cost_canvases (the padded-tile baselines); a canvas-
        # packing batcher emitting patches without canvases is a bug
        if self.check_invariants and inv.cost_canvases is None:
            validate(inv.canvases)
            # every queued patch must be placed exactly once (the unstitch
            # gather relies on this); checked on the packing itself so the
            # simulation never pays for device record packing
            placed = sorted(p.patch_idx for c in inv.canvases
                            for p in c.placements)
            assert placed == list(range(len(inv.patches))), placed
        self.invocations.append(inv)
        n = len(inv.patches)
        self._queued -= n
        self._inflight_count += n
        bound = getattr(self.executor, "max_inflight", None)
        if bound is not None:
            # make room before submitting (the submit below may pin
            # device memory for its canvases): take any already-finished
            # handle first, and only block on the oldest when none is
            while len(self._inflight) >= bound:
                self._resolve_one()
        tel = self.telemetry
        attrs = self._fire_attrs(inv) if tel.enabled else {}
        with tel.span("tangram.engine.dispatch", **attrs):
            handle = self._submit(inv)
        self._event_seq += 1
        handle.seq = self._event_seq
        if handle.model is None:
            handle.model = inv.model
        if handle.t_finish is not None:
            heapq.heappush(self._scheduled,
                           (handle.t_finish, self._event_seq, handle))
        else:
            self._inflight.append(handle)
            self.inflight_high_water = max(self.inflight_high_water,
                                           len(self._inflight))

    def _fire_attrs(self, inv: Invocation) -> dict:
        """The dispatch span's attributes: how the invocation fired, and
        when its patches arrived."""
        arrivals = []
        for p in inv.patches:
            slot = self._slot_of.get(id(p))
            if slot is not None:
                arrivals.append(self._slot_t[slot])
        return {"reason": inv.reason, "patches": len(inv.patches),
                "canvases": len(inv.canvases), "t_fire": inv.t_submit,
                "t_launch": self.clock.now(), "arrivals": arrivals}

    def _submit(self, inv: Invocation) -> ExecHandle:
        submit = getattr(self.executor, "submit", None)
        if submit is not None:
            return submit(inv)
        comp = self.executor.execute(inv)          # legacy executor
        return ExecHandle(inv, t_finish=comp.t_finish, completion=comp)

    @staticmethod
    def _delivery_order(handle: ExecHandle):
        """Pinned completion tie-break: worker index, then submit seq —
        so multi-worker replays deliver simultaneously-ready handles in a
        reproducible order (regression-tested)."""
        return (handle.worker, handle.seq)

    def _harvest_ready(self):
        """Deliver async completions the device has already finished.

        Non-blocking: *every* in-flight handle is probed, not just the
        FIFO head — with a worker pool (or any out-of-order substrate) a
        slow batch at the head must not pin completed later batches in
        flight (head-of-line harvest bug, regression-tested).  Handles
        ready at the same harvest deliver in ``(worker, seq)`` order."""
        ready = self._ready_probe
        if ready is None:
            return
        while True:
            done = [h for h in self._inflight if ready(h)]
            if not done:
                return
            for handle in sorted(done, key=self._delivery_order):
                self._inflight.remove(handle)
                self._resolve_inflight(handle)

    def _resolve_one(self):
        """Retire one in-flight handle: any already-ready handle first
        (lowest ``(worker, seq)``), else block on the FIFO head."""
        ready = self._ready_probe
        if ready is not None:
            done = [h for h in self._inflight if ready(h)]
            if done:
                handle = min(done, key=self._delivery_order)
                self._inflight.remove(handle)
                self._resolve_inflight(handle)
                return
        self._resolve_inflight(self._inflight.popleft())

    def _resolve_inflight(self, handle: ExecHandle):
        comp = self.executor.resolve(handle)
        # async finishes are measured on the device's own wall timer;
        # clamp monotone *per worker* — a worker is a serial queue, so
        # its dispatches really do finish in submit order and the clamp
        # only smooths timer jitter.  Across workers finishes genuinely
        # interleave: a global clamp would inflate the recorded latency
        # (and fabricate SLO violations) for a fast worker's completion
        # delivered after a slow worker's.
        last = self._last_async_finish.get(handle.worker, 0.0)
        comp.t_finish = max(last, comp.t_finish)
        self._last_async_finish[handle.worker] = comp.t_finish
        self._deliver(comp)

    def _deliver_scheduled(self):
        _, _, handle = heapq.heappop(self._scheduled)
        self._deliver(self.executor.resolve(handle))

    def _deliver(self, comp: Completion):
        """Completion delivery: executor bookkeeping, outcome recording,
        then batcher feedback — all observing the *actual* finish."""
        on_complete = getattr(self.executor, "on_complete", None)
        if on_complete is not None:
            on_complete(comp)
        inv = comp.invocation
        if comp.model is None:
            comp.model = inv.model
        self._inflight_count -= len(inv.patches)
        for p in inv.patches:
            slot = self._slot_of.pop(id(p), None)
            if slot is None:
                t_arrive = inv.t_submit
            else:
                t_arrive = self._slot_t[slot]
                self._slot_patch[slot] = None
                self._free_slots.append(slot)
            self.outcomes.append(
                PatchOutcome(p, t_arrive, inv.t_submit, comp.t_finish,
                             model=comp.model))
        on_result = getattr(self.pool, "on_result", None)
        if on_result is not None:
            on_result(inv, comp.t_finish)
        # the executor's on_complete is the delivery point for outputs;
        # dropping the payload here keeps the retained completion log
        # light — otherwise a long device run would pin every routed
        # pixel batch for the engine's lifetime
        comp.outputs = None
        self.completions.append(comp)
