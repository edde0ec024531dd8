"""Serving driver: the full Tangram pipeline against a real jit'd model.

Edge side per frame: GMM background subtraction -> RoI extraction ->
adaptive frame partitioning (Alg. 1).  Cloud side: the unified serving
engine (``core.engine``) drives the per-SLO-class invoker pool over
bandwidth-shaped arrivals and executes every fired invocation on the
device pipeline — batched stitch -> (data-parallel) detect -> inverse
unstitch -> per-frame routing.  Timers fire at their scheduled times
(not at the next arrival), and the executor's frame store is refcounted:
a frame is evicted the moment every patch cut from it has been routed.

Where arrivals come from is a ``--source`` choice (:mod:`repro.sources`):

* ``trace`` (default) — the edge pipeline runs up front and the
  pre-shaped arrivals replay through a
  :class:`~repro.sources.TraceSource`: the historical batch path.
* ``synthetic`` — live ingestion: ``--cameras`` synthetic cameras run
  the edge pipeline *during* serving, each shipping patches over its
  own FIFO uplink.  With ``--ingestion-window`` the engine's backlog
  feeds back to the cameras, which respond per ``--overload`` by
  dropping frames or degrading RoI quality; drop/degrade counts are
  reported at the end.
* ``file`` — like ``synthetic`` but frames come from a recorded stack
  (``--frames-path``, ``.npy``/``.npz`` or a directory of ``.npy``).

The whole pipeline is assembled from named factories —
``make_executor`` / ``make_clock`` / ``make_placement`` /
``make_source`` — driven by a :class:`~repro.core.config.ServeConfig`;
the CLI flags below are a direct projection of its fields.

``--async-device`` switches the executor to submit/complete mode
(:class:`~repro.core.engine.AsyncDeviceExecutor`): each fired invocation
is stitched and *dispatched* without blocking, the device works through
its queue while the engine keeps ingesting arrivals, and the engine
blocks only when ``--max-inflight`` handles are unresolved or the trace
drains.  ``--clock wall`` runs the engine on real time (timers fire at
wall instants, ``--wall-speed`` compresses the replay); the default
virtual clock replays the trace as fast as events can be processed.

``--workers N`` serves through a worker pool
(:class:`~repro.core.workers.WorkerPoolExecutor`): the local device set
is split into N independent mesh slices
(:func:`~repro.launch.mesh.make_worker_meshes`), each backing its own
async executor, and every fired invocation is routed to a worker by
``--placement``.  ``--online-latency`` wraps the profiled table in an
:class:`~repro.core.latency.OnlineLatencyTable` shared by the invokers
and the pool, folding observed per-worker completion times back into the
firing decision (EWMA), so batching tracks real device speed instead of
the offline profile.

Multi-device: the detector batch runs under a ``NamedSharding``
data-parallel layout — the stitched canvas batch is padded to the mesh's
"data"-axis size and split over it, so each device detects its slice of
the canvases.  On a 1-device world the mesh degenerates to 1x1 and every
step is identical to the unsharded path.

``--model NAME --full-width`` serves a registry model at its published
widths and native canvas (``ModelSpec.build(reduced=False)``), e.g. the
paper's ``tangram`` ViT-B/32 detector on 1024² canvases; without it a
registry model builds its reduced, CPU-sized trunk.  ``--devices N``
restricts the serve mesh to the first N local devices.

:func:`main` prints its summary and also returns it as a dict (patches,
invocations, routed detections and the detections themselves, SLO
violations, frames still held), so callers can compare runs in one
process.

``--telemetry PATH`` turns on the span recorder
(:mod:`repro.core.telemetry`) for the whole run and writes its spans and
the executors' transfer counters to PATH as Chrome trace-event JSON; the
summary then also gives the p95 of the engine's fire lag (engine time
a submit began less the instant the invoker fired at).

Usage:
  PYTHONPATH=src python -m repro.launch.serve --frames 40 --slo 1.0
  PYTHONPATH=src python -m repro.launch.serve --telemetry spans.json
  PYTHONPATH=src python -m repro.launch.serve --model tangram --full-width \
    --use-pallas-stitch
  PYTHONPATH=src python -m repro.launch.serve --async-device --max-inflight 4
  PYTHONPATH=src python -m repro.launch.serve --workers 2 --online-latency
  PYTHONPATH=src python -m repro.launch.serve --source synthetic \
    --ingestion-window 32 --overload degrade
  XLA_FLAGS=--xla_force_host_platform_device_count=8 \
    PYTHONPATH=src python -m repro.launch.serve --frames 16 --workers 4
"""
from __future__ import annotations

import argparse
import dataclasses
import time

import jax
import jax.numpy as jnp
import numpy as np

from repro import param as param_lib
from repro.compat import shardingx
from repro.config import DetectorConfig
from repro.core.clock import make_clock
from repro.core.config import ServeConfig, make_classify
from repro.core.engine import (InvokerPool, ModelRuntime, ServingEngine,
                               make_executor, uniform_pool)
from repro.core.engine import shard_canvases  # noqa: F401  (public re-export)
from repro.core.invoker import SLOAwareInvoker
from repro.core.latency import LatencyBank, OnlineLatencyTable, measure
from repro.core.models import make_model
from repro.core.parallel import ParallelShardedEngine
from repro.core.telemetry import Telemetry
from repro.core.fleet import (FleetInvokerPool, FleetPlan, FleetCostModel,
                              ShardedEngine, fleet_uniform_pool,
                              make_planner)
from repro.core.workers import (WorkerPoolExecutor, device_worker_pool,
                                make_placement, share_frame_store,
                                weight_caches)
from repro.launch.compile_cache import enable_compile_cache
from repro.launch.mesh import make_serve_mesh, make_worker_meshes
from repro.models import detector as detector_lib
from repro.sharding import ShardingConfig
from repro.sources import RateProfile, make_source


#: the device executors' counters, summed over executors into the
#: ``--telemetry`` file
COUNTERS = ("n_invocations", "n_fused", "n_host_stitched", "n_sharded",
            "n_detections", "evidence_bytes", "bytes_to_device",
            "bytes_from_device", "slot_pixels", "live_pixels")


def build_detector(canvas: int = 256, quantize: bool = False):
    cfg = DetectorConfig(name="serve-det", canvas=canvas, patch=32,
                         n_layers=2, d_model=64, n_heads=4, d_ff=128,
                         param_dtype="float32", compute_dtype="float32")
    rules = ShardingConfig.make().rules
    params = param_lib.init_params(jax.random.PRNGKey(0),
                                   detector_lib.param_specs(cfg))
    if quantize:
        # same weights, int8-resident: quantize the fp init through
        # models/quantize.py onto the quant spec tree
        from repro.models import quantize as quantize_lib

        cfg = dataclasses.replace(cfg, quant_weights=True)
        params = quantize_lib.quantize_params(
            detector_lib.param_specs(cfg), params)
    serve_fn = jax.jit(lambda p, x: detector_lib.serve(cfg, p, x, rules))
    # the same table the jit-internal logical constraints use: callers
    # must lay inputs out with these rules or force a reshard on entry
    return cfg, params, serve_fn, rules


def build_source(args, frame_sink, slos):
    """CLI -> source, through ``make_source``.  ``trace`` runs the same
    camera pipeline eagerly (no backpressure — the events pre-date the
    run) and replays the pre-shaped arrivals.  Multiple ``--slo`` values
    run one camera per class (distinct camera ids keep frame ids unique
    in the shared store) merged into one trace."""
    common = dict(n_frames=args.frames, canvas=args.canvas, slo=slos[0],
                  bandwidth_bps=args.bandwidth_mbps * 1e6,
                  overload=args.overload, frame_sink=frame_sink,
                  rate=RateProfile(fps=args.fps))
    if args.source == "file":
        return make_source("file", path=args.frames_path, **common)
    live = dict(scene=args.scene, n_cameras=args.cameras, **common)
    if args.source == "synthetic":
        return make_source("synthetic", **live)
    if len(slos) == 1:
        cam = make_source("synthetic", **live)
        return make_source("trace", arrivals=list(cam.events(None)))
    events = []
    for i, slo in enumerate(slos):
        per = dict(live, slo=slo, scene=args.scene + i, n_cameras=1,
                   camera_id=i)
        events.extend(make_source("synthetic", **per).events(None))
    events.sort(key=lambda a: a.t_arrive)
    return make_source("trace", arrivals=events)


def _record_routed(executor, routed: dict):
    """Copy every completion's routed detections into ``routed``
    ({frame_id: [(score, box_xyxy), ...]}) ahead of the executor's own
    delivery, which drops them."""
    deliver = executor.on_complete

    def on_complete(comp):
        for fid, dets in comp.outputs[0].items():
            routed.setdefault(fid, []).extend(dets)
        deliver(comp)

    executor.on_complete = on_complete


def main(argv=None):
    p = argparse.ArgumentParser()
    p.add_argument("--frames", type=int, default=40)
    p.add_argument("--slo", default="1.0",
                   help="SLO seconds; a comma list (e.g. 0.5,2.0) runs one "
                        "camera per class and shards the invoker per SLO")
    p.add_argument("--canvas", type=int, default=None,
                   help="canvas side in pixels (default 256, or the "
                        "model's native canvas with --full-width)")
    p.add_argument("--scene", type=int, default=0)
    p.add_argument("--fps", type=float, default=10.0)
    p.add_argument("--bandwidth-mbps", type=float, default=40.0,
                   help="uplink shaping for the virtual arrival clock")
    p.add_argument("--source", choices=("trace", "synthetic", "file"),
                   default="trace",
                   help="arrival source: trace replays a pre-generated "
                        "edge run; synthetic ingests live from --cameras "
                        "synthetic cameras; file streams --frames-path")
    p.add_argument("--cameras", type=int, default=1,
                   help="number of synthetic cameras (merged stream)")
    p.add_argument("--frames-path",
                   help="recorded frame stack for --source file "
                        "(.npy/.npz or a directory of .npy frames)")
    p.add_argument("--ingestion-window", type=int, default=None,
                   help="backlog bound, in patches, that live sources "
                        "throttle against (advisory; default: unbounded)")
    p.add_argument("--overload", choices=("drop", "degrade", "none"),
                   default="drop",
                   help="live-source response when the backlog fills the "
                        "ingestion window: drop frames, degrade RoI "
                        "quality (drops at 2x), or ignore")
    p.add_argument("--use-pallas-stitch", action="store_true",
                   help="run the unfused path's unstitch as a Pallas "
                        "kernel (compiled on TPU, interpreted on CPU); "
                        "its canvases are stitched on the host")
    p.add_argument("--obj-threshold", type=float, default=0.5,
                   help="objectness a detector cell needs to be routed "
                        "back to its frame as a detection")
    p.add_argument("--fuse", action="store_true",
                   help="fused device hot path: stitch->patch-embed and "
                        "decode->gather run as single kernels, so the "
                        "canvas batch never materializes in HBM and "
                        "detector outputs skip the host round-trip "
                        "(single-worker mesh; the fused path does not "
                        "shard the canvas batch)")
    p.add_argument("--quantize", action="store_true",
                   help="serve int8-resident weights: registry models "
                        "resolve to their _int8 variants (with their own "
                        "latency profiles) and the built-in detector "
                        "builds quantized through models/quantize.py")
    p.add_argument("--async-device", action="store_true",
                   help="overlap device execution with arrival ingestion "
                        "(submit/complete executor over JAX async dispatch)")
    p.add_argument("--max-inflight", type=int, default=4,
                   help="bound on unresolved device invocations in "
                        "--async-device mode")
    p.add_argument("--clock", choices=("virtual", "wall"), default="virtual",
                   help="virtual: replay as fast as events process; "
                        "wall: timers fire at real wall instants")
    p.add_argument("--wall-speed", type=float, default=1.0,
                   help="engine seconds per wall second with --clock wall "
                        "(>1 compresses the replay)")
    p.add_argument("--workers", type=int, default=1,
                   help="device worker pool size: the local device set is "
                        "split into this many independent mesh slices, "
                        "each an overlapped (async) executor, and "
                        "concurrent invocations are routed across them")
    p.add_argument("--shards", type=int, default=None,
                   help="fleet sharding: partition cameras into this many "
                        "shard groups, each its own invoker pool + "
                        "executor over its own mesh slice, under a "
                        "two-level ShardedEngine (core.fleet); mutually "
                        "exclusive with --workers > 1")
    p.add_argument("--planner", choices=("cost", "equal"), default=None,
                   help="shard layout planner with --shards: cost "
                        "(default; rate-aware LPT grouping + proportional "
                        "workers when the source exposes camera rates) or "
                        "equal (naive contiguous split); sources without "
                        "rate feeds route camera_id %% shards")
    p.add_argument("--parallel", action="store_true",
                   help="run each shard's engine loop on its own thread "
                        "(ParallelShardedEngine) with a bounded arrival "
                        "queue per shard; requires --shards; without it "
                        "the sequential sharded path is unchanged")
    p.add_argument("--placement",
                   choices=("least", "round", "affinity", "model"),
                   default="least",
                   help="worker placement policy with --workers > 1: "
                        "least-outstanding (default), round-robin, "
                        "class-affinity (tightest SLO class gets worker 0 "
                        "once a second class appears), or model-affinity "
                        "(same-model batches co-locate so weights stay "
                        "resident)")
    p.add_argument("--model", default=None,
                   help="registry model to serve (repro.core.models; "
                        "default: the historical tiny built-in detector)")
    p.add_argument("--full-width", action="store_true",
                   help="build the registry models at their published "
                        "widths and native canvas instead of the reduced "
                        "CPU-sized trunk; needs --model")
    p.add_argument("--devices", type=int, default=None,
                   help="span the serve mesh over the first N local "
                        "devices (default: all)")
    p.add_argument("--model-map", action="append", default=None,
                   metavar="CLASS=MODEL",
                   help="route an SLO class to a registry model, e.g. "
                        "--model-map 0.5=vit_s16 --model-map 2.0=tangram; "
                        "repeatable; classes not mapped fall back to "
                        "--model")
    p.add_argument("--telemetry", metavar="PATH", default=None,
                   help="record the serve path's spans (dispatch, launch, "
                        "finalize and their parts) and write them with the "
                        "transfer counters to PATH as Chrome trace-event "
                        "JSON at the end (loads in Perfetto)")
    p.add_argument("--online-latency", action="store_true",
                   help="fold observed per-worker completion times back "
                        "into the latency table (EWMA) so firing decisions "
                        "track real device speed; composes with any "
                        "executor mode")
    args = p.parse_args(argv)
    if args.workers < 1:
        p.error("--workers must be >= 1")
    if args.shards is not None and args.shards < 1:
        p.error("--shards must be >= 1")
    if args.shards is not None and args.workers > 1:
        p.error("--shards and --workers > 1 both carve the device set; "
                "pick one (per-shard worker pools: use the sim scheduler)")
    if args.parallel and args.shards is None:
        p.error("--parallel requires --shards")
    if args.cameras < 1:
        p.error("--cameras must be >= 1")
    if args.source == "file" and not args.frames_path:
        p.error("--source file requires --frames-path")
    if args.full_width and not args.model:
        p.error("--full-width builds a registry model: give --model")
    n_local = len(jax.devices())
    if args.devices is not None and not 1 <= args.devices <= n_local:
        p.error(f"--devices must be in [1, {n_local}]")
    try:
        slos = [float(s) for s in str(args.slo).split(",")]
    except ValueError:
        p.error(f"--slo must be a float or comma list, got {args.slo!r}")
    if len(slos) > 1 and args.source != "trace":
        p.error("multiple --slo classes need --source trace")
    model_map = None
    if args.model_map:
        try:
            model_map = dict(kv.split("=", 1) for kv in args.model_map)
        except ValueError:
            p.error("--model-map entries must look like CLASS=MODEL")

    # every pipeline choice below is a field of this one record
    config = ServeConfig(
        max_canvases=4,
        classify="slo" if (model_map or len(slos) > 1) else None,
        executor="async_device" if args.async_device or args.workers > 1
        else "device",
        use_pallas=args.use_pallas_stitch,
        fuse=args.fuse, quantize=args.quantize,
        max_inflight=args.max_inflight,
        clock=args.clock, wall_speed=args.wall_speed,
        n_workers=args.workers, placement=args.placement,
        online_latency=args.online_latency,
        source=args.source, ingestion_window=args.ingestion_window,
        model=args.model, model_map=model_map,
        shards=args.shards, planner=args.planner, parallel=args.parallel)

    enable_compile_cache()
    if config.quantize and config.multi_model:
        # --quantize reroutes every referenced registry model to its
        # _int8 variant (when one is registered): quantized weights,
        # economics, and latency profile, same routing keys
        from repro.core.models import model_names as _registry_names

        have = set(_registry_names())

        def _q(name):
            return (f"{name}_int8"
                    if name and f"{name}_int8" in have else name)

        config = config.replace(
            model=_q(config.model),
            model_map=({k: _q(v) for k, v in config.model_map.items()}
                       if config.model_map else None))
    specs = ({name: make_model(name) for name in config.model_names()}
             if config.multi_model else {})
    if args.full_width:
        sides = {side for s in specs.values()
                 for side in (s.canvas_m, s.canvas_n)}
        if len(sides) != 1:
            p.error(f"--full-width needs models that share one square "
                    f"native canvas, got sides {sorted(sides)}")
        native = sides.pop()
        if args.canvas not in (None, native):
            p.error(f"--full-width serves at the native canvas {native}, "
                    f"not --canvas {args.canvas}")
        args.canvas = native
    elif args.canvas is None:
        args.canvas = 256
    m = n = args.canvas
    if config.multi_model:
        # registry builds: each referenced model jit-compiles its trunk
        # (reduced unless --full-width) at the canvas, with per-name
        # weight seeds
        builds = {name: spec.build(canvas=m, reduced=not args.full_width)
                  for name, spec in specs.items()}
        default_model = config.model or sorted(builds)[0]
        cfg, params, serve_fn, rules = builds[default_model]
        print(f"models: {', '.join(sorted(builds))} "
              f"(default {default_model})")
    else:
        builds, default_model = {}, None
        cfg, params, serve_fn, rules = build_detector(
            args.canvas, quantize=config.quantize)

    def fused_kwargs(mcfg, pr, rl):
        """ModelRuntime fused-path fields (tokens_fn + patch-embed
        projection) for one built model; empty when fusion is off."""
        if not config.fuse:
            return {}
        ek, eb = detector_lib.embed_params(mcfg, pr)
        tok = jax.jit(lambda p, t, _c=mcfg, _r=rl:
                      detector_lib.forward_tokens(_c, p, t, _r))
        return dict(tokens_fn=tok, embed_kernel=ek, embed_bias=eb,
                    patch=mcfg.patch)
    n_slices = config.shards or config.n_workers
    if n_slices > 1:
        meshes = make_worker_meshes(n_slices,
                                    devices=jax.devices()[:args.devices])
    else:
        meshes = [make_serve_mesh(args.devices)]
    mesh = meshes[0]
    axis_sizes = shardingx.mesh_axis_sizes(mesh)
    print(f"serve mesh: {len(meshes)} worker(s) x "
          f"data={axis_sizes.get('data', 1)} "
          f"model={axis_sizes.get('model', 1)} "
          f"({mesh.devices.size} devices each)")

    # offline profiling (the paper's 1000-iteration stage, scaled down)
    # under the same data-parallel layout execution will use; the sync
    # hook keeps jit's async dispatch inside the timed region
    def profile(fn, pr, rl):
        def run_batch(b):
            x = jnp.zeros((b, m, n, 3), jnp.float32)
            x, _ = shard_canvases(x, mesh, rl)
            return fn(pr, x)
        return measure(run_batch, batch_sizes=(1, 2, 4), iters=5, warmup=1,
                       sync=jax.block_until_ready)

    table = profile(serve_fn, params, rules)
    print("latency table:",
          {k: (round(v[0], 4), round(v[1], 4)) for k, v in table.table.items()})
    model_tables = {}
    for name, (_, pr, fn, rl) in builds.items():
        model_tables[name] = (table if name == default_model
                              else profile(fn, pr, rl))
    if config.online_latency:
        # one estimator instance, shared between the invoker pool (reads
        # t_slack) and the worker pool (feeds observations back); with
        # models it is a LatencyBank routing observations per model
        table = OnlineLatencyTable(table)
        model_tables = {name: (table if name == default_model
                               else OnlineLatencyTable(t))
                        for name, t in model_tables.items()}
    estimator = None
    if config.online_latency:
        estimator = (LatencyBank(model_tables) if config.multi_model
                     else table)

    def runtimes(mesh_i):
        """Per-model device runtimes on one worker's mesh slice."""
        return {name: ModelRuntime(fn, pr, m, n, mesh=mesh_i, rules=rl,
                                   **fused_kwargs(mcfg, pr, rl))
                for name, (mcfg, pr, fn, rl) in builds.items()}

    caches = None
    if config.multi_model and len(specs) > 1:
        # each worker holds the largest single model: swaps are real and
        # model-affinity placement is what avoids paying them repeatedly
        caches = weight_caches(
            config.n_workers,
            max(s.weight_bytes for s in specs.values()),
            {name: (s.weight_bytes, s.load_s) for name, s in specs.items()})

    telemetry = Telemetry(enabled=args.telemetry is not None)
    t_start = time.time()
    shard_executors = None
    if config.shards:
        # one executor per shard over its own mesh slice; the frame
        # store is shared so any shard's completions can route evidence
        # for any camera's frames (cameras pin to shards, frames don't
        # need to)
        shard_executors = [
            make_executor(
                config.executor, serve_fn=serve_fn, params=params,
                canvas_m=m, canvas_n=n, use_pallas=config.use_pallas,
                fuse=config.fuse, mesh=meshes[i % len(meshes)],
                rules=rules, max_inflight=config.max_inflight,
                obj_threshold=args.obj_threshold, telemetry=telemetry,
                models=runtimes(meshes[i % len(meshes)]) if builds else None,
                **fused_kwargs(cfg, params, rules))
            for i in range(config.shards)]
        share_frame_store(shard_executors)
        executor = shard_executors[0]
    elif config.n_workers > 1:
        # a multi-worker pool overlaps by construction: each worker is an
        # async executor over its own mesh slice, sharing one frame store
        executor = device_worker_pool(
            config.n_workers,
            lambda i: make_executor(
                config.executor, serve_fn=serve_fn, params=params,
                canvas_m=m, canvas_n=n, use_pallas=config.use_pallas,
                fuse=config.fuse, mesh=meshes[i], rules=rules,
                max_inflight=config.max_inflight,
                obj_threshold=args.obj_threshold, telemetry=telemetry,
                models=runtimes(meshes[i]) if builds else None,
                **fused_kwargs(cfg, params, rules)),
            placement=make_placement(config.placement),
            estimator=estimator, weight_caches=caches)
    else:
        executor = make_executor(
            config.executor, serve_fn=serve_fn, params=params,
            canvas_m=m, canvas_n=n, use_pallas=config.use_pallas,
            fuse=config.fuse, mesh=mesh, rules=rules,
            max_inflight=config.max_inflight,
            obj_threshold=args.obj_threshold, telemetry=telemetry,
            models=runtimes(mesh) if builds else None,
            **fused_kwargs(cfg, params, rules))
        if config.online_latency or caches is not None:
            # a 1-worker pool only adds the estimator feedback loop and
            # weight-cache accounting: the wrapped executor keeps its
            # sync-vs-async semantics, so the flags never change
            # execution mode behind the user's back
            executor = WorkerPoolExecutor([executor], estimator=estimator,
                                          weight_caches=caches)

    routed: dict = {}
    for ex in shard_executors or [executor]:
        _record_routed(ex, routed)
    source = build_source(args, frame_sink=executor.add_frame, slos=slos)

    def build_pool(fleet: bool = False):
        if config.multi_model:
            # per-class invokers: each SLO class fires against its
            # model's own latency table, so t_slack is per-model
            # (Eqn. 8 per tenant)
            def make_invoker(key):
                name = config.resolve_model(key) or default_model
                return SLOAwareInvoker(m, n, model_tables[name],
                                       max_canvases=config.max_canvases)

            pool_cls = FleetInvokerPool if fleet else InvokerPool
            return pool_cls(
                make_invoker,
                classify=make_classify(config.classify) or (lambda p: None),
                model_of=lambda key: (config.resolve_model(key)
                                      or default_model))
        fn = fleet_uniform_pool if fleet else uniform_pool
        return fn(m, n, table, max_canvases=config.max_canvases,
                  classify=make_classify(config.classify))

    if config.shards:
        window = (max(1, config.ingestion_window // config.shards)
                  if config.ingestion_window else None)
        if config.parallel and config.clock == "wall":
            # one wall timeline, one thread-private monotone view each
            parent_clock = make_clock("wall", speed=config.wall_speed)
            shard_clocks = [parent_clock.shard_view()
                            for _ in range(config.shards)]
        else:
            shard_clocks = [make_clock(config.clock,
                                       speed=config.wall_speed)
                            for _ in range(config.shards)]
        shard_engines = [
            ServingEngine(build_pool(fleet=True), shard_executors[s],
                          clock=shard_clocks[s],
                          ingestion_window=window, telemetry=telemetry)
            for s in range(config.shards)]
        if hasattr(source, "camera_rates"):
            planner = make_planner(
                config.planner or "cost",
                cost_model=FleetCostModel(latency=table),
                worker_budget=config.shards)
            plan = planner.plan(source.camera_rates(),
                                n_shards=config.shards)
        else:
            plan = FleetPlan(n_shards=config.shards)
        engine_cls = (ParallelShardedEngine if config.parallel
                      else ShardedEngine)
        engine = engine_cls(shard_engines, plan.shard_of, plan=plan)
    else:
        engine = ServingEngine(build_pool(), executor,
                               clock=make_clock(config.clock,
                                                speed=config.wall_speed),
                               ingestion_window=config.ingestion_window,
                               telemetry=telemetry)
    outcomes = engine.serve(source)

    stats = source.stats()
    violated = sum(o.violated for o in outcomes)
    executors = shard_executors if shard_executors else [executor]

    def _total(attr: str) -> int:
        return sum(getattr(e, attr, 0) for e in executors)

    fire_lag_ms = None
    if telemetry.enabled:
        lags = [r["t_launch"] - r["t_fire"]
                for r in telemetry.invocations().values() if "t_fire" in r]
        if lags:
            fire_lag_ms = 1e3 * float(np.percentile(lags, 95))
        telemetry.write_chrome_trace(args.telemetry, counters={
            attr: _total(attr) for attr in COUNTERS})
    if config.shards:
        overlap = (f"{config.shards} shard(s), "
                   f"{config.planner or 'cost'} planner"
                   + (", parallel" if config.parallel else ""))
    elif config.n_workers > 1:
        overlap = (f"{config.n_workers} worker(s), {config.placement} "
                   f"placement, in-flight high water "
                   f"{engine.inflight_high_water}/"
                   f"{getattr(executor, 'max_inflight', '-')}")
    elif args.async_device:
        overlap = (f"async, in-flight high water "
                   f"{engine.inflight_high_water}/{config.max_inflight}")
    else:
        overlap = "sync"
    if config.online_latency:
        overlap += ", online latency"
    if config.fuse:
        overlap += ", fused"
    if config.quantize:
        overlap += ", int8"
    print(f"served {stats.patches_emitted} patches in "
          f"{_total('n_invocations')} invocations ({overlap}, "
          f"{config.clock} clock, {_total('n_sharded')} data-parallel over "
          f"data={axis_sizes.get('data', 1)}), "
          f"routed {_total('n_detections')} detections + "
          f"{_total('evidence_bytes') / 1e6:.2f} MB patch evidence back to "
          f"frames, {_total('bytes_to_device') / 1e6:.2f} MB to and "
          f"{_total('bytes_from_device') / 1e6:.2f} MB from the device"
          + (f", fire lag p95 {fire_lag_ms:.1f} ms"
             if fire_lag_ms is not None else "") +
          f", {violated} SLO violations "
          f"({len(executor.frames)} frames still held, "
          f"{time.time()-t_start:.1f}s wall)")
    if config.shards:
        for row in engine.shard_stats():
            print(f"  shard {row['shard']}: {row['arrivals']} arrivals, "
                  f"{row['invocations']} invocations, "
                  f"{row['violations']} violations, backlog high water "
                  f"{row['backlog_high_water']}")
    print(f"source {stats.kind}: {stats.frames_total} frames, "
          f"{stats.frames_dropped} dropped, {stats.frames_degraded} "
          f"degraded, backlog high water {engine.backlog_high_water}"
          + (f"/{config.ingestion_window}"
             if config.ingestion_window else ""))
    if isinstance(executor, WorkerPoolExecutor):
        for ws in executor.worker_stats():
            drift = (f", drift {ws['drift']}x" if "drift" in ws else "")
            print(f"  worker {ws['worker']}: {ws['invocations']} "
                  f"invocations, {ws['patches']} patches, "
                  f"busy {ws['busy_s']:.3f}s{drift}")
    by_model = {}
    for o in outcomes:
        if o.model is not None:
            row = by_model.setdefault(o.model, [0, 0])
            row[0] += 1
            row[1] += int(o.violated)
    if by_model:
        cache_stats = (executor.model_cache_stats()
                       if hasattr(executor, "model_cache_stats") else {})
        for name in sorted(by_model):
            served, viol = by_model[name]
            extra = ""
            cs = cache_stats.get(name)
            if cs:
                extra = (f", weight hits {cs['weight_hits']}/"
                         f"{cs['weight_hits'] + cs['weight_misses']}")
            print(f"  model {name}: {served} patches, "
                  f"{viol} violations{extra}")
    return {"patches": stats.patches_emitted,
            "invocations": _total("n_invocations"),
            "fused": _total("n_fused"),
            "sharded": _total("n_sharded"),
            "detections": _total("n_detections"),
            "mb_to_device": _total("bytes_to_device") / 1e6,
            "mb_from_device": _total("bytes_from_device") / 1e6,
            "fire_lag_p95_ms": fire_lag_ms,
            "routed": routed,
            "violations": violated,
            "frames_held": len(executor.frames)}


if __name__ == "__main__":
    main()
