"""Multi-worker device pool: placement, out-of-order harvest, determinism,
per-worker capacity agreement, and the online latency estimator loop.

Covers the PR acceptance criteria:

* boundary identity — a 1-worker ``WorkerPoolExecutor`` groups patches
  into the exact invocations (and routes the exact detections) of the
  plain ``AsyncDeviceExecutor``, and Sim (per-worker platform capacity
  shards) agrees with Device (per-worker executors) on boundaries;
* head-of-line harvest fix — a slow batch on one worker no longer pins
  completed batches on another worker in flight;
* deterministic event ordering — simultaneously-ready completions
  deliver in pinned ``(worker index, submit seq)`` order;
* drifted device — an ``OnlineLatencyTable`` fed by the pool cuts SLO
  violations versus the static profile when the device is slower than
  profiled;
* per-worker utilization and per-class violation breakdown in
  ``Results.summary()``.
"""
import math

import numpy as np
import pytest

from repro.core.clock import WallClock
from repro.core.devicestub import StubAccelerator, VirtualAccelerator
from repro.core.engine import (AsyncDeviceExecutor, Completion, ExecHandle,
                               ServingEngine, SimExecutor, slo_class,
                               uniform_pool)
from repro.core.invoker import Invocation
from repro.core.latency import LatencyTable, OnlineLatencyTable
from repro.core.partitioning import Patch
from repro.core.workers import (ClassAffinityPlacement,
                                LeastOutstandingPlacement,
                                RoundRobinPlacement, WorkerPoolExecutor,
                                device_worker_pool, make_placement,
                                share_frame_store)
from repro.data.video import Arrival
from repro.serverless.platform import (Platform, PlatformConfig,
                                       split_platform)


def table(mu=0.1, sigma=0.01, n=32):
    return LatencyTable({b: (mu * b, sigma) for b in range(1, n + 1)},
                        slack_sigmas=3.0)


def arrivals_of(patches):
    return [Arrival(p.t_gen, p, 0.0) for p in patches]


def fake_serve_fn(params, x):
    import jax.numpy as jnp
    return (jnp.zeros((x.shape[0], 2, 2)),
            jnp.zeros((x.shape[0], 2, 2, 4)))


def trace_for_device(n=24, seed=3):
    rng = np.random.default_rng(seed)
    ps = []
    for i in range(n):
        t = round(float(rng.uniform(0, 4.0)), 3)
        w = int(rng.integers(8, 64))
        h = int(rng.integers(8, 64))
        ps.append(Patch(0, 0, w, h, frame_id=i // 3, t_gen=t,
                        slo=float(rng.choice([0.6, 2.0]))))
    return sorted(ps, key=lambda p: p.t_gen)


def _groups(engine, trace):
    idx = {id(p): i for i, p in enumerate(trace)}
    return [[idx[id(p)] for p in inv.patches] for inv in engine.invocations]


def _inv(key=None, n_patches=1, t=0.0):
    ps = [Patch(0, 0, 16, 16, t_gen=t, slo=1.0) for _ in range(n_patches)]
    return Invocation(t, [], ps, 0.0, "timer", key=key)


class _ManualWorker:
    """Submit/complete worker with hand-controlled readiness: handles
    become ready only when the test releases them, and every completion
    reports the same finish time — the pinned-tie-break scenario."""

    def __init__(self, t_finish=1.0, max_inflight=None):
        self.t_finish = t_finish
        self.released = False
        self.submitted = []
        if max_inflight is not None:
            self.max_inflight = max_inflight

    def submit(self, inv):
        self.submitted.append(inv)
        return ExecHandle(inv, t_finish=None)

    def ready(self, handle):
        return self.released

    def resolve(self, handle):
        return Completion(handle.invocation, self.t_finish)


class _FixedPlacement:
    """Route invocation k to ``sequence[k]`` (test determinism helper)."""

    def __init__(self, sequence):
        self.sequence = list(sequence)
        self._k = 0

    def choose(self, inv, pool):
        idx = self.sequence[self._k % len(self.sequence)]
        self._k += 1
        return idx


# ------------------------------------------------- boundary identity ----

def test_one_worker_pool_matches_async_executor_boundaries():
    """Acceptance: the pool facade is invisible at 1 worker — identical
    invocation boundaries to the plain AsyncDeviceExecutor."""
    trace = trace_for_device()
    lat = table()

    def run(executor):
        eng = ServingEngine(uniform_pool(64, 64, lat, classify=slo_class),
                            executor)
        eng.run(arrivals_of(trace))
        return eng

    plain = run(AsyncDeviceExecutor(fake_serve_fn, None, 64, 64,
                                    max_inflight=2))
    pooled = run(device_worker_pool(
        1, lambda i: AsyncDeviceExecutor(fake_serve_fn, None, 64, 64,
                                         max_inflight=2)))
    assert _groups(pooled, trace) == _groups(plain, trace)


def test_sim_and_device_pools_agree_with_per_worker_capacity():
    """Acceptance: per-worker platform capacity shards (Sim) and
    per-worker device executors (Device) produce identical invocation
    boundaries for the same trace and pool size."""
    trace = trace_for_device()
    lat = table()

    def run(executor):
        eng = ServingEngine(uniform_pool(64, 64, lat, classify=slo_class),
                            executor)
        eng.run(arrivals_of(trace))
        return eng

    base = Platform(lat, PlatformConfig(max_instances=8))
    sim = run(WorkerPoolExecutor(
        [SimExecutor(p) for p in split_platform(base, 2)]))
    dev = run(device_worker_pool(
        2, lambda i: AsyncDeviceExecutor(fake_serve_fn, None, 64, 64,
                                         max_inflight=2)))
    assert _groups(sim, trace) == _groups(dev, trace)
    assert len(sim.outcomes) == len(dev.outcomes) == len(trace)


def detecting_serve_fn(params, x):
    import jax
    import jax.numpy as jnp

    @jax.jit
    def go(x):
        b, m, n, _ = x.shape
        s = 4
        obj = x.reshape(b, s, m // s, s, n // s, 3).mean(axis=(2, 4, 5))
        ys, xs = jnp.meshgrid(jnp.arange(s), jnp.arange(s), indexing="ij")
        cw, ch = n // s, m // s
        boxes = jnp.stack([xs * cw, ys * ch, (xs + 1) * cw, (ys + 1) * ch],
                          axis=-1).astype(jnp.float32)
        return obj, jnp.broadcast_to(boxes, (b, s, s, 4))

    return go(x)


class _CaptureAsync(AsyncDeviceExecutor):
    def __init__(self, captured, *a, **k):
        super().__init__(*a, **k)
        self.captured = captured

    def on_complete(self, comp):
        per_frame, _ = comp.outputs
        for fid, dets in per_frame.items():
            self.captured.setdefault(fid, []).extend(dets)
        super().on_complete(comp)


def _frames_and_trace(n_frames=4, per_frame=3, seed=7):
    rng = np.random.default_rng(seed)
    frames, ps = {}, []
    for fid in range(n_frames):
        px = rng.uniform(0.0, 1.0, size=(64, 128, 3)).astype(np.float32)
        px[:, : 32 * (fid % 3)] = 0.9
        frames[fid] = px
        for j in range(per_frame):
            x0 = int(rng.integers(0, 64))
            y0 = int(rng.integers(0, 32))
            ps.append(Patch(x0, y0, x0 + int(rng.integers(16, 64)),
                            y0 + int(rng.integers(16, 32)), frame_id=fid,
                            t_gen=round(0.3 * fid + 0.07 * j, 3), slo=0.5))
    return frames, sorted(ps, key=lambda p: p.t_gen)


def _sorted_dets(captured):
    return {fid: sorted((round(s, 5), tuple(round(v, 3) for v in box))
                        for s, box in dets)
            for fid, dets in captured.items()}


@pytest.mark.parametrize("n_workers", [1, 2])
def test_pool_routes_identical_detections_to_plain_async(n_workers):
    """Acceptance: routed detections are identical between the plain
    async executor and an n-worker pool (shared frame store, any
    placement interleaving)."""
    frames, trace = _frames_and_trace()
    counts = {}
    for p in trace:
        counts[p.frame_id] = counts.get(p.frame_id, 0) + 1

    def run(executor):
        for fid, px in frames.items():
            executor.add_frame(fid, px, counts.get(fid, 0))
        eng = ServingEngine(uniform_pool(64, 64, table()), executor)
        eng.run(arrivals_of(trace))
        return eng

    plain_cap = {}
    plain = _CaptureAsync(plain_cap, detecting_serve_fn, None, 64, 64,
                          max_inflight=2)
    run(plain)

    pool_cap = {}
    pool = device_worker_pool(
        n_workers,
        lambda i: _CaptureAsync(pool_cap, detecting_serve_fn, None, 64, 64,
                                max_inflight=2))
    eng = run(pool)

    assert plain_cap, "trace produced no detections to compare"
    assert _sorted_dets(pool_cap) == _sorted_dets(plain_cap)
    for attr in ("n_detections", "n_host_stitched", "n_fused",
                 "bytes_to_device", "bytes_from_device", "slot_pixels",
                 "live_pixels"):
        assert getattr(pool, attr) == getattr(plain, attr), attr
    assert plain.bytes_to_device > 0 and plain.slot_pixels > 0
    # every invocation crossed as a host-stitched canvas batch
    assert pool.n_host_stitched == pool.n_invocations > 0
    assert pool.n_fused == 0
    # shared frame store fully drained even when different workers route
    # different patches of the same frame
    assert pool.frames == {}
    for w in pool.workers:
        assert w.frames == {} and w._refs == {}
    assert len(eng.outcomes) == len(trace)


# ------------------------------------------- head-of-line harvest fix ----

def _warm_stitch_jits():
    """Compile the stitch/unstitch jits for the 64x64/32x32 shapes the
    wall-clock test below uses, so compilation time cannot eat into its
    timing margins on a cold process."""
    with StubAccelerator(service_s=0.0) as stub:
        dev = AsyncDeviceExecutor(stub.serve_fn, None, 64, 64,
                                  max_inflight=1, sync=stub.sync)
        eng = ServingEngine(uniform_pool(64, 64, table()), dev)
        eng.run(arrivals_of([Patch(0, 0, 32, 32, frame_id=0, t_gen=0.0,
                                   slo=1e-6)]))


def test_slow_worker_does_not_pin_fast_workers_completions():
    """Regression (head-of-line harvest bug): only the FIFO head used to
    be probed, so one slow batch pinned completed later batches in
    flight.  Two stub workers with very unequal service times: the fast
    worker's completion must be delivered while the slow one is still in
    flight."""
    _warm_stitch_jits()
    with StubAccelerator(service_s=0.5) as slow, \
            StubAccelerator(service_s=0.02) as fast:
        stubs = [slow, fast]
        workers = [AsyncDeviceExecutor(s.serve_fn, None, 64, 64,
                                       max_inflight=4, sync=s.sync)
                   for s in stubs]
        share_frame_store(workers)
        pool = WorkerPoolExecutor(workers,
                                  placement=_FixedPlacement([0, 1, 1]))
        # immediate "late" fires: one single-patch invocation per arrival
        ps = [Patch(0, 0, 32, 32, frame_id=i, t_gen=0.05 * i, slo=1e-6)
              for i in range(3)]
        eng = ServingEngine(uniform_pool(64, 64, table()), pool,
                            clock=WallClock(speed=1.0))
        # the trailing arrival lands ~0.25s (wall) after the fast worker
        # finished and while the slow worker is still busy: the harvest
        # at that arrival must deliver the fast completion out of order
        ps.append(Patch(0, 0, 32, 32, frame_id=3, t_gen=0.35, slo=1e-6))
        eng.run(arrivals_of(ps))

    assert len(eng.completions) == 4
    first = eng.completions[0]
    assert first.worker == 1, (
        "fast worker's completion was pinned behind the slow FIFO head: "
        f"delivered {[c.worker for c in eng.completions]}")
    # and the slow invocation still completes, after the fast ones
    assert {c.worker for c in eng.completions} == {0, 1}
    # the fast worker's finish is not clamped up to the slow worker's
    # (monotone clamp is per worker, not global)
    w0_first = next(c.t_finish for c in eng.completions if c.worker == 0)
    w1_first = next(c.t_finish for c in eng.completions if c.worker == 1)
    assert w1_first < w0_first
    by_worker = {}
    for c in eng.completions:
        by_worker.setdefault(c.worker, []).append(c.t_finish)
    for fins in by_worker.values():
        assert fins == sorted(fins)     # per-worker monotone preserved


# ------------------------------------------- deterministic ordering ----

def test_simultaneously_ready_completions_deliver_in_worker_seq_order():
    """Pinned tie-break: when several in-flight handles report ready at
    the same harvest, delivery order is (worker index, submit seq) —
    multi-worker replays are reproducible."""

    def run_once():
        workers = [_ManualWorker() for _ in range(3)]
        pool = WorkerPoolExecutor(workers,
                                  placement=RoundRobinPlacement())
        eng = ServingEngine(uniform_pool(64, 64, table()), pool)
        ps = [Patch(0, 0, 32, 32, frame_id=i, t_gen=0.0, slo=1e-6)
              for i in range(6)]
        for a in arrivals_of(ps):
            eng.offer(a)
        assert len(eng._inflight) == 6
        for w in workers:
            w.released = True          # everything becomes ready at once
        eng.finish()
        return [c.invocation.patches[0].frame_id for c in eng.completions]

    order = run_once()
    # round-robin over 3 workers: submit order 0..5 lands on workers
    # [0,1,2,0,1,2]; (worker, seq) delivery groups by worker first
    assert order == [0, 3, 1, 4, 2, 5]
    assert run_once() == order          # reproducible across replays


# ------------------------------------------------- placement policies ----

def test_least_outstanding_placement_spreads_load():
    workers = [_ManualWorker() for _ in range(3)]
    pool = WorkerPoolExecutor(workers, placement=LeastOutstandingPlacement())
    for _ in range(6):
        pool.submit(_inv())
    assert pool.outstanding == [2, 2, 2]
    assert [len(w.submitted) for w in workers] == [2, 2, 2]


def test_least_outstanding_prefers_drained_worker():
    workers = [_ManualWorker() for _ in range(2)]
    pool = WorkerPoolExecutor(workers)
    h0 = pool.submit(_inv())
    pool.submit(_inv())
    workers[0].released = True
    pool.resolve(h0)                    # worker 0 drains
    pool.submit(_inv())
    assert len(workers[0].submitted) == 2


def test_class_affinity_reserves_workers_for_tight_class():
    workers = [_ManualWorker() for _ in range(3)]
    pool = WorkerPoolExecutor(
        workers,
        placement=ClassAffinityPlacement(reserved={0.2: (0,)}))
    for _ in range(2):
        pool.submit(_inv(key=0.2))      # tight class -> reserved worker 0
    for _ in range(4):
        pool.submit(_inv(key=2.0))      # loose class -> workers 1 and 2
    assert len(workers[0].submitted) == 2
    assert all(inv.key == 0.2 for inv in workers[0].submitted)
    assert len(workers[1].submitted) == 2 and len(workers[2].submitted) == 2
    assert all(inv.key == 2.0
               for w in workers[1:] for inv in w.submitted)


def test_class_affinity_reserve_tightest_dynamic():
    workers = [_ManualWorker() for _ in range(2)]
    pool = WorkerPoolExecutor(
        workers, placement=ClassAffinityPlacement(reserve_tightest=1))
    pool.submit(_inv(key=0.5))          # single class yet: no reservation
    pool.submit(_inv(key=2.0))          # second class appears -> worker 1
    pool.submit(_inv(key=2.0))
    assert len(workers[0].submitted) == 1
    assert len(workers[1].submitted) == 2


def test_class_affinity_single_class_uses_whole_pool():
    """reserve_tightest must not degenerate a single-class workload to
    one worker: with no second class there is nothing to protect, so
    placement spreads least-outstanding over every worker."""
    workers = [_ManualWorker() for _ in range(3)]
    pool = WorkerPoolExecutor(
        workers, placement=ClassAffinityPlacement(reserve_tightest=1))
    for _ in range(6):
        pool.submit(_inv(key=None))     # serve driver's default classify
    assert [len(w.submitted) for w in workers] == [2, 2, 2]


def test_make_placement_names():
    assert isinstance(make_placement("least"), LeastOutstandingPlacement)
    assert isinstance(make_placement("round"), RoundRobinPlacement)
    assert isinstance(make_placement("affinity"), ClassAffinityPlacement)
    with pytest.raises(ValueError):
        make_placement("nope")


def test_pool_requires_workers_and_valid_placement_choice():
    with pytest.raises(ValueError):
        WorkerPoolExecutor([])
    pool = WorkerPoolExecutor([_ManualWorker()],
                              placement=_FixedPlacement([5]))
    with pytest.raises(ValueError):
        pool.submit(_inv())


def test_pool_max_inflight_sums_worker_bounds():
    workers = [AsyncDeviceExecutor(fake_serve_fn, None, 64, 64,
                                   max_inflight=3) for _ in range(2)]
    assert WorkerPoolExecutor(workers).max_inflight == 6
    assert not hasattr(WorkerPoolExecutor([_ManualWorker()]), "max_inflight")


def test_per_worker_inflight_bound_is_hard_under_skewed_placement():
    """A worker's own max_inflight is a device-memory bound: a placement
    that keeps choosing a saturated worker is overridden and the
    overflow re-routed to a worker with room."""
    workers = [_ManualWorker(max_inflight=2) for _ in range(2)]
    pool = WorkerPoolExecutor(workers, placement=_FixedPlacement([0]))
    for _ in range(4):
        pool.submit(_inv())
    assert pool.outstanding == [2, 2]
    assert len(workers[0].submitted) == 2
    assert len(workers[1].submitted) == 2


# ------------------------------------------------ online latency loop ----

def _drift_run(online: bool, service_s=0.06, n=20, slo=0.1, spacing=0.15):
    """Serve evenly-spaced single-patch invocations on a deterministic
    engine-time device that is much slower than its profile."""
    seed = LatencyTable({1: (0.004, 0.0005), 2: (0.008, 0.001)},
                        slack_sigmas=3.0)
    lat = OnlineLatencyTable(seed) if online else seed
    dev = VirtualAccelerator(service_s)
    pool = WorkerPoolExecutor([dev],
                              estimator=lat if online else None)
    eng = ServingEngine(uniform_pool(64, 64, lat), pool)
    ps = [Patch(0, 0, 32, 32, frame_id=i, t_gen=round(i * spacing, 4),
                slo=slo) for i in range(n)]
    eng.run(arrivals_of(ps))
    assert len(eng.outcomes) == len(ps)
    return eng


def test_online_latency_cuts_violations_on_drifted_device():
    """Acceptance: the device runs 15x slower than its offline profile;
    the static table keeps firing too late (every deadline missed), the
    online table learns the real service time after the first completions
    and the violation rate collapses."""
    static = _drift_run(online=False)
    online = _drift_run(online=True)
    v_static = sum(o.violated for o in static.outcomes)
    v_online = sum(o.violated for o in online.outcomes)
    assert v_static == len(static.outcomes), \
        "static arm unexpectedly met deadlines — drift scenario broken"
    assert v_online < v_static
    assert v_online <= 2                # only the pre-feedback prefix


def test_pool_over_sync_device_executor_feeds_estimator():
    """A 1-worker pool around the *sync* DeviceExecutor (the serve
    driver's --online-latency without --async-device) keeps synchronous
    execution semantics while feeding every completion to the
    estimator."""
    from repro.core.engine import DeviceExecutor

    est = OnlineLatencyTable(table())
    pool = WorkerPoolExecutor([DeviceExecutor(fake_serve_fn, None, 64, 64)],
                              estimator=est)
    eng = ServingEngine(uniform_pool(64, 64, est), pool)
    ps = [Patch(0, 0, 32, 32, frame_id=i, t_gen=0.3 * i, slo=1.0)
          for i in range(4)]
    eng.run(arrivals_of(ps))
    assert len(eng.outcomes) == len(ps)
    assert eng.inflight_high_water == 0     # still fully synchronous
    assert est.n_observations == len(eng.invocations) > 0


def test_online_latency_estimator_tracks_per_worker_drift():
    seed = table(mu=0.01, sigma=0.0)
    est = OnlineLatencyTable(seed, alpha=0.5)
    fast = VirtualAccelerator(0.01)
    slow = VirtualAccelerator(0.08)
    pool = WorkerPoolExecutor([fast, slow],
                              placement=RoundRobinPlacement(),
                              estimator=est)
    eng = ServingEngine(uniform_pool(64, 64, est), pool)
    ps = [Patch(0, 0, 32, 32, frame_id=i, t_gen=round(0.2 * i, 4), slo=1e-6)
          for i in range(8)]
    eng.run(arrivals_of(ps))
    assert est.n_observations == 8
    assert est.drift(worker=1) > est.drift(worker=0) > 0
    # the aggregate estimate moved toward the observed service times
    mu1, _ = est.mu_sigma(1)
    assert 0.01 < mu1 < 0.08


# -------------------------------------------- platform capacity shards ----

def test_split_platform_shards_capacity_and_shares_meter():
    lat = table()
    base = Platform(lat, PlatformConfig(max_instances=8, pre_warm=2, seed=7))
    shards = split_platform(base, 4)
    assert len(shards) == 4
    for i, sh in enumerate(shards):
        assert sh.cfg.max_instances == 2
        assert sh.cfg.seed == 7 + i
        assert sh.meter is base.meter
    # pre-warm remainder goes to the lowest-index workers
    assert [sh.cfg.pre_warm for sh in shards] == [1, 1, 0, 0]
    shards[0].submit(0.0, 1)
    shards[1].submit(0.0, 2)
    assert base.meter.invocations == 2
    assert base.total_cost > 0


def test_per_worker_config_conserves_total_capacity():
    cfg = PlatformConfig(max_instances=7, pre_warm=3)
    shards = [cfg.per_worker(3, worker=i) for i in range(3)]
    assert [s.max_instances for s in shards] == [3, 2, 2]   # sums to 7
    assert [s.pre_warm for s in shards] == [1, 1, 1]
    assert [s.seed for s in shards] == [cfg.seed + i for i in range(3)]
    with pytest.raises(ValueError):
        cfg.per_worker(0)
    with pytest.raises(ValueError):
        cfg.per_worker(3, worker=3)
    with pytest.raises(ValueError):
        PlatformConfig(max_instances=2).per_worker(4)   # worker would be
                                                        # zero-capacity


# --------------------------------------------------- results summary ----

def test_results_summary_has_per_worker_and_class_breakdown():
    from repro.core.scheduler import TangramScheduler

    lat = table()
    rng = np.random.default_rng(0)
    streams = [[Patch(0, 0, int(rng.integers(16, 64)),
                      int(rng.integers(16, 64)), frame_id=f, camera_id=cam,
                      t_gen=f / 10.0, slo=float(rng.choice([0.4, 2.0])))
                for f in range(12)] for cam in range(2)]
    sched = TangramScheduler(64, 64, lat,
                             Platform(lat, PlatformConfig(max_instances=8)),
                             classify=slo_class, n_workers=2,
                             placement="least", online_latency=True)
    res = sched.run(streams, bandwidth_bps=50e6)
    s = res.summary()

    assert set(s["class_violations"]) == {"0.4", "2.0"}
    total = sum(v["patches"] for v in s["class_violations"].values())
    assert total == res.n_patches
    for v in s["class_violations"].values():
        assert 0.0 <= v["violation_rate"] <= 1.0

    assert len(s["per_worker"]) == 2
    assert sum(w["invocations"] for w in s["per_worker"]) == res.invocations
    for w in s["per_worker"]:
        # busy_s is an interval union, so utilization is a true fraction
        assert 0.0 <= w["utilization"] <= 1.0
        assert "drift" in w                 # online estimator attached
    assert sched.estimator is not None
    assert sched.estimator.n_observations == res.invocations


def test_scheduler_worker_pool_keeps_boundaries_and_reports_stats():
    """The scheduler's worker-pool path batches identically to the plain
    path (placement cannot leak into batching) and attaches per-worker
    stats only when a pool actually served the run."""
    from repro.core.scheduler import TangramScheduler

    lat = table()
    rng = np.random.default_rng(1)
    streams = [[Patch(0, 0, int(rng.integers(16, 64)),
                      int(rng.integers(16, 64)), frame_id=f,
                      t_gen=f / 10.0, slo=1.0) for f in range(10)]]

    def run(**kw):
        plat = Platform(lat, PlatformConfig())
        return TangramScheduler(64, 64, lat, plat, **kw).run(
            streams, bandwidth_bps=50e6)

    plain = run()
    pooled = run(n_workers=2)
    assert plain.n_patches == pooled.n_patches
    assert plain.patches_per_batch == pooled.patches_per_batch
    assert plain.worker_stats is None
    assert pooled.worker_stats is not None and len(pooled.worker_stats) == 2
