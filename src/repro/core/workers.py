"""Multi-worker device pool: route concurrent invocations across workers.

The PR 4 async core overlaps device execution with arrival ingestion, but
every invocation still funnels through *one* executor with one in-flight
queue — the simulation models N concurrent instances while the real
pipeline can exploit only one.  This module splits the executor layer
into independent **workers** (each its own mesh slice / device queue /
platform shard) behind one submit/complete facade:

* :class:`WorkerPoolExecutor` implements the engine's executor protocol
  (``submit``/``resolve``/``ready``/``max_inflight``/``on_complete``)
  and dispatches each fired :class:`~repro.core.invoker.Invocation` to a
  worker chosen by a pluggable **placement policy**.  Workers are plain
  executors — ``AsyncDeviceExecutor`` over per-worker mesh slices
  (:func:`repro.launch.mesh.make_worker_meshes`), ``SimExecutor`` over
  per-worker platform shards (:func:`repro.serverless.platform.
  split_platform`), or stubs — so Sim and Device scenarios share the
  same pool semantics.
* Placement policies: :class:`LeastOutstandingPlacement` (default — the
  worker with the fewest unresolved invocations wins, index breaks
  ties), :class:`RoundRobinPlacement`,
  :class:`ClassAffinityPlacement` (tight-SLO classes get reserved
  workers; everything else spreads over the rest), and
  :class:`ModelAffinityPlacement` (same-model batches co-locate so
  weights stay resident — see :class:`WeightCache`, the per-worker LRU
  weight cache with a modeled swap-in cost).
* The engine harvests completions **out of order** across all workers'
  in-flight work (a slow batch on worker 0 no longer pins completed
  batches on worker 1), with delivery ties pinned to ``(worker index,
  submit seq)`` so multi-worker replays are reproducible.
* Pass an :class:`~repro.core.latency.OnlineLatencyTable` as
  ``estimator`` and every resolved completion feeds its observed
  per-worker, per-batch elapsed time back into the table the invokers
  fire against — the closed loop between real device speed and batching
  decisions.

Device workers sharing pixels: :func:`share_frame_store` aliases the
refcounted frame store across a pool's device executors, so any worker
can gather crops for any frame and eviction still happens exactly when
the last patch cut from a frame has been routed (regardless of which
workers routed them).
"""
from __future__ import annotations

import collections
import math
from typing import Callable, Dict, List, Mapping, Optional, Sequence, Tuple

from repro.core.engine import Completion, ExecHandle
from repro.core.invoker import Invocation
from repro.core.registry import lookup


# ----------------------------------------------------- weight cache ----

class WeightCache:
    """Per-worker model-weight residency: LRU over a byte budget.

    The single-model pipeline kept its detector implicitly
    always-resident; with multiple models a worker holds whichever
    weights fit in ``capacity_bytes`` and pays a modeled load cost to
    swap one in.  ``models`` maps a registry model name to
    ``(weight_bytes, load_s)`` (both straight off a
    :class:`~repro.core.models.ModelSpec`).

    :meth:`ensure` is the one mutation: it returns the load seconds the
    caller must add to the invocation's finish time — ``0.0`` on a hit —
    touching the entry MRU and evicting least-recently-used residents
    until the new weights fit.  A model larger than the whole budget
    still loads (it runs resident alone, everything else evicted), the
    same semantics as a platform instance hosting one oversized model.
    Unknown or untagged models cost nothing and are not cached — the
    legacy single-model path goes through unchanged.

    Deterministic by construction (no clock, no randomness): eviction
    order is pinned by the access sequence alone, which is what the
    eviction regression test relies on.
    """

    def __init__(self, capacity_bytes: float,
                 models: Mapping[str, Tuple[float, float]]):
        if capacity_bytes <= 0:
            raise ValueError(
                f"capacity_bytes must be positive, got {capacity_bytes}")
        self.capacity_bytes = float(capacity_bytes)
        self.models = {name: (float(size), float(load))
                       for name, (size, load) in models.items()}
        self._resident: "collections.OrderedDict[str, float]" = \
            collections.OrderedDict()          # name -> weight_bytes
        self.used_bytes = 0.0
        self.hits: Dict[str, int] = {}
        self.misses: Dict[str, int] = {}
        self.evictions = 0
        self.load_seconds = 0.0

    def holds(self, model: Optional[str]) -> bool:
        return model in self._resident

    def resident(self) -> List[str]:
        """Resident model names, LRU first (the next eviction victim
        leads)."""
        return list(self._resident)

    @property
    def n_hits(self) -> int:
        return sum(self.hits.values())

    @property
    def n_misses(self) -> int:
        return sum(self.misses.values())

    @property
    def hit_rate(self) -> float:
        total = self.n_hits + self.n_misses
        return self.n_hits / total if total else 0.0

    def ensure(self, model: Optional[str]) -> float:
        """Make ``model`` resident; returns the modeled load seconds
        (0.0 on a hit, or for untagged/unknown models)."""
        if model is None or model not in self.models:
            return 0.0
        if model in self._resident:
            self._resident.move_to_end(model)
            self.hits[model] = self.hits.get(model, 0) + 1
            return 0.0
        size, load_s = self.models[model]
        while self._resident and self.used_bytes + size > self.capacity_bytes:
            _, evicted = self._resident.popitem(last=False)
            self.used_bytes -= evicted
            self.evictions += 1
        self._resident[model] = size
        self.used_bytes += size
        self.misses[model] = self.misses.get(model, 0) + 1
        self.load_seconds += load_s
        return load_s

    def stats(self) -> dict:
        return {"hits": self.n_hits, "misses": self.n_misses,
                "hit_rate": round(self.hit_rate, 4),
                "evictions": self.evictions,
                "load_s": round(self.load_seconds, 4),
                "resident": self.resident()}


def weight_caches(n_workers: int, capacity_bytes: float,
                  models: Mapping[str, Tuple[float, float]]
                  ) -> List[WeightCache]:
    """One independent :class:`WeightCache` per pool worker."""
    return [WeightCache(capacity_bytes, models) for _ in range(n_workers)]


# ------------------------------------------------------- placement ----

class LeastOutstandingPlacement:
    """Pick the worker with the fewest unresolved invocations (lowest
    index wins ties) — the classic join-the-shortest-queue heuristic."""

    def choose(self, inv: Invocation, pool: "WorkerPoolExecutor") -> int:
        return min(range(pool.n_workers),
                   key=lambda i: (pool.outstanding[i], i))


class RoundRobinPlacement:
    """Cycle through workers regardless of load (baseline policy)."""

    def __init__(self):
        self._next = 0

    def choose(self, inv: Invocation, pool: "WorkerPoolExecutor") -> int:
        idx = self._next % pool.n_workers
        self._next += 1
        return idx


class ClassAffinityPlacement:
    """Reserve workers for specific SLO classes.

    ``reserved`` maps an invocation's class key (``inv.key``, tagged by
    the :class:`~repro.core.engine.InvokerPool`) to the worker indices
    its batches may run on; keys not in the map spread over the
    *unreserved* workers (or over every worker when nothing is left).
    Within the allowed set the least-outstanding worker wins, so the
    policy degrades to :class:`LeastOutstandingPlacement` inside each
    partition.

    ``reserve_tightest`` is the zero-config variant: the first
    ``reserve_tightest`` workers are reserved for the numerically
    smallest class key observed so far (tightest SLO under the default
    ``slo_class`` classification) — useful when class keys are not known
    up front.  The reservation only activates once a *second* class has
    been seen: with a single class there is no competition to protect
    against, and pinning all traffic to the reserved workers would
    silently waste the rest of the pool.
    """

    def __init__(self, reserved: Optional[Mapping[object,
                                                  Sequence[int]]] = None,
                 reserve_tightest: int = 0):
        self.reserved = {k: tuple(v) for k, v in (reserved or {}).items()}
        self.reserve_tightest = reserve_tightest
        self._tightest: object = None
        self._seen: set = set()

    def _allowed(self, key: object, n_workers: int) -> Sequence[int]:
        if self.reserve_tightest > 0:
            k = min(self.reserve_tightest, n_workers)
            self._seen.add(key)
            try:
                if self._tightest is None or key < self._tightest:
                    self._tightest = key
            except TypeError:          # uncomparable keys: first one wins
                if self._tightest is None:
                    self._tightest = key
            if len(self._seen) < 2:
                return range(n_workers)
            if key == self._tightest:
                return range(k)
            rest = range(k, n_workers)
            return rest if len(rest) else range(n_workers)
        if key in self.reserved:
            allowed = [i for i in self.reserved[key] if i < n_workers]
            if allowed:
                return allowed
        taken = {i for v in self.reserved.values() for i in v}
        free = [i for i in range(n_workers) if i not in taken]
        return free if free else range(n_workers)

    def choose(self, inv: Invocation, pool: "WorkerPoolExecutor") -> int:
        allowed = self._allowed(inv.key, pool.n_workers)
        return min(allowed, key=lambda i: (pool.outstanding[i], i))


class ReservedClassPlacement:
    """Placement honouring a :class:`~repro.core.fleet.FleetPlan`
    shard's per-class worker reservations.

    ``reserved`` maps a class key's ``str()`` (the plan's JSON-safe
    spelling) to a worker count: that class's batches run on the
    lowest-index workers reserved for it, unmatched classes on whatever
    is left (everything, when nothing is reserved).  Least-outstanding
    within the allowed set, lowest index on ties — the same degradation
    rule as :class:`ClassAffinityPlacement`.
    """

    def __init__(self, reserved: Mapping[str, int]):
        self.reserved = dict(reserved)
        self._ranges: Dict[str, range] = {}
        start = 0
        for key in sorted(self.reserved):
            count = self.reserved[key]
            self._ranges[key] = range(start, start + count)
            start += count
        self._first_free = start

    def choose(self, inv: Invocation, pool: "WorkerPoolExecutor") -> int:
        allowed = self._ranges.get(str(inv.key))
        if allowed is None or len(allowed) == 0:
            allowed = range(self._first_free, pool.n_workers)
            if len(allowed) == 0:
                allowed = range(pool.n_workers)
        allowed = [i for i in allowed if i < pool.n_workers]
        if not allowed:
            allowed = list(range(pool.n_workers))
        return min(allowed, key=lambda i: (pool.outstanding[i], i))


class ModelAffinityPlacement:
    """Co-locate batches of the same model so weights stay resident.

    An invocation tagged with a registry model (``inv.model``, set by
    the :class:`~repro.core.engine.InvokerPool`'s ``model_of``) prefers
    workers that already hold that model's weights:

    * with pool :class:`WeightCache`\\ s, the least-outstanding worker
      whose cache holds the model wins (real residency);
    * otherwise each model gets a sticky **home worker** assigned
      round-robin on first sight, so an N-model workload spreads over
      the pool while every model's traffic stays on one worker — the
      sim-platform analogue, where each worker's platform shard then
      keeps its instances warm for exactly one model.

    Untagged invocations fall back to least-outstanding.  The pool's
    per-worker in-flight bound still wins over affinity (overflow
    re-routes, as for every policy) — a resident model is worth a warm
    start, not an unbounded queue.
    """

    def __init__(self):
        self._home: Dict[str, int] = {}
        self._next = 0

    def choose(self, inv: Invocation, pool: "WorkerPoolExecutor") -> int:
        model = getattr(inv, "model", None)
        if model is None:
            return min(range(pool.n_workers),
                       key=lambda i: (pool.outstanding[i], i))
        caches = pool.weight_caches
        if caches is not None:
            resident = [i for i in range(pool.n_workers)
                        if caches[i].holds(model)]
            if resident:
                return min(resident,
                           key=lambda i: (pool.outstanding[i], i))
        home = self._home.get(model)
        if home is None:
            home = self._home[model] = self._next % pool.n_workers
            self._next += 1
        return home


_PLACEMENTS = {
    "least": LeastOutstandingPlacement,
    "round": RoundRobinPlacement,
    "affinity": lambda: ClassAffinityPlacement(reserve_tightest=1),
    "model": ModelAffinityPlacement,
}


def make_placement(name: str):
    """CLI-name -> policy instance
    (``least`` | ``round`` | ``affinity`` | ``model``)."""
    return lookup("placement", _PLACEMENTS, name)()


# ------------------------------------------------------------ pool ----

class WorkerPoolExecutor:
    """N independent workers behind one engine-facing executor.

    ``workers`` are executors implementing the submit/complete protocol
    (legacy ``execute``-only executors are not supported here — wrap them
    first).  ``placement`` chooses a worker per invocation; ``estimator``
    (an :class:`~repro.core.latency.OnlineLatencyTable`) receives every
    resolved completion's ``(batch, elapsed, worker)`` observation.

    ``max_inflight`` is the sum of the workers' bounds (the engine blocks
    only when the whole pool is saturated).  A worker's *own* bound is a
    hard constraint — it exists because each unresolved handle pins
    device memory on that worker — so :meth:`submit` treats placement as
    a preference that yields to it: an invocation placed on a worker
    already at its bound is re-routed to the least-outstanding worker
    with room (there always is one while the engine admits submits).
    Workers without a bound (sim workers resolve from the model at
    submit) are never full — a pool of only such workers exposes no
    bound at all.
    """

    def __init__(self, workers: Sequence[object], placement=None,
                 estimator=None,
                 weight_caches: Optional[Sequence[WeightCache]] = None):
        if not workers:
            raise ValueError("WorkerPoolExecutor needs at least one worker")
        self.workers = list(workers)
        self.placement = placement or LeastOutstandingPlacement()
        self.estimator = estimator
        if weight_caches is not None and len(weight_caches) != len(workers):
            raise ValueError(
                f"weight_caches has {len(weight_caches)} entries "
                f"for {len(workers)} workers")
        self.weight_caches = (list(weight_caches)
                              if weight_caches is not None else None)
        n = len(self.workers)
        self.outstanding = [0] * n       # unresolved invocations per worker
        self.n_submitted = [0] * n
        self.n_patches = [0] * n
        self.busy_s = [0.0] * n          # union of per-worker busy intervals
        self._last_finish = [0.0] * n
        bounds = [getattr(w, "max_inflight", None) for w in self.workers]
        known = [b for b in bounds if b is not None]
        if known:
            self.max_inflight = sum(known)

    @property
    def n_workers(self) -> int:
        return len(self.workers)

    def _has_room(self, idx: int) -> bool:
        bound = getattr(self.workers[idx], "max_inflight", None)
        return bound is None or self.outstanding[idx] < bound

    # ------------------------------------------------ engine protocol ----

    def submit(self, inv: Invocation) -> ExecHandle:
        idx = self.placement.choose(inv, self)
        if not 0 <= idx < self.n_workers:
            raise ValueError(f"placement chose worker {idx} "
                             f"of {self.n_workers}")
        if not self._has_room(idx):
            # the per-worker in-flight bound is a device-memory bound and
            # therefore hard; overflow to the least-loaded worker with
            # room rather than exceed it (skewed policies like class
            # affinity can otherwise pile everything on one worker)
            room = [i for i in range(self.n_workers) if self._has_room(i)]
            if room:
                idx = min(room, key=lambda i: (self.outstanding[i], i))
        handle = self.workers[idx].submit(inv)
        handle.worker = idx
        if self.weight_caches is not None:
            # charge the weight-swap cost at submit (residency is decided
            # by where the batch lands, i.e. here, not inside the worker)
            load_s = self.weight_caches[idx].ensure(
                getattr(inv, "model", None))
            if load_s:
                if handle.t_finish is not None:
                    handle.t_finish += load_s
                    if handle.completion is not None:
                        handle.completion.t_finish += load_s
                else:
                    # async worker: finish time unknown until resolve;
                    # remember the debit and apply it there
                    handle.load_s += load_s
        self.outstanding[idx] += 1
        self.n_submitted[idx] += 1
        self.n_patches[idx] += len(inv.patches)
        return handle

    def ready(self, handle: ExecHandle) -> bool:
        probe = getattr(self.workers[handle.worker], "ready", None)
        if probe is None:
            return handle.completion is not None
        return probe(handle)

    def resolve(self, handle: ExecHandle) -> Completion:
        comp = self.workers[handle.worker].resolve(handle)
        w = handle.worker
        comp.worker = w
        if handle.load_s:
            comp.t_finish += handle.load_s
            handle.load_s = 0.0
        self.outstanding[w] -= 1
        elapsed = comp.t_finish - comp.invocation.t_submit
        if math.isfinite(elapsed) and elapsed > 0:
            # busy time is the union of the worker's service intervals: a
            # queued invocation's interval starts where the previous one
            # finished, so overlapped in-flight work is not double-counted
            # (utilization = busy_s / horizon must stay <= 1)
            start = max(comp.invocation.t_submit, self._last_finish[w])
            self.busy_s[w] += max(0.0, comp.t_finish - start)
            self._last_finish[w] = max(self._last_finish[w], comp.t_finish)
        if self.estimator is not None:
            # the estimator deliberately sees submit->finish elapsed
            # (including queueing on the worker): that is the quantity
            # t_slack must cover for the firing decision to be safe
            batch = (len(comp.invocation.canvases)
                     or len(comp.invocation.patches))
            model = getattr(comp.invocation, "model", None)
            if model is not None:
                # pass the model only when tagged: duck-typed estimators
                # predating multi-model need not accept the kwarg
                self.estimator.observe(batch, elapsed, worker=w,
                                       model=model)
            else:
                self.estimator.observe(batch, elapsed, worker=w)
        return comp

    def on_complete(self, comp: Completion):
        on_complete = getattr(self.workers[comp.worker], "on_complete", None)
        if on_complete is not None:
            on_complete(comp)

    # ---------------------------------------------- frame store facade ----

    def add_frame(self, frame_id, pixels, n_patches: int):
        """Register a frame once; device workers share one store (see
        :func:`share_frame_store`), so worker 0's store is the store."""
        self.workers[0].add_frame(frame_id, pixels, n_patches)

    @property
    def frames(self):
        return self.workers[0].frames

    # --------------------------------------------------- aggregation ----

    def _sum(self, attr: str) -> int:
        return sum(getattr(w, attr, 0) for w in self.workers)

    @property
    def n_invocations(self) -> int:
        return self._sum("n_invocations")

    @property
    def n_detections(self) -> int:
        return self._sum("n_detections")

    @property
    def n_sharded(self) -> int:
        return self._sum("n_sharded")

    @property
    def n_fused(self) -> int:
        return self._sum("n_fused")

    @property
    def n_host_stitched(self) -> int:
        return self._sum("n_host_stitched")

    @property
    def evidence_bytes(self) -> int:
        return self._sum("evidence_bytes")

    @property
    def bytes_to_device(self) -> int:
        return self._sum("bytes_to_device")

    @property
    def bytes_from_device(self) -> int:
        return self._sum("bytes_from_device")

    @property
    def slot_pixels(self) -> int:
        return self._sum("slot_pixels")

    @property
    def live_pixels(self) -> int:
        return self._sum("live_pixels")

    def worker_stats(self) -> List[dict]:
        """Per-worker counters for ``Results.worker_stats`` / benchmarks."""
        stats = []
        for i in range(self.n_workers):
            ws = {"worker": i,
                  "invocations": self.n_submitted[i],
                  "patches": self.n_patches[i],
                  "busy_s": round(self.busy_s[i], 4)}
            if self.estimator is not None:
                ws["drift"] = round(self.estimator.drift(worker=i), 3)
            if self.weight_caches is not None:
                ws["weights"] = self.weight_caches[i].stats()
            stats.append(ws)
        return stats

    def model_cache_stats(self) -> Dict[str, dict]:
        """Pool-wide per-model weight-cache counters (empty without
        caches): hits/misses aggregated over every worker's cache."""
        if self.weight_caches is None:
            return {}
        out: Dict[str, dict] = {}
        for cache in self.weight_caches:
            for name in set(cache.hits) | set(cache.misses):
                row = out.setdefault(name, {"weight_hits": 0,
                                            "weight_misses": 0})
                row["weight_hits"] += cache.hits.get(name, 0)
                row["weight_misses"] += cache.misses.get(name, 0)
        for row in out.values():
            total = row["weight_hits"] + row["weight_misses"]
            row["weight_hit_rate"] = (round(row["weight_hits"] / total, 4)
                                      if total else 0.0)
        return out


def share_frame_store(executors: Sequence[object]) -> None:
    """Alias one refcounted frame store across device executors.

    Patches cut from one frame may be routed by different workers; with
    per-worker stores each worker's refcount would never drain (worker A
    cannot see the decrements worker B's completions perform).  Sharing
    the store keeps `DeviceExecutor.on_complete`'s eviction exact: the
    frame disappears when the *pool-wide* last patch is routed.  The
    store is the striped-lock :class:`~repro.core.framestore.FrameStore`,
    so the sharing is also safe across the parallel fleet runtime's
    shard threads; duck-typed executors that predate the store (bare
    ``frames`` / ``_refs`` dicts) still get the historical dict
    aliasing."""
    if not executors:
        return
    head = executors[0]
    store = getattr(head, "store", None)
    for ex in executors[1:]:
        if store is not None and hasattr(ex, "store"):
            ex.store = store
        else:
            ex.frames = head.frames
            ex._refs = head._refs


def device_worker_pool(n_workers: int, make_executor: Callable[[int], object],
                       placement=None, estimator=None,
                       weight_caches: Optional[Sequence[WeightCache]] = None
                       ) -> WorkerPoolExecutor:
    """Build a device pool: ``make_executor(i)`` constructs worker ``i``
    (typically an ``AsyncDeviceExecutor`` over mesh slice ``i``); the
    frame stores are shared and the pool assembled."""
    workers = [make_executor(i) for i in range(n_workers)]
    share_frame_store(workers)
    return WorkerPoolExecutor(workers, placement=placement,
                              estimator=estimator,
                              weight_caches=weight_caches)
