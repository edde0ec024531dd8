"""In-process accelerator stand-in for overlap tests and benchmarks.

A real accelerator accepts a dispatch, queues it behind earlier work, and
crunches without consuming host CPU; the host only stalls when it *joins*
a result.  :class:`StubAccelerator` reproduces exactly that contract with
a single worker thread (a serial device queue) and a fixed per-invocation
service time, so engine-level overlap measurements are deterministic and
independent of how fast the host's XLA happens to be:

* ``serve_fn(params, canvases)`` enqueues one detector call and
  immediately returns :class:`DeviceFuture` handles — the same shape
  contract as the jit'd detector (objectness ``(B, s, s)``, boxes
  ``(B, s, s, 4)``).
* ``DeviceFuture.is_ready()`` / ``result()`` mirror ``jax.Array``'s
  readiness probe and ``block_until_ready`` join, so
  ``AsyncDeviceExecutor`` drives stub and real device identically.
* ``sync(tree)`` is the executor's ``sync`` hook: joins every
  ``DeviceFuture`` in the tree and ``block_until_ready``s any real JAX
  arrays alongside them.
"""
from __future__ import annotations

import concurrent.futures
import time
from typing import Callable, Tuple, Union

import numpy as np


class DeviceFuture:
    """One output of an in-flight stub call (duck-types a jax.Array)."""

    def __init__(self, fut: concurrent.futures.Future, idx: int):
        self._fut = fut
        self._idx = idx

    def is_ready(self) -> bool:
        return self._fut.done()

    def result(self) -> np.ndarray:
        return self._fut.result()[self._idx]

    def __array__(self, dtype=None):
        a = np.asarray(self.result())
        return a.astype(dtype) if dtype is not None else a


class StubAccelerator:
    """Serial device queue with a fixed per-invocation service time."""

    def __init__(self, service_s: float, grid: int = 2):
        self.service_s = service_s
        self.grid = grid
        self.n_calls = 0
        self._pool = concurrent.futures.ThreadPoolExecutor(max_workers=1)

    def serve_fn(self, params, canvases) -> Tuple[DeviceFuture, DeviceFuture]:
        b = int(canvases.shape[0])
        self.n_calls += 1
        fut = self._pool.submit(self._run, b, canvases)
        return DeviceFuture(fut, 0), DeviceFuture(fut, 1)

    def _run(self, b: int, canvases):
        # causal ordering of a real detector: service cannot start before
        # the input batch exists — join the (possibly still-dispatching)
        # stitched canvases first, off the caller's thread
        try:
            import jax
            jax.block_until_ready(canvases)
        except ImportError:            # plain numpy input
            pass
        time.sleep(self.service_s)
        s = self.grid
        return (np.zeros((b, s, s), np.float32),
                np.zeros((b, s, s, 4), np.float32))

    def sync(self, tree) -> None:
        import jax

        leaves = jax.tree_util.tree_leaves(
            tree, is_leaf=lambda v: isinstance(v, DeviceFuture))
        for leaf in leaves:
            if isinstance(leaf, DeviceFuture):
                leaf.result()
            else:
                jax.block_until_ready(leaf)

    def close(self) -> None:
        self._pool.shutdown(wait=True)

    def __enter__(self) -> "StubAccelerator":
        return self

    def __exit__(self, *exc) -> None:
        self.close()


class VirtualAccelerator:
    """Engine-time device stand-in: a submit/complete executor whose
    finish times live on the *engine* clock, not the wall clock.

    ``service`` is seconds per invocation — a float, or a callable
    ``service(batch_size) -> seconds`` for batch-dependent devices.  The
    executor models one serial device queue: an invocation starts when
    both it has been submitted and the queue is free, and finishes
    ``service`` later, so ``t_finish = max(t_submit, queue_free) +
    service``.  No threads, no sleeps — drifted-device and placement
    tests stay exactly reproducible under the virtual clock (a real
    ``StubAccelerator`` would race engine virtual time against wall
    sleeps).

    Handles carry their finish time at submit, so the engine schedules
    delivery on the event heap — the device analogue of ``SimExecutor``'s
    "the model tells us now, the event fires later".
    """

    def __init__(self, service: Union[float, Callable[[int], float]]):
        self.service = service
        self.queue_free = 0.0
        self.n_calls = 0
        self.per_batch: list = []      # (t_submit, batch, t_finish) log

    def _service_s(self, batch: int) -> float:
        if callable(self.service):
            return float(self.service(batch))
        return float(self.service)

    def submit(self, inv) -> "ExecHandle":
        from repro.core.engine import Completion, ExecHandle

        batch = len(inv.canvases) or len(inv.patches)
        start = max(inv.t_submit, self.queue_free)
        t_finish = start + self._service_s(batch)
        self.queue_free = t_finish
        self.n_calls += 1
        self.per_batch.append((inv.t_submit, batch, t_finish))
        return ExecHandle(inv, t_finish=t_finish,
                          completion=Completion(inv, t_finish))

    def resolve(self, handle) -> "Completion":
        return handle.completion
