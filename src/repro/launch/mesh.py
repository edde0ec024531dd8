"""Mesh construction for every launch surface.

FUNCTIONS, not module-level constants: importing this module never touches
jax device state, so smoke tests keep their 1-device world.  All factories
route through the compat layer (``repro.compat.shardingx``), the one place
the tree calls JAX's mesh API.
"""
from __future__ import annotations

import jax

from repro.compat import shardingx


def make_production_mesh(*, multi_pod: bool = False):
    """16x16 = 256 chips per pod; multi_pod stacks 2 pods (512 chips)."""
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return shardingx.make_mesh(shape, axes)


def make_test_mesh(*, multi_pod: bool = False):
    """Small stand-in meshes for CI (8 fake host devices)."""
    shape = (2, 2, 2) if multi_pod else (2, 4)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return shardingx.make_mesh(shape, axes)


def make_unit_mesh():
    """1x1 (data, model) mesh for single-device smoke tests: the same
    rule tables resolve, every axis collapses to size 1."""
    return shardingx.make_mesh((1, 1), ("data", "model"))


def make_serve_mesh(n_devices: int | None = None):
    """Data-parallel serving mesh over the local device set.

    The canvas batch shards its leading axis over "data"; "model" is kept
    (size 1) so the standard rule tables resolve unchanged.  On a
    1-device world this degenerates to the unit mesh and sharding is a
    no-op — the serve driver runs identically either way.
    """
    devices = jax.devices()
    n = n_devices if n_devices is not None else len(devices)
    return shardingx.make_mesh((n, 1), ("data", "model"),
                               devices=devices[:n])


def make_worker_meshes(n_workers: int, devices=None):
    """Split the device set into ``n_workers`` independent serve meshes.

    Each worker mesh is a contiguous (data, model) slice the shape of
    :func:`make_serve_mesh`, so per-worker executors shard their canvas
    batches data-parallel *within* their slice while the worker pool
    routes concurrent invocations *across* slices.  With fewer devices
    than workers the devices are reused round-robin (worker i pins device
    ``i % n_devices`` — on a 1-device host every worker degenerates to
    the unit mesh and the pool still exercises the full routing path).
    Leftover devices (``n_devices % n_workers``) are left unused so every
    worker has identical capacity and the latency profile of one worker
    holds for all.
    """
    if n_workers < 1:
        raise ValueError(f"n_workers must be >= 1, got {n_workers}")
    devices = list(jax.devices()) if devices is None else list(devices)
    if len(devices) >= n_workers:
        per = len(devices) // n_workers
        slices = [devices[i * per:(i + 1) * per] for i in range(n_workers)]
    else:
        slices = [[devices[i % len(devices)]] for i in range(n_workers)]
    return [shardingx.make_mesh((len(sl), 1), ("data", "model"), devices=sl)
            for sl in slices]


def mesh_chips(mesh) -> int:
    return mesh.devices.size
