"""The served path, assembled from the program's public pieces as
``repro.launch.serve.main`` assembles it for one full-width registry
model, and the benchmark's own weights.

``serve.main`` cannot take a traffic source, so the harness builds the
path itself: the model through ``ModelSpec.build(reduced=False)``, the
latency table through ``core.latency.measure`` over batch sizes (1, 2, 4),
``uniform_pool``, ``make_executor`` with ``ServeConfig``'s own defaults
for ``use_pallas`` and ``fuse`` (a change of the default device path is
measured), and ``ServingEngine`` on a ``WallClock``.

The weights are the benchmark's: drawn from ``--seed`` on the device, in
one jitted call, in the dtype they are served in.  The program's own
initialiser is not used for them, so the plain reference compares with
nothing the program made.  Its parameter specs give only the tree, the
shapes and each leaf's kind (zeros, ones, a scaled normal).
"""
from __future__ import annotations

import dataclasses
import math

import numpy as np


def seed_key(seed: int):
    """A PRNG key from any non-negative whole number (wider than 32 bits)."""
    import jax

    state = np.random.SeedSequence(int(seed)).generate_state(2)
    key = jax.random.PRNGKey(int(state[0]) & 0x7FFFFFFF)
    return jax.random.fold_in(key, int(state[1]) & 0x7FFFFFFF)


def detector_arch(config: dict):
    from repro.config import DetectorConfig

    return DetectorConfig(name=config["name"], **config["arch"])


def check_registry(config: dict):
    """The file's widths are the registry model's: refuse a drift."""
    from repro.core.models import make_model

    reg = make_model(config["model"]).arch
    mine = detector_arch(config)
    for f in dataclasses.fields(mine):
        if f.name == "name":
            continue
        if getattr(reg, f.name) != getattr(mine, f.name):
            raise ValueError(
                f"configuration {config['name']!r}: {f.name} is "
                f"{getattr(mine, f.name)!r} here but "
                f"{getattr(reg, f.name)!r} in the registry model "
                f"{config['model']!r}")


def build_serve_fn(config: dict):
    """``(cfg, serve_fn, rules)`` through ``ModelSpec.build``.  The params
    that ``build`` makes are thrown away; they are made on the host CPU,
    where its leaf-by-leaf initialiser costs seconds and not a compile
    per leaf on the chip."""
    import jax

    from repro.core.models import ModelSpec

    spec = ModelSpec(name=config["model"], arch=detector_arch(config))
    try:
        host = jax.devices("cpu")[0]
    except RuntimeError:
        host = None
    if host is not None:
        with jax.default_device(host):
            cfg, _params, serve_fn, rules = spec.build(reduced=False)
    else:
        cfg, _params, serve_fn, rules = spec.build(reduced=False)
    del _params
    return cfg, serve_fn, rules


def make_weights(cfg, seed: int):
    """The detector's parameter tree, drawn from ``seed`` on the default
    device in one jitted call."""
    import jax
    import jax.numpy as jnp

    from repro.models import detector as detector_lib
    from repro.param import ParamSpec

    specs = detector_lib.param_specs(cfg)
    leaves, treedef = jax.tree_util.tree_flatten(
        specs, is_leaf=lambda x: isinstance(x, ParamSpec))

    def draw(key):
        out = []
        for i, s in enumerate(leaves):
            if s.init == "zeros":
                out.append(jnp.zeros(s.shape, s.dtype))
            elif s.init == "ones":
                out.append(jnp.ones(s.shape, s.dtype))
            else:
                if s.init == "normal":
                    axes = s.fan_in_axes or tuple(range(len(s.shape) - 1))
                    fan_in = math.prod(s.shape[a] for a in axes) or 1
                    std = s.scale / math.sqrt(fan_in)
                else:                         # learned embeddings
                    std = 0.02 * s.scale
                x = jax.random.normal(jax.random.fold_in(key, i), s.shape,
                                      jnp.float32) * std
                out.append(x.astype(s.dtype))
        return jax.tree_util.tree_unflatten(treedef, out)

    return jax.jit(draw)(seed_key(seed))


def serve_defaults() -> dict:
    """The device-path switches as ``ServeConfig`` defaults them."""
    from repro.core.config import ServeConfig

    d = ServeConfig()
    return {"use_pallas": d.use_pallas, "fuse": d.fuse,
            "max_inflight": d.max_inflight}


def fused_kwargs(cfg, params, rules, fuse: bool) -> dict:
    """``ModelRuntime`` fused-path fields, as ``serve.main`` builds them."""
    if not fuse:
        return {}
    import jax

    from repro.models import detector as detector_lib

    ek, eb = detector_lib.embed_params(cfg, params)
    tok = jax.jit(lambda p, t: detector_lib.forward_tokens(cfg, p, t, rules))
    return dict(tokens_fn=tok, embed_kernel=ek, embed_bias=eb,
                patch=cfg.patch)


def latency_table(serve_fn, params, cfg, mesh, rules, profile: dict):
    """The invoker's table, profiled as ``serve.main`` profiles it: the
    trunk alone over zero canvases already on the device."""
    import jax
    import jax.numpy as jnp

    from repro.core.engine import shard_canvases
    from repro.core.latency import measure

    m = cfg.canvas

    def run_batch(b):
        x = jnp.zeros((b, m, m, 3), jnp.float32)
        x, _ = shard_canvases(x, mesh, rules)
        return serve_fn(params, x)

    return measure(run_batch, batch_sizes=tuple(profile["batch_sizes"]),
                   iters=profile["iters"], warmup=profile["warmup"],
                   sync=jax.block_until_ready)
