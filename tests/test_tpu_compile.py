"""Compile the main serve path for one chip of a described TPU v5e.

Nothing runs: each test lowers and compiles for ``v5e:2x2`` device 0
through the TPU compiler, which refuses what the chip would refuse
(unsupported Mosaic lowerings, unaligned dynamic slices, VMEM overflow)
at no chip time.  Shapes are the serving ones: 1024² canvases, 512-pixel
patch slots, B=4 canvases of K=8 placements over P=32 slots, and the
full-width ``tangram`` detector.

The topology is described inside a module fixture, never at import:
only one process may load the TPU library at a time, and every test
worker imports this file.
"""
import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro import param as param_lib
from repro.core.models import make_model
from repro.kernels.stitch import ops
from repro.models import detector as detector_lib

B, K, P, CANVAS, SLOT, PATCH, D = 4, 8, 32, 1024, 512, 32, 768


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture(scope="module", autouse=True)
def no_persistent_cache():
    """A compile for a described chip cannot be read back without one:
    keep the persistent cache out of these compiles."""
    from jax.experimental.compilation_cache import compilation_cache
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield
    jax.config.update("jax_enable_compilation_cache", was)
    compilation_cache.reset_cache()


def _compile(fn, *shapes):
    return jax.jit(fn).lower(*shapes).compile()


KERNELS = {
    "stitch": (lambda s, r: ops.stitch_canvases(s, r, CANVAS, CANVAS,
                                                impl="pallas"),
               [((P, SLOT, SLOT, 3), jnp.float32), ((B, K, 6), jnp.int32)]),
    "unstitch": (lambda c, r: ops.unstitch_patches(c, r, P, SLOT, SLOT,
                                                   impl="pallas"),
                 [((B, CANVAS, CANVAS, 3), jnp.float32),
                  ((B, K, 6), jnp.int32)]),
    "stitch_embed": (lambda s, r, w, b: ops.stitch_embed(
        s, r, w, b, CANVAS, CANVAS, PATCH, impl="pallas"),
        [((P, SLOT, SLOT, 3), jnp.float32), ((B, K, 6), jnp.int32),
         ((PATCH * PATCH * 3, D), jnp.bfloat16), ((D,), jnp.bfloat16)]),
    "unstitch_decode": (lambda raw, r: ops.unstitch_decode(
        raw, r, PATCH, P, impl="pallas"),
        [((B, CANVAS // PATCH, CANVAS // PATCH, 5), jnp.bfloat16),
         ((B, K, 6), jnp.int32)]),
}


@pytest.mark.parametrize("name", sorted(KERNELS))
def test_stitch_kernel_compiles_for_v5e(name, one_chip):
    fn, shapes = KERNELS[name]
    compiled = _compile(fn, *(jax.ShapeDtypeStruct(s, dt, sharding=one_chip)
                              for s, dt in shapes))
    # the Pallas kernel itself is in the program, not an XLA fallback
    assert "tpu_custom_call" in compiled.as_text()


def test_full_width_tangram_serve_step_compiles_for_v5e(one_chip):
    """The XLA serve step of the paper's detector (ViT-B/32 trunk, bf16)
    on a batch of 8 canvases fits one chip."""
    spec = make_model("tangram")
    cfg = spec.arch
    assert (cfg.canvas, cfg.n_layers, cfg.d_model, cfg.param_dtype) == \
        (CANVAS, 12, 768, "bfloat16")
    from repro.sharding import ShardingConfig
    rules = ShardingConfig.make().rules
    params = jax.eval_shape(lambda: param_lib.init_params(
        jax.random.PRNGKey(0), detector_lib.param_specs(cfg)))
    params = jax.tree_util.tree_map(
        lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=one_chip),
        params)
    canvases = jax.ShapeDtypeStruct((8, CANVAS, CANVAS, 3), jnp.float32,
                                    sharding=one_chip)

    def step(p, x):
        return detector_lib.serve(cfg, p, x, rules)

    mem = _compile(step, params, canvases).memory_analysis()
    assert mem.argument_size_in_bytes + mem.temp_size_in_bytes < 16e9
