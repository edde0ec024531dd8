"""The benchmark's own operation and byte counts."""
import math

import pytest

from bench import work

TANGRAM = dict(canvas=1024, patch=32, n_layers=12, d_model=768, n_heads=12,
               d_ff=3072)
VIT_S16 = dict(canvas=1024, patch=16, n_layers=12, d_model=384, n_heads=6,
               d_ff=1536)


@pytest.mark.parametrize("arch", [
    dict(canvas=128, patch=16, n_layers=2, d_model=128, n_heads=4, d_ff=512),
    dict(canvas=256, patch=32, n_layers=2, d_model=256, n_heads=4,
         d_ff=1024),
])
def test_trunk_flops_match_xla_cost_analysis(arch):
    """XLA's count of the compiled forward (layers unrolled, since it
    counts a loop's body once) adds only the element-wise work to the
    benchmark's count of matrix products."""
    import jax
    import jax.numpy as jnp

    from repro.config import DetectorConfig
    from repro.models import detector
    from repro.param import abstract_params
    from repro.sharding import ShardingConfig

    cfg = DetectorConfig(name="t", param_dtype="float32",
                         compute_dtype="float32", scan_layers=False, **arch)
    rules = ShardingConfig.make().rules
    params = abstract_params(detector.param_specs(cfg))
    x = jax.ShapeDtypeStruct((2, arch["canvas"], arch["canvas"], 3),
                             jnp.float32)
    compiled = jax.jit(lambda p, c: detector.forward(cfg, p, c, rules)) \
        .lower(params, x).compile()
    cost = compiled.cost_analysis()
    cost = cost[0] if isinstance(cost, list) else cost
    ratio = cost["flops"] / (2 * work.trunk_flops(arch))
    assert 1.0 <= ratio < 1.05
    n = sum(math.prod(a.shape) for a in jax.tree_util.tree_leaves(params))
    assert work.trunk_params(arch) == n


def test_served_trunks():
    # ViT-B/32 on a 1024^2 canvas: 217 GFLOP; ViT-S/16: 486, attention 309
    assert work.trunk_flops(TANGRAM) == pytest.approx(217.4e9, rel=1e-3)
    assert work.trunk_flops(VIT_S16) == pytest.approx(486.0e9, rel=1e-3)
    s = (1024 // 16) ** 2
    assert 12 * 4 * s * s * 384 == pytest.approx(309e9, rel=1e-2)


def test_the_programs_count_doubles_the_mlp():
    from repro.core.latency import detector_flops

    a = TANGRAM
    theirs = detector_flops(1024, a["patch"], a["n_layers"], a["d_model"],
                            a["d_ff"])
    assert theirs / work.trunk_flops(a) == pytest.approx(1.54, abs=0.01)


def test_roofline_bound():
    peak = {"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9}
    t, bound = work.roofline_seconds(work.trunk_flops(TANGRAM) * 4,
                                     work.trunk_bytes(TANGRAM, 4), peak)
    assert bound == "compute"
    assert t == pytest.approx(4 * 217.4e9 / 197e12, rel=1e-3)
    t, bound = work.roofline_seconds(
        0.0, work.stitch_bytes(1000, 1, 1024), peak)
    assert bound == "memory"
    assert t == pytest.approx((1000 + 1024 * 1024) * 12 / 819e9)
