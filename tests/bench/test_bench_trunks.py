"""The trunk hook: a configuration names its trunk module, the harness
hands it each invocation's placements, the readers count through it; and
the ViT module's counts and live tokens."""
import json
import math
from types import SimpleNamespace

import numpy as np
import pytest

from bench import harness
from bench.trunks import vit

TANGRAM = dict(canvas=1024, patch=32, n_layers=12, d_model=768, n_heads=12,
               d_ff=3072)
VIT_S16 = dict(canvas=1024, patch=16, n_layers=12, d_model=384, n_heads=6,
               d_ff=1536)
ONE = SimpleNamespace(canvases=1)


@pytest.mark.parametrize("arch", [
    dict(canvas=128, patch=16, n_layers=2, d_model=128, n_heads=4, d_ff=512),
    dict(canvas=256, patch=32, n_layers=2, d_model=256, n_heads=4,
         d_ff=1024),
])
def test_trunk_flops_match_xla_cost_analysis(arch):
    """XLA's count of the compiled forward (layers unrolled, since it
    counts a loop's body once) adds only the element-wise work to the
    ViT module's count of matrix products."""
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
    ratio = cost["flops"] / vit.flops(arch, SimpleNamespace(canvases=2))
    assert 1.0 <= ratio < 1.05
    n = sum(math.prod(a.shape) for a in jax.tree_util.tree_leaves(params))
    assert vit.params(arch) == n


def test_served_trunks():
    # ViT-B/32 on a 1024^2 canvas: 217 GFLOP; ViT-S/16: 486, attention 309
    assert vit.flops(TANGRAM, ONE) == pytest.approx(217.4e9, rel=1e-3)
    assert vit.flops(VIT_S16, ONE) == pytest.approx(486.0e9, rel=1e-3)
    s = (1024 // 16) ** 2
    assert 12 * 4 * s * s * 384 == pytest.approx(309e9, rel=1e-2)


def test_the_programs_count_doubles_the_mlp():
    from repro.core.latency import detector_flops

    a = TANGRAM
    theirs = detector_flops(1024, a["patch"], a["n_layers"], a["d_model"],
                            a["d_ff"])
    assert theirs / vit.flops(a, ONE) == pytest.approx(1.54, abs=0.01)


@pytest.mark.parametrize("trunk, error", [
    (None, "lacks 'trunk'"),
    ("nope", "no trunk module"),
    ("../trunks/vit", "no trunk module"),
], ids=["missing", "unknown", "path"])
def test_load_config_refuses_a_missing_or_unknown_trunk(tmp_path,
                                                        monkeypatch, trunk,
                                                        error):
    cfg = json.loads((harness.CONFIG_DIR / "tangram.json").read_text())
    if trunk is None:
        del cfg["trunk"]
    else:
        cfg["trunk"] = trunk
    (tmp_path / "bad.json").write_text(json.dumps(cfg))
    monkeypatch.setattr(harness, "CONFIG_DIR", tmp_path)
    with pytest.raises(ValueError, match=error):
        harness.load_config("bad")


def test_live_tokens_match_a_hand_count():
    """Two placements on a 128-pixel canvas of 16-pixel tokens (an 8 x 8
    grid): (0, 0) 20 x 20 covers rows and columns 0-1, 4 tokens; (24, 24)
    20 x 20 covers rows and columns 1-2, 4 tokens, one of them (1, 1)
    shared; an invalid record counts nothing.  7 tokens."""
    arch = dict(canvas=128, patch=16)
    records = np.array([[[1, 0, 0, 0, 20, 20],
                         [1, 1, 24, 24, 20, 20],
                         [0, 0, 64, 64, 64, 64]]])
    mask = vit.live_mask(records, arch)
    assert mask.shape == (1, 8, 8) and mask.sum() == 7
    assert mask[0, :3, :3].sum() == 7 and not mask[0, 0, 2]
    # the same placements on two canvases count on each
    two = vit.live_mask(np.concatenate([records, records]), arch)
    assert two.sum() == 14 and (two[0] == two[1]).all()


def test_a_window_records_its_live_tokens(tiny_harness):
    """Every invocation of a CPU window carries its plan's placements, one
    row of records a canvas, from which a trunk module counts its live
    tokens: enough to hold the placed pixels, no more than the canvases
    hold."""
    h = tiny_harness
    h.reseed(2**31 + 11)
    run, _kept = h.window(2**31 + 11, 2.0, checked=False)
    p, side = h.arch["patch"], h.arch["canvas"] // h.arch["patch"]
    assert run.trunk is h.trunk
    assert run.invocations
    for r in run.invocations:
        assert r.records.shape[0] == r.canvases
        assert int(np.sum(r.records[..., 0] > 0)) == r.patches
        live = int(vit.live_mask(r.records, h.arch).sum())
        assert r.used_area <= live * p * p
        assert live <= r.canvases * side * side


def _counting_trunk(seen):
    """A trunk module whose counts follow the record's live tokens."""
    def flops(arch, rec):
        seen.append(("flops", rec.ordinal))
        return 1e9 * vit.live_mask(rec.records, arch).sum()

    def nbytes(arch, rec):
        seen.append(("bytes", rec.ordinal))
        return 1e6

    return SimpleNamespace(flops=flops, bytes=nbytes)


def _placements(n_tokens):
    """One canvas of 32-pixel tokens with ``n_tokens`` of them covered, a
    placement of one token each."""
    return np.array([[[1, i, 32 * (i % 32), 32 * (i // 32), 32, 32]
                      for i in range(n_tokens)]])


@pytest.mark.parametrize("name, want, calls", [
    # the traced invocation: 200 tokens, 2e11 operations over 10 ms
    ("trunk_roofline", 100.0 * 2e11 / 197e12 / 0.01,
     [("flops", 1), ("bytes", 1)]),
    # the untraced one: 100 tokens, 1e11 operations over 0.5 s billed
    ("step_mfu", 100.0 * 1e11 / (0.5 * 197e12), [("flops", 0)]),
])
def test_the_roofline_readers_count_through_the_trunk_module(name, want,
                                                              calls):
    from bench.metrics import reader
    from bench.peaks import peaks

    seen = []
    invs = [SimpleNamespace(ordinal=i, canvases=1,
                            records=_placements(100 * (i + 1)),
                            traced=i == 1, t_start=0.0, t_done=0.5)
            for i in range(2)]
    run = SimpleNamespace(invocations=invs, arch=dict(canvas=1024, patch=32),
                          peak=peaks("TPU v5 lite"),
                          trunk=_counting_trunk(seen),
                          trace={"kernel_s": {"trunk": 0.01}})
    assert reader(name)(run) == pytest.approx(want)
    assert seen == calls
