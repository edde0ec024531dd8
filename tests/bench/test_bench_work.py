"""The benchmark's own byte counts and roofline bound."""
from types import SimpleNamespace

import pytest

from bench import work
from bench.trunks import vit

TANGRAM = dict(canvas=1024, patch=32, n_layers=12, d_model=768, n_heads=12,
               d_ff=3072)


def test_roofline_bound():
    peak = {"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9}
    four = SimpleNamespace(canvases=4)
    t, bound = work.roofline_seconds(vit.flops(TANGRAM, four),
                                     vit.bytes(TANGRAM, four), peak)
    assert bound == "compute"
    assert t == pytest.approx(4 * 217.4e9 / 197e12, rel=1e-3)
    t, bound = work.roofline_seconds(
        0.0, work.stitch_bytes(1000, 1, 1024), peak)
    assert bound == "memory"
    assert t == pytest.approx((1000 + 1024 * 1024) * 12 / 819e9)
