"""The traffic generator, at the mixes' own 4K frame size."""
import collections

import numpy as np
import pytest

from bench import traffic

SEED = 2**31 + 977


def arrivals(name, seed=SEED, seconds=6.0, scale=1.0):
    return traffic.generate(traffic.load_mix(name), seed, seconds, 1024,
                            fps_scale=scale).arrivals


def test_same_seed_same_arrivals():
    assert arrivals("crowd4k") == arrivals("crowd4k")


def test_seeds_reorder_the_same_work():
    """A seed moves the cameras' phases, and so how their frames
    interleave; each camera sends the same patches in the same order."""
    a, b = arrivals("crowd4k"), arrivals("crowd4k", seed=SEED + 1)
    assert a != b
    per_camera = lambda arr: {
        cam: [(p.frame_id, p.x0, p.y0, p.x1, p.y1) for _, p in
              sorted(arr, key=lambda x: (x[1].t_gen, x[0]))
              if p.camera_id == cam] for cam in range(4)}
    assert per_camera(a) == per_camera(b)


@pytest.mark.parametrize("name", ["crowd4k", "sparse4k"])
def test_each_camera_sends_its_frames_in_order_at_its_period(name):
    mix = traffic.load_mix(name)
    period = 1.0 / mix["fps_per_camera"]
    for cam in range(len(mix["scenes"])):
        gen = {}
        for _, p in arrivals(name):
            if p.camera_id == cam:
                gen.setdefault(traffic.frame_index(p.frame_id), p.t_gen)
        ks = sorted(gen)
        assert ks == list(range(len(ks)))
        assert 0.0 <= gen[0] < period
        assert np.diff([gen[k] for k in ks]) == pytest.approx(period)


@pytest.mark.parametrize("name", ["crowd4k", "sparse4k"])
def test_rate_scales_with_the_frame_rate(name):
    one = arrivals(name, seconds=8.0)
    two = arrivals(name, seconds=8.0, scale=2.0)
    frames = lambda arr: len({p.frame_id for _, p in arr})
    mix = traffic.load_mix(name)
    assert frames(one) == pytest.approx(
        8.0 * mix["fps_per_camera"] * len(mix["scenes"]), abs=len(
            mix["scenes"]))
    assert frames(two) == pytest.approx(2 * frames(one), rel=0.1)
    assert len(two) == pytest.approx(2 * len(one), rel=0.25)


def test_crowd_pads_to_larger_slots_than_sparse():
    def extents(name):
        by_frame = collections.defaultdict(list)
        for _, p in arrivals(name, seconds=10.0):
            by_frame[p.frame_id].append(p)
        return np.mean([np.prod(traffic.slot_extents(ps, 1024))
                        for ps in by_frame.values()])

    assert extents("crowd4k") > 1.5 * extents("sparse4k")


@pytest.mark.parametrize("name", ["crowd4k", "sparse4k"])
def test_patches_stay_inside_the_frame_and_the_canvas(name):
    mix = traffic.load_mix(name)
    arr = arrivals(name)
    assert arr
    for t_arr, p in arr:
        assert 0 <= p.x0 < p.x1 <= mix["frame_w"]
        assert 0 <= p.y0 < p.y1 <= mix["frame_h"]
        assert p.w <= 1024 and p.h <= 1024
        assert t_arr > p.t_gen
    times = [t for t, _ in arr]
    assert times == sorted(times)


def test_uplink_is_a_fifo_per_camera():
    link = traffic.Uplink(8e6)                      # 1 MB/s
    p = traffic.Patch(0, 0, 64, 64, t_gen=0.0)
    first = link.send(p)
    assert first == pytest.approx(traffic.patch_bytes(p) / 1e6)
    assert link.send(p) == pytest.approx(2 * first)
