"""The check that decides ``correct``, driven through a whole CPU-sized
window: the program passes it, the lower-precision control fails it, and
so does each fault the cell can have, planted in the timed path."""
import numpy as np
import pytest

from bench import calibrate, run

SEEDS = (2**31 + 101, 2**31 + 102)
SECONDS = 3.0


def window(h, seed):
    h.reseed(seed)
    return h.window(seed, SECONDS)


@pytest.mark.parametrize("seed", SEEDS)
def test_program_passes_and_fp8_control_fails(tiny_harness, seed):
    h = tiny_harness
    r, kept = window(h, seed)
    ok, checks = run.judge(h.check(kept, r), h.config)
    assert ok, checks
    fp8 = calibrate.controls(h)["fp8"]
    numbers = h.check(kept, r, control=fp8)
    assert numbers["obj_gap"] > 2 * checks["obj_gap"]["limit"] \
        or numbers["box_gap"] > 2 * checks["box_gap"]["limit"]
    numbers.update(placement_faults=0, evidence_mismatch=0,
                   route_mismatch=0, undelivered=0)
    ok, _ = run.judge(numbers, h.config)
    assert not ok


def test_the_fused_path_passes(tiny_harness, monkeypatch):
    """With ``ServeConfig``'s fused path on, the check compares the raw
    head outputs it runs the trunk to, and its routing and evidence."""
    h = tiny_harness
    monkeypatch.setitem(h.defaults, "fuse", True)
    r, kept = window(h, SEEDS[0])
    assert kept and all(k["out"][0] == "raw" for k in kept.values())
    numbers = h.check(kept, r)
    ok, checks = run.judge(numbers, h.config)
    assert ok, checks
    assert numbers["compared_invocations"] == len(kept)


def _half_batch(fn):
    """Half the canvases left out, their outputs the mean of the rest."""
    def serve(p, x):
        obj, boxes = fn(p, x)
        keep = max(1, x.shape[0] // 2)
        if keep == x.shape[0]:
            return obj, boxes
        fill = lambda a: a.at[keep:].set(a[:keep].mean(0))
        return fill(obj), fill(boxes)
    return serve


def _altered_answer(fn):
    """One objectness the trunk produced, moved by 0.5."""
    def serve(p, x):
        obj, boxes = fn(p, x)
        v = obj[0, 0, 0]
        return obj.at[0, 0, 0].set(np.where(v > 0.5, v - 0.5, v + 0.5)), boxes
    return serve


def _altered_raw(fused_kwargs):
    """The fused path's trunk, with one raw objectness moved by 5."""
    def make(*a, **k):
        out = fused_kwargs(*a, **k)
        fn = out["tokens_fn"]
        out["tokens_fn"] = lambda p, t: fn(p, t).at[0, 0, 0, 0].add(5.0)
        return out
    return make


def _moved_box(route):
    """One routed detection's box moved by a pixel."""
    def moved(*a, **k):
        out = route(*a, **k)
        for dets in out.values():
            dets[-1] = (dets[-1][0], tuple(v + 1.0 for v in dets[-1][1]))
            break
        return out
    return moved


@pytest.mark.parametrize("fault", ["half_batch", "altered_answer",
                                   "altered_route", "altered_evidence",
                                   "fused_altered_answer",
                                   "fused_altered_route"])
def test_a_planted_fault_is_not_correct(tiny_harness, monkeypatch, fault):
    from bench import harness
    from repro.kernels.stitch import ops as stitch_ops

    h = tiny_harness
    if fault.startswith("fused_"):
        monkeypatch.setitem(h.defaults, "fuse", True)
    if fault == "fused_altered_answer":
        monkeypatch.setattr(harness, "fused_kwargs",
                            _altered_raw(harness.fused_kwargs))
    elif fault == "fused_altered_route":
        monkeypatch.setattr(stitch_ops, "route_fused",
                            _moved_box(stitch_ops.route_fused))
    elif fault == "half_batch":
        monkeypatch.setattr(h, "serve_fn", _half_batch(h.serve_fn))
    elif fault == "altered_answer":
        monkeypatch.setattr(h, "serve_fn", _altered_answer(h.serve_fn))
    elif fault == "altered_route":
        monkeypatch.setattr(stitch_ops, "route_detections",
                            _moved_box(stitch_ops.route_detections))
    else:
        unstitch = stitch_ops.unstitch_patches

        def bump(*a, **k):
            return unstitch(*a, **k).at[0, 0, 0, 0].add(1.0)
        monkeypatch.setattr(stitch_ops, "unstitch_patches", bump)
    r, kept = window(h, SEEDS[0])
    numbers = h.check(kept, r)
    ok, checks = run.judge(numbers, h.config)
    assert not ok, checks
    failing = {k for k, c in checks.items() if c["value"] > c["limit"]}
    want = {"half_batch": {"obj_gap", "box_gap"},
            "altered_answer": {"obj_gap"},
            "altered_route": {"route_mismatch"},
            "altered_evidence": {"evidence_mismatch"},
            "fused_altered_answer": {"obj_gap"},
            "fused_altered_route": {"route_mismatch"}}[fault]
    assert failing & want, checks
