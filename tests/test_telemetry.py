"""The serve path's span recorder and transfer counters
(``repro.core.telemetry``, ``DeviceExecutor``, ``ServingEngine``)."""
import json
import sys
import threading

import jax
import numpy as np
import pytest

from repro.core import telemetry as telemetry_lib
from repro.core.clock import WallClock
from repro.core.engine import (AsyncDeviceExecutor, Completion,
                               DeviceExecutor, ExecHandle, ServingEngine,
                               uniform_pool)
from repro.core.invoker import Invocation
from repro.core.latency import LatencyTable
from repro.core.partitioning import Patch
from repro.core.stitching import stitch
from repro.core.telemetry import Telemetry
from repro.data.video import Arrival
from repro.models import detector as detector_lib

M = N = 128
P = "tangram.executor."
#: the executor's spans: each root and its children, in the order they run
TREE = {P + "launch": [P + "gather", P + "pack", P + "put", P + "enqueue"],
        P + "finalize": [P + "sync", P + "fetch", P + "route"]}


@pytest.fixture(scope="module")
def tiny():
    from repro.launch.serve import build_detector

    cfg, params, serve_fn, rules = build_detector(canvas=M)
    ek, eb = detector_lib.embed_params(cfg, params)
    tok = jax.jit(lambda p, t: detector_lib.forward_tokens(cfg, p, t, rules))
    rng = np.random.default_rng(5)
    frames = {fid: np.asarray(rng.normal(size=(M, 2 * N, 3)), np.float32)
              for fid in (0, 1)}
    patches = [Patch(10, 10, 74, 74, frame_id=0),
               Patch(80, 20, 120, 60, frame_id=0),
               Patch(0, 0, 48, 48, frame_id=1),
               Patch(128, 64, 192, 128, frame_id=1)]
    fused = dict(fuse=True, tokens_fn=tok, embed_kernel=ek, embed_bias=eb,
                 patch=cfg.patch)
    return dict(params=params, serve_fn=serve_fn, frames=frames,
                patches=patches, fused=fused)


def run_one(tiny, cls=DeviceExecutor, fuse=False, telemetry=None):
    """One invocation of ``tiny``'s four patches through an executor."""
    kw = tiny["fused"] if fuse else {}
    if telemetry is not None:
        kw = dict(kw, telemetry=telemetry)
    ex = cls(tiny["serve_fn"], tiny["params"], M, N, **kw)
    patches = tiny["patches"]
    for fid, px in tiny["frames"].items():
        ex.add_frame(fid, px, sum(1 for p in patches if p.frame_id == fid))
    inv = Invocation(0.0, list(stitch(patches, M, N)), list(patches), 0.0,
                     "timer")
    ex.resolve(ex.submit(inv))
    return ex, inv


def plan_counts(inv, fused=False):
    """What the plan's shapes give: (pixels sent, live pixels, bytes to
    the device) of one invocation's float32 RGB pixel buffer and int32
    records.  The buffer is the canvas batch on the unfused path and the
    pow2-padded slots on the fused one."""
    plan = inv.batch_plan()
    if fused:
        sent_px = plan.slot_capacity * plan.hmax * plan.wmax
    else:
        sent_px = plan.num_canvases * plan.canvas_m * plan.canvas_n
    live_px = sum(p.h * p.w for p in inv.patches)
    records = np.asarray(plan.records)
    return sent_px, live_px, sent_px * 3 * 4 + records.size * 4


def test_disabled_recorder_records_nothing_and_counters_count(tiny):
    off = Telemetry()
    assert off.span("a") is off.span("b", inv=3, x=1)
    ex, inv = run_one(tiny)
    assert not ex.telemetry.enabled and ex.telemetry.spans == []
    slot_px, live_px, to_dev = plan_counts(inv)
    assert (ex.slot_pixels, ex.live_pixels, ex.bytes_to_device) == \
        (slot_px, live_px, to_dev)
    assert ex.bytes_from_device > 0 and ex.n_invocations == 1


@pytest.mark.parametrize("fuse", [False, True], ids=["canvas", "slots"])
def test_counters_count_the_buffer_each_path_sends(tiny, fuse):
    """The unfused path sends the canvas batch and counts an invocation
    in ``n_host_stitched``; the fused one sends the slots and counts it in
    ``n_fused``; the pack span names the layout."""
    tel = Telemetry(enabled=True)
    ex, inv = run_one(tiny, fuse=fuse, telemetry=tel)
    assert (ex.slot_pixels, ex.live_pixels, ex.bytes_to_device) == \
        plan_counts(inv, fused=fuse)
    assert (ex.n_host_stitched, ex.n_fused) == (int(not fuse), int(fuse))
    pack = [s[7] for s in tel.spans if s[3] == P + "pack"]
    assert pack == [{"layout": "slots" if fuse else "canvas"}]


@pytest.mark.parametrize("cls, fuse", [
    (DeviceExecutor, False), (DeviceExecutor, True),
    (AsyncDeviceExecutor, False)], ids=["unfused", "fused", "async"])
def test_span_tree_of_one_invocation(tiny, cls, fuse):
    tel = Telemetry(enabled=True)
    ex, _ = run_one(tiny, cls, fuse, telemetry=tel)
    by_id = {s[0]: s for s in tel.spans}
    names = sorted(s[3] for s in tel.spans)
    assert names == sorted(list(TREE) + sum(TREE.values(), []))
    assert len({s[2] for s in tel.spans}) == 1          # one invocation
    for sid, parent, _inv, name, t0, t1, thread, _ in tel.spans:
        assert t0 <= t1
        if name in TREE:
            assert parent is None
            continue
        up = by_id[parent]
        assert name in TREE[up[3]]
        assert up[4] <= t0 and t1 <= up[5] and up[6] == thread
    for root, children in TREE.items():
        starts = [next(s[4] for s in tel.spans if s[3] == c)
                  for c in children]
        assert starts == sorted(starts), root
    assert ex.n_fused == int(fuse)


@pytest.mark.parametrize("fuse", [False, True], ids=["unfused", "fused"])
def test_transfer_counters_are_the_arrays_nbytes(tiny, fuse):
    """Bytes to the device are the slots' and records' own; bytes from it
    are every device array the finalize fetches."""
    kw = tiny["fused"] if fuse else {}
    ex = DeviceExecutor(tiny["serve_fn"], tiny["params"], M, N, **kw)
    patches = tiny["patches"]
    for fid, px in tiny["frames"].items():
        ex.add_frame(fid, px, sum(1 for p in patches if p.frame_id == fid))
    inv = Invocation(0.0, list(stitch(patches, M, N)), list(patches), 0.0,
                     "timer")
    payload = ex._launch(inv)
    device = ([payload["fused"]] if fuse else
              [payload[k] for k in ("obj", "boxes", "patch_out")])
    want_from = sum(np.asarray(a).nbytes for a in device)
    ex._finalize(inv, payload)
    assert ex.bytes_to_device == plan_counts(inv, fused=fuse)[2]
    assert ex.bytes_from_device == want_from


def device_trace(n=24, seed=3):
    rng = np.random.default_rng(seed)
    ps = []
    for i in range(n):
        w, h = int(rng.integers(8, 64)), int(rng.integers(8, 64))
        ps.append(Patch(0, 0, w, h, frame_id=i // 3,
                        t_gen=round(float(rng.uniform(0, 4.0)), 3), slo=0.6))
    return sorted(ps, key=lambda p: p.t_gen)


def small_serve_fn(params, x):
    import jax.numpy as jnp
    return (jnp.zeros((x.shape[0], 2, 2)), jnp.zeros((x.shape[0], 2, 2, 4)))


def small_tokens_fn(params, tokens):
    import jax.numpy as jnp
    return jnp.zeros((tokens.shape[0], 2, 2, 5))


#: the fused path's fields for ``small_tokens_fn`` on 64 x 64 canvases
SMALL_FUSED = dict(fuse=True, tokens_fn=small_tokens_fn,
                   embed_kernel=np.zeros((32 * 32 * 3, 8), np.float32),
                   embed_bias=np.zeros((8,), np.float32), patch=32)


def test_xfer_and_slot_fill_equal_the_plans_per_invocation():
    """Per invocation, the spans' transfer and pixel attributes are what
    each plan's shapes give, and they sum to the executor's counters."""
    check_counts_per_invocation(fused=False)


def test_xfer_and_slot_fill_equal_the_plans_per_invocation_fused():
    check_counts_per_invocation(fused=True)


def check_counts_per_invocation(fused):
    tel = Telemetry(enabled=True)
    ex = DeviceExecutor(small_serve_fn, None, 64, 64, telemetry=tel,
                        **(SMALL_FUSED if fused else {}))
    trace = device_trace()
    for fid in {p.frame_id for p in trace}:
        ex.add_frame(fid, np.ones((64, 64, 3), np.float32),
                     sum(1 for p in trace if p.frame_id == fid))
    lat = LatencyTable({b: (0.1 * b, 0.01) for b in range(1, 33)},
                       slack_sigmas=3.0)
    eng = ServingEngine(uniform_pool(64, 64, lat), ex, telemetry=tel)
    eng.run([Arrival(p.t_gen, p, 0.0) for p in trace])
    rows = tel.invocations()
    assert len(eng.invocations) > 1
    assert sorted(rows) == list(range(len(eng.invocations)))
    for i, inv in enumerate(eng.invocations):
        row = rows[i]
        assert (row["slot_pixels"], row["live_pixels"],
                row["bytes_to_device"]) == plan_counts(inv, fused=fused)
        assert row["patches"] == len(inv.patches)
        assert row["t_fire"] == inv.t_submit
        assert len(row["arrivals"]) == len(inv.patches)
        assert max(row["arrivals"]) <= row["t_fire"]
    for attr in ("slot_pixels", "live_pixels", "bytes_to_device",
                 "bytes_from_device"):
        assert sum(r[attr] for r in rows.values()) == getattr(ex, attr)
    n = len(eng.invocations)
    assert (ex.n_host_stitched, ex.n_fused) == ((0, n) if fused else (n, 0))


class _Instant:
    """Executor that completes at submit: only the engine's spans."""

    def submit(self, inv):
        return ExecHandle(inv, t_finish=inv.t_submit,
                          completion=Completion(inv, inv.t_submit))

    def resolve(self, handle):
        return handle.completion


def fire_lags(clock):
    tel = Telemetry(enabled=True)
    lat = LatencyTable({b: (0.1 * b, 0.01) for b in range(1, 33)},
                       slack_sigmas=3.0)
    eng = ServingEngine(uniform_pool(64, 64, lat, max_canvases=2),
                        _Instant(), clock=clock, telemetry=tel)
    eng.run([Arrival(p.t_gen, p, 0.0) for p in device_trace()])
    rows = tel.invocations().values()
    assert len(rows) == len(eng.invocations) > 1
    return [r["t_launch"] - r["t_fire"] for r in rows]


def test_fire_lag_shows_a_host_that_fires_late():
    """On a virtual clock every invocation launches at the instant it
    fired; a wall clock whose time runs ahead of the engine's sleeps (a
    busy host) makes the engine launch late, and the lag shows."""
    from repro.core.clock import VirtualClock

    assert set(fire_lags(VirtualClock())) == {0.0}
    now = [0.0]

    def lagging():
        now[0] += 0.5           # every read finds the host 0.5 s later
        return now[0]

    lags = fire_lags(WallClock(time_fn=lagging, sleep_fn=lambda dt: None))
    assert min(lags) >= 0.0 and max(lags) > 0.0


def test_spans_past_the_cap_are_counted_not_kept(monkeypatch):
    monkeypatch.setattr(telemetry_lib, "MAX_SPANS", 3)
    tel = Telemetry(enabled=True)
    for i in range(5):
        with tel.span("tangram.test", i=i):
            pass
    assert [s[7]["i"] for s in tel.spans] == [0, 1, 2]
    assert tel.dropped == 2


def test_threads_keep_their_own_span_stacks():
    """Shard threads share one recorder: every child's parent is a span
    of its own thread, every root opens its own invocation, and no span
    is lost."""
    tel = Telemetry(enabled=True)
    n_threads, n_roots = 16, 200

    def work():
        for _ in range(n_roots):
            with tel.span("tangram.root"):
                with tel.span("tangram.child"):
                    pass

    old = sys.getswitchinterval()
    sys.setswitchinterval(1e-6)
    try:
        threads = [threading.Thread(target=work) for _ in range(n_threads)]
        for t in threads:
            t.start()
        for t in threads:
            t.join(timeout=60)
        assert not any(t.is_alive() for t in threads)
    finally:
        sys.setswitchinterval(old)
    assert len(tel.spans) == 2 * n_threads * n_roots and tel.dropped == 0
    by_id = {s[0]: s for s in tel.spans}
    roots = [s for s in tel.spans if s[3] == "tangram.root"]
    assert len({s[2] for s in roots}) == n_threads * n_roots
    for sid, parent, inv, name, _t0, _t1, thread, _ in tel.spans:
        if name == "tangram.child":
            up = by_id[parent]
            assert (up[3], up[2], up[6]) == ("tangram.root", inv, thread)


def test_chrome_trace_export(tmp_path):
    tel = Telemetry(enabled=True)
    with tel.span("tangram.a", x=1):
        with tel.span("tangram.b"):
            pass
    with tel.span("tangram.c", inv=0):
        pass
    path = tmp_path / "spans.json"
    tel.write_chrome_trace(path, counters={"bytes_to_device": 7})
    doc = json.loads(path.read_text())
    events = {e["name"]: e for e in doc["traceEvents"]}
    assert set(events) == {"tangram.a", "tangram.b", "tangram.c"}
    a, b = events["tangram.a"], events["tangram.b"]
    assert all(e["ph"] == "X" and e["dur"] >= 0 for e in events.values())
    assert a["ts"] <= b["ts"] and b["ts"] + b["dur"] <= a["ts"] + a["dur"]
    assert b["args"]["parent"] == a["args"]["id"] and a["args"]["x"] == 1
    assert {e["args"]["inv"] for e in events.values()} == {0}
    assert doc["otherData"] == {"counters": {"bytes_to_device": 7},
                                "dropped_spans": 0}


@pytest.mark.parametrize("record", [False, True], ids=["off", "on"])
def test_serve_driver_telemetry_file_and_summary(tmp_path, record):
    from repro.launch import serve

    path = tmp_path / "spans.json"
    argv = ["--frames", "16", "--canvas", "128", "--slo", "5.0"]
    out = serve.main(argv + (["--telemetry", str(path)] if record else []))
    assert out["mb_to_device"] > 0 and out["mb_from_device"] > 0
    if not record:
        assert out["fire_lag_p95_ms"] is None and not path.exists()
        return
    assert out["fire_lag_p95_ms"] == 0.0            # virtual clock
    doc = json.loads(path.read_text())
    names = {e["name"] for e in doc["traceEvents"]}
    assert {"tangram.engine.dispatch", P + "launch", P + "finalize"} <= names
    counters = doc["otherData"]["counters"]
    assert counters["n_invocations"] == out["invocations"]
    assert counters["bytes_to_device"] / 1e6 == out["mb_to_device"]
