"""The trace reduction on a small trace recorded on the chip, against
values counted by hand from the fixture's events, and what a traced
run's per-patch readers leave out."""
import json
from pathlib import Path

import numpy as np
import pytest

from bench import trace

FIXTURE = Path(__file__).resolve().parent / "fixtures" / "trace_small.json"

# window [250000000, 500000000) ns; modules (start, duration):
#   stitch   275005755 + 878200
#   trunk    275892330 + 8587791
#   unstitch 284491089 + 1413620   (no two overlap)
BUSY = 878200 + 8587791 + 1413620
WINDOW = 250000000


@pytest.fixture(scope="module")
def fixture():
    return json.loads(FIXTURE.read_text())


def test_busy_and_kernel_time(fixture):
    red = trace.reduce(fixture, trace.load_modules())
    assert red["window_s"] == pytest.approx(WINDOW / 1e9)
    assert red["busy_s"] == pytest.approx(BUSY / 1e9)
    assert 1 - red["busy_s"] / red["window_s"] == pytest.approx(
        1 - 10879611 / 250000000)
    assert red["kernel_s"] == pytest.approx(
        {"trunk": 8587791e-9, "stitch": 878200e-9, "unstitch": 1413620e-9})
    assert red["kernel_runs"] == {"trunk": 1, "stitch": 1, "unstitch": 1}


def test_idle_gaps_are_named_by_the_open_host_span(fixture):
    gaps = dict(trace.reduce(fixture, trace.load_modules())
                ["breakdown"]["idle_gaps"])
    # before the stitch 25005755, between modules 8375 + 10968, then the
    # first submit to its end 365465209 - 285904709 = 79560500, and the
    # second submit from 385715237 to the window's end 114284763
    assert gaps["bench.submit"] == pytest.approx(218870361e-9)
    assert gaps["bench.sleep"] == pytest.approx(20231129e-9)
    # between the first submit's end and the sleep, and after the sleep
    assert gaps["no span"] == pytest.approx((5909 + 12990) * 1e-9)
    assert sum(gaps.values()) == pytest.approx((WINDOW - BUSY) * 1e-9)


def test_operation_self_time(fixture):
    ops = dict(trace.reduce(fixture, trace.load_modules())
               ["breakdown"]["device_ops"])
    # the trunk's layer loop holds the fusion inside it; the stitch's
    # placement loop is an operation of the stitch module
    assert ops == pytest.approx({
        "jit__lambda/while": (7696668 - 231632) * 1e-9,
        "jit__lambda/add_add_fusion": 231632e-9,
        "jit_stitch_canvases/while": 791246e-9})


def test_clipping_to_the_window(fixture):
    devices = fixture["devices"]["/device:TPU:0"]["modules"]
    lo = 280000000
    assert trace.busy_ns(devices, lo, 500000000) == \
        284480121 - lo + 1413620
    assert trace.kernel_runs(devices, trace.load_modules(), lo,
                             500000000)["trunk"] == 0


@pytest.mark.parametrize("name, want", [
    ("jit_stitch_canvases(10235699636306278226)", "jit_stitch_canvases"),
    ("jit__lambda(929022773403553673)", "jit__lambda"),
])
def test_module_names(name, want):
    assert trace.module_name(name) == want


def test_no_window_span_is_an_error(fixture):
    fx = dict(fixture, host=[h for h in fixture["host"]
                             if h[0] != trace.WINDOW_SPAN])
    with pytest.raises(ValueError, match="window"):
        trace.reduce(fx, trace.load_modules())


@pytest.mark.parametrize("name,untraced,traced", [
    ("p95_latency_ms", 1000.0 * np.percentile([0.5] * 19 + [2.0], 95),
     500.0),
    ("slo_attainment", 95.0, 100.0)])
def test_per_patch_readers_skip_patches_due_after_the_trace_began(
        name, untraced, traced):
    """Stopping the profiler stalls the host: a traced run's per-patch
    readers take only the patches whose deadline passed before it began."""
    from types import SimpleNamespace

    from bench.metrics import reader

    t_gen = np.arange(20, dtype=float)
    lat = np.array([0.5] * 19 + [2.0])              # the last one is late
    run = SimpleNamespace(t_gen=t_gen, t_done=t_gen + lat,
                          deadline=t_gen + 1.0, t_trace=np.inf)
    assert reader(name)(run) == pytest.approx(untraced)
    run.t_trace = 19.5                              # the last is due after
    assert reader(name)(run) == pytest.approx(traced)


def _traced_run(fixture, drop=()):
    """One traced invocation of 2 canvases and 300000 live pixels on the
    fixture's trace, without the modules named in ``drop``."""
    from types import SimpleNamespace

    from bench.harness import InvRecord
    from bench.peaks import peaks

    fx = json.loads(json.dumps(fixture))
    for dev in fx["devices"].values():
        dev["modules"] = [m for m in dev["modules"]
                          if trace.module_name(m[0]) not in drop]
    inv = InvRecord(ordinal=0, canvases=2, patches=9, used_area=300000,
                    canvas_area=2 * 1024 ** 2, t_slack=0.0,
                    live_pixels=300000, traced=True)
    return SimpleNamespace(invocations=[inv], arch={"canvas": 1024},
                           peak=peaks("TPU v5 lite"),
                           trace=trace.reduce(fx, trace.load_modules()))


@pytest.mark.parametrize("drop, nbytes, ns", [
    # both programs ran: each one's bytes over both one's times
    ((), 300000 * 12 + 2 * 1024 ** 2 * 12 + 2 * 300000 * 12,
     878200 + 1413620),
    # the unfused path: the unstitch alone ran, and only its bytes count
    (("jit_stitch_canvases",), 2 * 300000 * 12, 1413620),
], ids=["stitch_and_unstitch", "unstitch_alone"])
def test_stitch_roofline_counts_each_program_with_its_own_time(
        fixture, drop, nbytes, ns):
    from bench.metrics import reader

    run = _traced_run(fixture, drop)
    assert reader("stitch_roofline")(run) == pytest.approx(
        100.0 * nbytes / 819e9 / (ns * 1e-9))


def test_stitch_roofline_reads_nothing_without_either_program(fixture):
    from bench.metrics import reader

    run = _traced_run(fixture, ("jit_stitch_canvases",
                                "jit_unstitch_patches"))
    assert reader("stitch_roofline")(run) is None
