"""The readers of the program's spans and transfer counters: on a
synthetic run, on a run without the recorder, and on a CPU-sized window
that recorded them."""
from types import SimpleNamespace

import numpy as np
import pytest

from bench.metrics import reader

#: two untraced invocations and one traced (ordinal 2), as the recorder's
#: ``invocations()`` gives them
ROWS = {
    0: dict(patches=2, t_fire=1.0, t_launch=1.1, arrivals=[0.5, 0.9],
            gather_s=0.01, pack_s=0.02, sync_s=0.2, fetch_s=0.05,
            bytes_to_device=3e6, bytes_from_device=1e6, slot_pixels=100,
            live_pixels=25),
    1: dict(patches=1, t_fire=2.0, t_launch=2.3, arrivals=[1.2],
            gather_s=0.03, pack_s=0.04, sync_s=0.4, fetch_s=0.07,
            bytes_to_device=2e6, bytes_from_device=2e6, slot_pixels=100,
            live_pixels=15),
    2: dict(patches=5, t_fire=9.0, t_launch=19.0, arrivals=[0.0],
            gather_s=5.0, pack_s=5.0, sync_s=5.0, fetch_s=5.0,
            bytes_to_device=9e9, bytes_from_device=9e9, slot_pixels=1,
            live_pixels=1),
}

NAMES = ("queue_wait_ms", "fire_lag_ms", "pack_ms_per_inv",
         "fetch_ms_per_inv", "sync_ms_per_inv", "xfer_mb_per_patch",
         "slot_fill")


def synthetic_run(telemetry=True):
    run = SimpleNamespace(invocations=[
        SimpleNamespace(ordinal=i, traced=i == 2) for i in ROWS])
    if telemetry:
        run.telemetry = SimpleNamespace(invocations=lambda: ROWS)
    return run


@pytest.mark.parametrize("name, want", [
    ("queue_wait_ms", 1e3 * np.percentile([0.5, 0.1, 0.8], 95)),
    ("fire_lag_ms", 1e3 * np.percentile([0.1, 0.3], 95)),
    ("pack_ms_per_inv", 50.0),
    ("fetch_ms_per_inv", 60.0),
    ("sync_ms_per_inv", 300.0),
    ("xfer_mb_per_patch", 8.0 / 3.0),
    ("slot_fill", 20.0),
])
def test_reader_on_the_untraced_invocations(name, want):
    assert reader(name)(synthetic_run()) == pytest.approx(want)


@pytest.mark.parametrize("name", NAMES)
def test_reader_without_the_recorder_reads_nothing(name):
    assert reader(name)(synthetic_run(telemetry=False)) is None


def test_a_recorded_window_reads_every_metric(tiny_harness, monkeypatch):
    """A CPU window with the recorder on for all of it, as a traced run
    would hand it over: every reader finds a finite value, the transfer
    readers equal what the window's plans give, and the executor's spans
    sit inside the harness's own ``submit``/``resolve``/``sync`` times."""
    import repro.core.engine as engine
    from repro.core.telemetry import Telemetry

    tel = Telemetry(enabled=True)
    make, serving = engine.make_executor, engine.ServingEngine
    made = []

    def make_recorded(name, **cfg):
        ex = make(name, telemetry=tel, **cfg)
        made.append(ex)
        return ex

    monkeypatch.setattr(engine, "make_executor", make_recorded)
    # the window's engine records; the replay that plans it does not
    monkeypatch.setattr(
        engine, "ServingEngine",
        lambda pool, ex, **kw: serving(
            pool, ex, telemetry=tel if ex in made else None, **kw))
    h = tiny_harness
    h.reseed(2**31 + 7)
    # nor the warm-up's executor, whose invocations would take the first
    # ids (on the CPU the window then compiles its shapes itself)
    monkeypatch.setattr(h, "warm", lambda invs: 0)
    run, _kept = h.window(2**31 + 7, 3.0, checked=False)
    run.telemetry = tel
    ex = made[-1]
    assert len(tel.invocations()) == len(run.invocations) > 1
    values = {n: reader(n)(run) for n in NAMES}
    assert all(np.isfinite(v) for v in values.values()), values
    patches = sum(r.patches for r in run.invocations)
    assert values["xfer_mb_per_patch"] == pytest.approx(
        (ex.bytes_to_device + ex.bytes_from_device) / 1e6 / patches)
    assert values["slot_fill"] == pytest.approx(
        100.0 * ex.live_pixels / ex.slot_pixels)
    rows = tel.invocations()
    for rec in run.invocations:
        row = rows[rec.ordinal]
        assert row["patches"] == rec.patches
        assert row["live_pixels"] == rec.live_pixels
        assert row["sync_s"] >= rec.sync_s
        assert row["launch_s"] + row["finalize_s"] <= \
            rec.submit_s + rec.resolve_s
