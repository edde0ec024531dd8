"""The readers of the program's spans and transfer counters: on a
synthetic run, on a run without the recorder, on a traced CPU-sized
window, which records them, and on an untraced one, which does not."""
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


def _made_executors(monkeypatch):
    """Every executor the harness makes from here on, with the recorder
    it was given."""
    import repro.core.engine as engine

    make, made = engine.make_executor, []

    def spy(name, **cfg):
        ex = make(name, **cfg)
        made.append((ex, cfg.get("telemetry")))
        return ex

    monkeypatch.setattr(engine, "make_executor", spy)
    return made


def _profiler_stub(monkeypatch):
    """The profiler is the chip's: on the CPU a traced window marks what
    it would trace and keeps no trace."""
    from bench import harness

    def start(self):
        self.tracing = True
        return "stub"

    def stop(self):
        self.tracing = False

    monkeypatch.setattr(harness.Spans, "start_trace", start)
    monkeypatch.setattr(harness.Spans, "stop_trace", stop)
    monkeypatch.setattr(harness.Spans, "reduce", lambda self, d: (None, 0))


def test_a_recorded_window_reads_every_metric(tiny_harness, monkeypatch):
    """A traced CPU window: the harness gives the window's executor and
    engine one recorder for all of it, and no other (the warm-up's
    executor and the replay's engine would take the first invocation
    ids).  Every reader finds a finite value, the transfer readers equal
    what the window's plans give, and the executor's spans sit inside
    the harness's own ``submit``/``resolve``/``sync`` times."""
    made = _made_executors(monkeypatch)
    _profiler_stub(monkeypatch)
    h = tiny_harness
    h.reseed(2**31 + 7)
    run, _kept = h.window(2**31 + 7, 3.0, trace=True, checked=False)
    tel = run.telemetry
    assert tel is not None and tel.enabled
    ex, given = made[-1]
    assert given is tel
    assert all(t is None for _, t in made[:-1])
    assert len(tel.invocations()) == len(run.invocations) > 1
    assert any(r.traced for r in run.invocations)
    assert sum(not r.traced for r in run.invocations) > 1
    values = {n: reader(n)(run) for n in NAMES}
    assert all(v is not None and np.isfinite(v)
               for v in values.values()), values
    rows = tel.invocations()
    untraced = [r for r in run.invocations if not r.traced]
    patches = sum(r.patches for r in untraced)
    moved = sum(rows[r.ordinal]["bytes_to_device"]
                + rows[r.ordinal]["bytes_from_device"] for r in untraced)
    assert values["xfer_mb_per_patch"] == pytest.approx(moved / 1e6
                                                        / patches)
    assert 0 < values["slot_fill"] <= 100
    assert sum(row["bytes_to_device"] for row in rows.values()) == \
        pytest.approx(ex.bytes_to_device)
    assert sum(row["slot_pixels"] for row in rows.values()) == \
        ex.slot_pixels
    for rec in run.invocations:
        row = rows[rec.ordinal]
        assert row["patches"] == rec.patches
        assert row["live_pixels"] == rec.live_pixels
        assert row["sync_s"] >= rec.sync_s
        assert row["launch_s"] + row["finalize_s"] <= \
            rec.submit_s + rec.resolve_s


def test_an_untraced_window_keeps_the_recorder_off(tiny_harness,
                                                   monkeypatch):
    """The untraced runs give the end-to-end numbers: no executor gets a
    recorder, the run holds none, and the span readers read nothing."""
    made = _made_executors(monkeypatch)
    h = tiny_harness
    h.reseed(2**31 + 8)
    run, _kept = h.window(2**31 + 8, 2.0, checked=False)
    assert run.telemetry is None
    assert made and all(t is None for _, t in made)
    assert all(not ex.telemetry.enabled for ex, _ in made)
    assert all(reader(n)(run) is None for n in NAMES)
