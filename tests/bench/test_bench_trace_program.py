"""Idle gaps named by the program's own spans: a trace whose host events
hold the serve path's ``tangram.*`` spans inside the benchmark's
``bench.submit`` and ``bench.sync`` charges each idle instant to the
innermost of them."""
import json
from pathlib import Path

import pytest

from bench import trace

FIXTURE = Path(__file__).resolve().parent / "fixtures" / "trace_program.json"
P = "tangram.executor."
# window [250000000, 500000000) ns; device busy [275005755, 275883955),
# [275892330, 284480121), [284491089, 285904709)
BUSY = 878200 + 8587791 + 1413620
IDLE = 250000000 - BUSY


@pytest.fixture(scope="module")
def gaps():
    fx = json.loads(FIXTURE.read_text())
    lo, hi = trace.window(fx["host"])
    modules = fx["devices"]["/device:TPU:0"]["modules"]
    return trace.idle_gaps(modules, fx["host"], lo, hi)


@pytest.mark.parametrize("span, ns", [
    (P + "gather", 253000000 - 251000300),
    (P + "pack", 270000000 - 253000100),
    (P + "put", 272000000 - 270000100),
    (P + "enqueue", 273999000 - 272000100),
    # the device runs inside the sync: the idle before, between and after
    # its three modules
    ("bench.sync", (275005755 - 274000300) + (275892330 - 275883955)
     + (284491089 - 284480121) + (299999900 - 285904709)),
    (P + "fetch", 330000000 - 300000100),
    (P + "route", 359997000 - 330000100),
    ("bench.sleep", 140000000),
    # only the instants between its children
    ("bench.submit", 100 + 100 + 1000),
    (P + "launch", 100 * 4 + 1000),
    (P + "finalize", 100 * 3 + 1000),
    (P + "sync", 100 + 100),
    ("tangram.engine.dispatch", 100 + 1000),
    ("no span", 1000000),
])
def test_idle_is_charged_to_the_innermost_span(gaps, span, ns):
    assert gaps[span] == pytest.approx(ns)


def test_the_gaps_add_up_and_bench_submit_keeps_almost_none(gaps):
    assert sum(gaps.values()) == pytest.approx(IDLE)
    assert gaps["bench.submit"] < 0.05 * IDLE
