import os
import sys

# NOTE: no XLA_FLAGS here on purpose — smoke tests and benches must see the
# real 1-device world.  Multi-device dry-run coverage runs in a subprocess
# (tests/test_dryrun_multidevice.py) which sets its own flags.
sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..", "src"))

try:
    import hypothesis  # noqa: F401  (the real package, when installed)
except ModuleNotFoundError:
    # fall back to the vendored shim so property tests collect and run in
    # environments without hypothesis (see tests/_vendor/hypothesis)
    sys.path.append(os.path.join(os.path.dirname(__file__), "_vendor"))

import signal
import threading

import numpy as np
import pytest

# Per-test deadline (seconds).  The parallel fleet runtime joins shard
# threads at finish(); a deadlocked shard would otherwise hang the whole
# lane silently.  SIGALRM turns a hang into a loud TimeoutError with a
# traceback pointing at the stuck join/barrier.  pytest-timeout is not a
# repo dependency — this is the conftest-alarm variant.
TEST_TIMEOUT_S = int(os.environ.get("REPRO_TEST_TIMEOUT_S", "300"))


@pytest.hookimpl(wrapper=True)
def pytest_runtest_call(item):
    if (TEST_TIMEOUT_S <= 0 or not hasattr(signal, "SIGALRM")
            or threading.current_thread() is not threading.main_thread()):
        return (yield)

    def _on_alarm(signum, frame):
        raise TimeoutError(
            f"test exceeded {TEST_TIMEOUT_S}s "
            "(deadlocked shard thread?)")

    old_handler = signal.signal(signal.SIGALRM, _on_alarm)
    signal.alarm(TEST_TIMEOUT_S)
    try:
        return (yield)
    finally:
        signal.alarm(0)
        signal.signal(signal.SIGALRM, old_handler)

# Modules excluded from the CI fast lane.  The former tracked-red modules
# (arch smoke, sharding API, multi-device dry-run, elastic re-mesh) went
# green with the sharding compat layer (src/repro/compat/shardingx.py)
# and now run in the enforced lane; only
# genuinely heavyweight sweeps belong here.
SLOW_MODULES: set = set()


def pytest_collection_modifyitems(config, items):
    for item in items:
        module = item.module.__name__ if item.module else ""
        if module in SLOW_MODULES:
            item.add_marker(pytest.mark.slow)
        else:
            item.add_marker(pytest.mark.fast)


@pytest.fixture
def rng():
    return np.random.default_rng(0)
