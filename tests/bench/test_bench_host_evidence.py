"""The check that decides ``correct`` sees a fault planted in the
evidence the unfused path serves, which it cuts from the host canvas
batch (``stitch_ops.unstitch_plan_host``)."""
from bench import run

SEEDS = (2**31 + 101, 2**31 + 102)
SECONDS = 3.0


def test_a_fault_in_the_host_evidence_is_not_correct(tiny_harness,
                                                     monkeypatch):
    from repro.kernels.stitch import ops as stitch_ops

    h = tiny_harness
    unstitch = stitch_ops.unstitch_plan_host
    calls = []

    def bump(*a, **k):
        out = unstitch(*a, **k)
        out[0][0, 0, 0] += 1.0
        calls.append(len(out))
        return out
    monkeypatch.setattr(stitch_ops, "unstitch_plan_host", bump)
    h.reseed(SEEDS[0])
    r, kept = h.window(SEEDS[0], SECONDS)
    assert calls, "the served path did not cut its evidence on the host"
    numbers = h.check(kept, r)
    ok, checks = run.judge(numbers, h.config)
    assert not ok, checks
    failing = {k for k, c in checks.items() if c["value"] > c["limit"]}
    assert "evidence_mismatch" in failing, checks
