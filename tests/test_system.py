"""End-to-end behaviour tests for the Tangram system.

The full loop: synthetic scene -> GMM -> RoIs -> Algorithm 1 -> bandwidth-
shaped arrivals -> Algorithm 2 (stitch + SLO-aware invoker) -> serverless
platform -> per-patch SLO accounting — plus the real-model serving driver
(stitch kernel in interpret mode + jit'd detector).
"""
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import gmm, partitioning, rois
from repro.core.latency import detector_latency_model
from repro.core.scheduler import ServeConfig, TangramScheduler
from repro.data.synthetic import Scene, preset
from repro.serverless.platform import Platform, PlatformConfig


def build_patch_streams(n_frames=25, slo=1.0):
    scene = Scene(preset(0, width=320, height=160))
    state = gmm.init_state(160, 320)
    stream = []
    for t, frame, gt in scene.frames(n_frames):
        state, fg = gmm.update_jit(state, jnp.asarray(frame))
        if t < 1.0:
            continue
        boxes, valid = rois.extract_rois_jit(jnp.asarray(fg))
        b = np.asarray(boxes)[np.asarray(valid)]
        stream.extend(partitioning.partition_host(
            b, 320, 160, 4, 4, frame_id=scene.t, t_gen=t, slo=slo))
    return [stream]


def test_full_pipeline_meets_slo_budget():
    streams = build_patch_streams()
    assert sum(len(s) for s in streams) > 10
    model = detector_latency_model(256, 256)
    table = model.build_table(16)
    plat = Platform(table, PlatformConfig())
    sched = TangramScheduler(256, 256, table, plat,
                             config=ServeConfig(check_invariants=True))
    res = sched.run(streams, bandwidth_bps=20e6)
    assert res.n_patches == sum(len(s) for s in streams)
    assert res.violation_rate <= 0.05          # the paper's headline claim
    assert res.invocations >= 1
    assert res.total_cost > 0


def test_serve_driver_with_real_model_and_pallas_stitch():
    """launch/serve.py: real jit'd detector + Pallas stitch (interpret)."""
    from repro.launch import serve
    serve.main(["--frames", "16", "--canvas", "128", "--slo", "5.0",
                "--use-pallas-stitch"])


def test_serve_driver_pallas_paths_route_the_xla_detections():
    """serve.main returns its summary; the Pallas stitch and fused paths
    route exactly the XLA path's detections on the same trace.  The long
    SLO makes every invocation fire on the canvas budget, so the plans do
    not depend on measured latency."""
    from repro.launch import serve
    argv = ["--frames", "16", "--canvas", "128", "--slo", "1000"]
    xla = serve.main(argv)
    assert xla["patches"] > 0 and xla["invocations"] > 0
    assert xla["frames_held"] == 0 and xla["fused"] == 0
    assert xla["detections"] == sum(len(v) for v in xla["routed"].values())
    pallas = serve.main(argv + ["--use-pallas-stitch"])
    assert pallas["routed"] == xla["routed"]
    fused = serve.main(argv + ["--fuse"])
    assert fused["fused"] == fused["invocations"] == xla["invocations"]
    assert fused["frames_held"] == 0
    assert set(fused["routed"]) == set(xla["routed"])
    for fid, dets in xla["routed"].items():
        assert len(fused["routed"][fid]) == len(dets)
        for (fs, fb), (xs, xb) in zip(fused["routed"][fid], dets):
            assert fs == pytest.approx(xs, abs=1e-4)
            assert fb == pytest.approx(xb, abs=1e-3)


@pytest.mark.parametrize("argv", [
    ["--full-width"],                                      # needs --model
    ["--model", "tangram", "--full-width", "--canvas", "256"],
    ["--devices", "0"],
])
def test_serve_driver_rejects_bad_width_and_device_flags(argv):
    from repro.launch import serve
    with pytest.raises(SystemExit):
        serve.main(argv)


def test_serve_driver_async_wall_clock_smoke():
    """launch/serve.py --async-device on a compressed wall clock: the
    full driver path through AsyncDeviceExecutor + WallClock."""
    from repro.launch import serve
    serve.main(["--frames", "10", "--canvas", "128", "--slo", "5.0",
                "--async-device", "--max-inflight", "2",
                "--clock", "wall", "--wall-speed", "50"])


def test_serve_driver_worker_pool_online_latency_smoke():
    """launch/serve.py --workers/--placement/--online-latency: the full
    driver path through make_worker_meshes -> WorkerPoolExecutor (shared
    frame store) with the online estimator fed back into the invoker."""
    from repro.launch import serve
    serve.main(["--frames", "10", "--canvas", "128", "--slo", "5.0",
                "--workers", "2", "--placement", "least",
                "--online-latency"])


def test_serve_driver_live_synthetic_virtual_clock():
    """launch/serve.py --source synthetic: live edge ingestion (GMM ->
    RoIs -> Alg. 1 during serving) against the real jit'd detector, with
    the ingestion window + degrade policy active, on the virtual clock."""
    from repro.launch import serve
    serve.main(["--frames", "12", "--canvas", "128", "--slo", "5.0",
                "--source", "synthetic", "--ingestion-window", "64",
                "--overload", "degrade"])


def test_serve_driver_live_synthetic_wall_clock():
    """The same live path on a compressed wall clock with the async
    executor: arrivals are produced in real (scaled) time while device
    work overlaps — the end-to-end live serving configuration."""
    from repro.launch import serve
    serve.main(["--frames", "10", "--canvas", "128", "--slo", "5.0",
                "--source", "synthetic", "--async-device",
                "--max-inflight", "2", "--clock", "wall",
                "--wall-speed", "50", "--ingestion-window", "64"])


def test_serve_driver_live_file_source(tmp_path):
    """launch/serve.py --source file: a recorded frame stack through the
    live edge pipeline."""
    from repro.data.synthetic import Scene, preset
    from repro.launch import serve
    sc = Scene(preset(0, width=256, height=128))
    frames = []
    for _ in range(10):
        sc.step()
        frames.append(sc.render())
    np.save(tmp_path / "clip.npy", np.stack(frames))
    serve.main(["--frames", "10", "--canvas", "128", "--slo", "5.0",
                "--source", "file", "--frames-path",
                str(tmp_path / "clip.npy")])


def test_train_driver_reduced_detector():
    from repro.launch import train
    train.main(["--arch", "tangram-detector", "--steps", "3", "--batch", "2"])
