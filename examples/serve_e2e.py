"""End-to-end serving driver: batched requests through a REAL jit'd model.

This is the paper's deployment loop with actual tensors: the edge pipeline
emits patches, the unified serving engine (``core.engine``) batches them
through the SLO-aware invoker pool, the executor stitches canvases on the
host, and a jit-compiled ViT detector serves each batch on the
``DeviceExecutor`` — the exact control plane the simulator benchmarks run
on.

    PYTHONPATH=src python examples/serve_e2e.py
"""
from repro.launch import serve

if __name__ == "__main__":
    serve.main(["--frames", "40", "--canvas", "192", "--slo", "2.0",
                "--use-pallas-stitch"])
