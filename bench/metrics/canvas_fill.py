"""Invoker: patch area over canvas area, over the window's invocations
(``Invocation.canvases``, as ``stitching.total_efficiency`` counts it)."""


def read(run):
    area = sum(r.canvas_area for r in run.invocations)
    if not area:
        return None
    return 100.0 * sum(r.used_area for r in run.invocations) / area
