"""Executor host path: mean over invocations of the time inside the
executor's ``submit`` and ``resolve``, minus the time inside its
``sync`` hook (a timed ``jax.block_until_ready``), over the invocations
outside the trace."""
from bench.metrics._invocations import untraced


def read(run):
    inv = untraced(run)
    if not inv:
        return None
    host = sum(r.submit_s + r.resolve_s - r.sync_s for r in inv)
    return 1e3 * host / len(inv)
