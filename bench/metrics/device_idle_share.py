"""Device: 1 - (union of the intervals in which an XLA module ran) over
the traced window, averaged over the chips in use."""


def read(run):
    if run.trace is None or run.trace["window_s"] <= 0:
        return None
    return 100.0 * (1.0 - run.trace["busy_s"] / run.trace["window_s"])
