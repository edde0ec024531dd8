"""Patches whose completion was delivered inside the window, per second
of the window: the capacity a saturated deployment shows."""
import numpy as np


def read(run):
    done = np.nan_to_num(run.t_done, nan=np.inf) <= run.seconds
    return float(np.sum(done)) / run.seconds
