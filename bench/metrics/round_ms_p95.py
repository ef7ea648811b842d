"""95th percentile of the window's round times, in ms (host clock, each
round timed to a host read of its results)."""
import numpy as np


def read(run):
    return float(np.percentile(np.asarray(run.step_s) * 1e3, 95))
