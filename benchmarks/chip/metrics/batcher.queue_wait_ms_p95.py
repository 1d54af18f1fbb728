"""95th percentile of the batcher's queue wait (RequestStats.wait_s,
submit to launch) over every answered request of the window."""
import numpy as np


def read(run):
    if run.wait_s is None or not len(run.wait_s):
        return None
    return float(np.percentile(run.wait_s, 95)) * 1e3
