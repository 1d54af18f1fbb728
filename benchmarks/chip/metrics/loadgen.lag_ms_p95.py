"""95th percentile of how late each request left the load generator
against the time it was due."""
import numpy as np


def read(run):
    if run.lag_s is None or not len(run.lag_s):
        return None
    return float(np.percentile(run.lag_s, 95)) * 1e3
