"""Median latency of every request due in the window, from the moment it
was due to its verdicts; a failed or refused request counts at the
longest the run waits for an answer."""
import numpy as np


def read(run):
    if run.latency_s is None or not len(run.latency_s):
        return None
    return float(np.percentile(run.latency_s, 50)) * 1e3
