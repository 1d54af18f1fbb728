"""95th percentile of the latency of every request due in the window, from
the moment it was due to its verdicts; a failed or refused request counts
at the longest the run waits for an answer.  A stall of the whole host
(~0.1 s) delays every request due in it, so the count of such stalls in
the window, and not the service, decides this number from run to run."""
import numpy as np


def read(run):
    if run.latency_s is None or not len(run.latency_s):
        return None
    return float(np.percentile(run.latency_s, 95)) * 1e3
