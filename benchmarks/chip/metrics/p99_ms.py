"""p99_ms: 99th percentile over every request scheduled in the window,
from its scheduled arrival to its response on the host (host clock).  A
request unanswered when the drain ends counts with its wait so far."""
import numpy as np


def read(run):
    if run.latencies_s is None or not len(run.latencies_s):
        return None
    return float(np.percentile(run.latencies_s, 99)) * 1e3
