"""serve_p99_ms: 99th percentile, over every request due in the window,
of reply time minus due time (an unanswered request counts as
infinitely late)."""
import numpy as np


def read(run):
    if not run.latency_s:
        return None
    return float(np.percentile(np.asarray(run.latency_s), 99)) * 1e3
