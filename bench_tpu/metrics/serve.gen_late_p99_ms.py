"""serve.gen_late_p99_ms: how late the load generator submitted a
request after it was due, 99th percentile (the generator shares its
thread with the server's pump)."""
import numpy as np


def read(run):
    if not run.late_s:
        return None
    return float(np.percentile(np.asarray(run.late_s), 99)) * 1e3
