"""scan.device_ms_per_call: device time of the `range_many` program per
execution in the traced window."""


def read(run):
    if run.trace is None:
        return None
    secs, n = run.trace.program("range_many_impl")
    return secs / n * 1e3 if n else None
