"""device_idle.write: share of the traced window in which no operation
ran on the device, in %."""


def read(run):
    if run.trace is None or not run.trace.devices:
        return None
    share = run.trace.idle_share
    return None if share is None else 100.0 * share
