"""tape.device_ms_per_window: device time of the mixed-op tape program
per served window (`bench.pump` span) in the traced window."""


def read(run):
    if run.trace is None:
        return None
    secs, n = run.trace.program("tape_exec_impl")
    windows = run.trace.spans.get("bench.pump", [0])[0]
    return secs / windows * 1e3 if n and windows else None
