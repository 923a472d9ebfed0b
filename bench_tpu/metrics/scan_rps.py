"""scan_rps: range windows answered, over the window."""


def read(run):
    n = run.work["scans"]
    return n / run.window_s if n and run.window_s else None
