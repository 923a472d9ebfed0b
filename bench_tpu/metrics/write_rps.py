"""write_rps: records whose `insert` call returned (its group commit
included), over the window."""


def read(run):
    n = run.work["records"]
    return n / run.window_s if n and run.window_s else None
