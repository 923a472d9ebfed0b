"""lookup_rps: point lookups answered, over the window."""


def read(run):
    n = run.work["keys"]
    return n / run.window_s if n and run.window_s else None
