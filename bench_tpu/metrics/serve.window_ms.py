"""serve.window_ms: mean host time of a `Server.pump` call that served a
window (coalesce, tape, scatter, group commit, governor steps)."""


def read(run):
    if not run.pump_s:
        return None
    return sum(run.pump_s) / len(run.pump_s) * 1e3
