"""sched.syncs_per_chunk: blocking device-to-host reads of the driver
and its scheduler (the engine's `host_syncs` counter) per staged insert
chunk (`chunks_staged`), over the window."""


def read(run):
    chunks = run.stat_delta("chunks_staged") if run.stats_after else 0
    if not chunks:
        return None
    return run.stat_delta("host_syncs") / chunks
