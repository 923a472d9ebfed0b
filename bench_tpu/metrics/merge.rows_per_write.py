"""merge.rows_per_write: rows that entered flush, spill and compaction
merges in the window (the engine's `rows_merged_in` counter) per record
written."""


def read(run):
    n = run.work["records"]
    if not n or not run.stats_after:
        return None
    return run.stat_delta("rows_merged_in") / n
