"""merge_roofline: the merge programs' share of their HBM roofline.

The least bytes a merge moves are its rows read in and written out, 16
bytes a row (key, value, weight and sequence number, int32 each), from
the engine's `rows_merged_in`/`rows_merged_out` counters. Their time at
the chip's peak HBM bandwidth (bench_tpu/peaks.json), over the device
time of the flush, spill and compaction programs in the trace, in %.
None where no merge ran in the traced window, or where the trace holds
only part of the window (the counters cover all of it)."""

ROW_BYTES = 16
PROGRAMS = ("merge_buffer_to_level0_impl", "merge_level_down_impl",
            "compact_last_level_impl")


def read(run):
    if (run.trace is None or run.trace.truncated or not run.stats_after
            or run.chip_peaks is None):
        return None
    secs = sum(run.trace.program(p)[0] for p in PROGRAMS)
    rows = run.stat_delta("rows_merged_in") + run.stat_delta(
        "rows_merged_out")
    if secs <= 0 or rows <= 0:
        return None
    return 100.0 * rows * ROW_BYTES / run.chip_peaks["hbm_bytes_per_s"] / secs
