"""wal.bytes_per_write: bytes the write-ahead log grew in the window
(`Durability.stats()["wal_bytes"]`) per record written."""


def read(run):
    n = run.work["records"]
    if run.wal_bytes is None or not n:
        return None
    return run.wal_bytes / n
