"""ingest.call_max_ms: the longest `insert` call of the window, its
device work included (host clock around the call and a
`block_until_ready` of the state)."""


def read(run):
    if run.traffic.get("op") != "insert" or not run.call_s:
        return None
    return max(run.call_s) * 1e3
