"""99th percentile of the gap between two looks at the unfinished write
batches: a write's acknowledgement is stamped at the first look after it,
so this bounds how much a commit latency overstates."""


def read(run):
    return run.client.get("client.stamp_resolution_ms")
