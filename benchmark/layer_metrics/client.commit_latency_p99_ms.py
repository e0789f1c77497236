"""99th percentile of the open loop's write latency (due time to
acknowledgement, a failed write at its timeout) over the tens of thousands
of writes of a window. A per-layer metric because its run-to-run spread
(up to 9.9 % on the chip in PR 22) does not fit under the bound limit;
the median is the end-to-end metric."""


def read(run):
    return run.client.get("client.commit_latency_p99_ms")
