"""99th percentile of the open loop's linearizable-read latency (due time
to value in hand). Per-layer for the reason its write twin is."""


def read(run):
    return run.client.get("client.read_latency_p99_ms")
