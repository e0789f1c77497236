"""Median time from submitting a batch to seeing it accounted for, in a
closed loop: the loop's own queue (Little's law), not a service time."""


def read(run):
    return run.client.get("client.commit_latency_p50_ms")
