"""Mean time of a sampled read from its pack to the reads phase that
confirmed its context (the quorum's heartbeat round)."""

from benchmark.lib import spans


def read(run):
    return spans.per_request(run, "r", "confirm")
