"""Mean time a sampled linearizable read waited in its node's
incoming_reads until _pack bound it to a ReadIndex context."""

from benchmark.lib import spans


def read(run):
    return spans.per_request(run, "r", "queue")
