"""Mean time a sampled write waited in its node's incoming_proposals
until _pack took it into a launch (t_pack - t0)."""

from benchmark.lib import spans


def read(run):
    return spans.per_request(run, "w", "queue")
