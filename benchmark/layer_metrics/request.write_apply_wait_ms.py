"""Mean time of a sampled write from its quorum commit until an apply
worker took up the ready nodes, its own among them (t_apply0 - t_commit)."""

from benchmark.lib import spans


def read(run):
    return spans.per_request(run, "w", "apply_wait")
