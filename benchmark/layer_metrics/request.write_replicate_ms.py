"""Mean time of a sampled write from its pack to the decode that saw
its quorum commit (t_commit - t_pack): request.write_launches launches
of the loop."""

from benchmark.lib import spans


def read(run):
    return spans.per_request(run, "w", "replicate")
