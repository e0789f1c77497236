"""Mean time of a sampled read from its confirmation until the applied
index covered it and on_complete ran."""

from benchmark.lib import spans


def read(run):
    return spans.per_request(run, "r", "complete")
