"""Mean number of kernel launches a sampled read took from the launch
its context was packed into to the launch the loop was in when the
context was confirmed, both counted."""

from benchmark.lib import spans


def read(run):
    return spans.per_request(run, "r", "launches", 1.0)
