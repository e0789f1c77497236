"""Mean number of kernel launches a sampled write took from the launch
it was packed into to the launch the loop was in when it saw the
commit, both counted."""

from benchmark.lib import spans


def read(run):
    return spans.per_request(run, "w", "launches", 1.0)
