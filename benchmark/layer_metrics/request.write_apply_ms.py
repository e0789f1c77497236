"""Mean time of a sampled write from that wake-up of the worker until
it was applied and its batch accounted (t_done - t_apply0)."""

from benchmark.lib import spans


def read(run):
    return spans.per_request(run, "w", "apply")
