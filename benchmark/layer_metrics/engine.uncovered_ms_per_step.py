"""A check, expected near 0: window seconds per launch that lie under
none of the loop thread's twelve top-level spans."""

from benchmark.lib import spans


def read(run):
    return spans.uncovered_ms_per_step(run)
