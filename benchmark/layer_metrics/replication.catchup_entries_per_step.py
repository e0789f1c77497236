"""Entries per launch that leaders sent from their host logs to peers
below the device window (`n.catchup_entries`, folded once a sampled
sweep of VectorEngine._run_catchups, 0 included). None on a program
without the counter."""

from benchmark.lib import counters


def read(run):
    return counters.per_launch(run, "catchup_entries")
