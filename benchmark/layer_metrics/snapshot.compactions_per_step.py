"""Deferred log compactions per launch, over all replicas
(`n.log_compactions`: one a LogDB.remove_entries_to run on a snapshot
worker behind a committed snapshot). None on a program without the
counter."""

from benchmark.lib import counters


def read(run):
    return counters.per_launch(run, "log_compactions")
