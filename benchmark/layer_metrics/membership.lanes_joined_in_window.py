"""Lanes activated on the running core inside the window
(`n.lanes_joined`: start_cluster of a replica, counted where the loop
scatters its bring-up values). 0 where the program counted its saves
and no lane joined; None on a program without the counters."""

from benchmark.lib import spans


def read(run):
    if spans.count(run, "snapshots_saved") is None:
        return None
    return spans.count(run, "lanes_joined") or 0
