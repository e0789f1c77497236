"""Lanes freed on the running core inside the window (`n.lanes_left`:
stop_cluster of a replica, counted where the loop reaps its lane). 0
where the program counted its saves and no lane left; None on a program
without the counters."""

from benchmark.lib import spans


def read(run):
    if spans.count(run, "snapshots_saved") is None:
        return None
    return spans.count(run, "lanes_left") or 0
