"""Snapshots a replica was restored from inside the window
(`n.snapshots_installed`: one a completed recover on a snapshot worker
that an InstallSnapshot caused). 0 where the program counted its saves
and no install; None on a program without the counters."""

from benchmark.lib import spans


def read(run):
    if spans.count(run, "snapshots_saved") is None:
        return None
    return spans.count(run, "snapshots_installed") or 0
