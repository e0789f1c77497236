"""Snapshots saved per launch, over all replicas (`n.snapshots_saved`:
one a completed Node._do_save_snapshot, image written and record
committed). None on a program without the counter."""

from benchmark.lib import counters


def read(run):
    return counters.per_launch(run, "snapshots_saved")
