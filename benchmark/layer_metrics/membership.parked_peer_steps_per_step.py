"""Follower slots parked behind the device window per launch
(`n.peer_steps_parked`: over the launches, the leaders' peer slots in
the SNAPSHOT state, waiting for a snapshot install or a host-log
catch-up). 0 where the program counted its launches' parked slots and
found none; None on a program without the counter."""

from benchmark.lib import counters, spans


def read(run):
    if spans.count(run, "snapshots_saved") is None:
        return None
    return counters.per_launch(run, "peer_steps_parked") or 0.0
