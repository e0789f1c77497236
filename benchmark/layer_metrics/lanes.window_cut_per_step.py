"""Lanes whose staged proposals did not all fit a launch, cut by the
free space of the device window or by the inbox slots left
(`n.lanes_window_cut`), per launch: 0 under even lanes."""

from benchmark.lib import counters


def read(run):
    return counters.per_pack(run, "lanes_window_cut")
