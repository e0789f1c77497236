"""The proposal entries of the fullest lane of a launch
(`n.hot_lane_entries`, summed over launches), per launch: what the
hottest group gets through its device window in one step."""

from benchmark.lib import counters


def read(run):
    return counters.per_pack(run, "hot_lane_entries")
