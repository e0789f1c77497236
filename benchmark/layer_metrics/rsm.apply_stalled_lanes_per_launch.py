"""Lanes whose state machine stands below their commit index and applied
nothing between two looks eight of the watch's sweeps apart (a sweep is
a launch at least an election timeout after the sweep before it), per
launch (`n.apply_stall_steps`, folded by the progress watch,
`VectorEngine._watch_progress`, every sampled launch; the level itself
is taken every eighth sweep): a replica whose committed entries never
reach its apply worker, or whose `update` does not return. 0 where the
watch ran and found none; None on a program without it."""

from benchmark.lib import launches


def read(run):
    return launches.per_launch(run, "n.apply_stall_steps")
