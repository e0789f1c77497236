"""Lanes that know a leader, hold entries above their commit index and
have seen that index stand for eight of the watch's sweeps or more (a
sweep is a launch at least an election timeout after the sweep before
it), per launch (`n.commit_stall_steps`, folded by the progress watch,
`VectorEngine._watch_progress`, every sampled launch): a follower that
is not told what is committed, a leader without its quorum. 0 where the
watch ran and found none; None on a program without it."""

from benchmark.lib import launches


def read(run):
    return launches.per_launch(run, "n.commit_stall_steps")
