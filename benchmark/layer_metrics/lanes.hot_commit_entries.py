"""How far the fullest leader lane's commit index moved since that lane's
last pack (`n.hot_commit_entries`, summed over launches), per launch:
what one group commits in a launch. The fullest leader lane is the one
with the most entries appended and not yet committed when `_pack` looks.
Beside `engine.steps_per_launch` x E it says whether the one Replicate a
peer a step caps the group. None on a program without the counter."""

from benchmark.lib import counters


def read(run):
    return counters.per_pack(run, "hot_commit_entries")
