"""Operations left staged on the host after a pack (`n.staged_left`:
proposals, reads and config changes of the lanes carried over), per
launch: the queue the hot lanes build in front of the device."""

from benchmark.lib import counters


def read(run):
    return counters.per_pack(run, "staged_left")
