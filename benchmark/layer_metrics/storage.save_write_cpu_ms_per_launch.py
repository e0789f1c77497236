"""The loop thread's own CPU time per launch in the write stretch of
`save` (`save.write.cpu`): encode, append and table together, the
barrier left out. Beside the three parts' wall time it says how much of
the write is Python and how much a wait."""

from benchmark.lib import launches


def read(run):
    return launches.ms_per_launch(run, "save.write.cpu")
