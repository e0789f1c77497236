"""One of the six parts of the loop's `save` span, in ms per launch: the
`log_reader.append` and `set_state` of every lane saved (`save.mirror`).
None on a program without the sub-span."""

from benchmark.lib import launches


def read(run):
    return launches.ms_per_launch(run, "save.mirror")
