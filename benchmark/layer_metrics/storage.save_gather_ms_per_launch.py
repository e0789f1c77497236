"""One of the six parts of the loop's `save` span, in ms per launch:
`build_save_updates`: the per-lane `arena.get_run` and the `State` and
`Update` objects of the wave (`save.gather`). None on a program without
the sub-span."""

from benchmark.lib import launches


def read(run):
    return launches.ms_per_launch(run, "save.gather")
