"""One of the six parts of the loop's `save` span, in ms per launch: the
durability barrier, `storage.kv.sync_all` over every WAL the wave
touched (`save.sync`): the only stretch of `save` in which the thread is
meant to be off the CPU. None on a program without the sub-span."""

from benchmark.lib import launches


def read(run):
    return launches.ms_per_launch(run, "save.sync")
