"""Time per launch that the loop thread was off the CPU inside `save`
with no blocking call to show for it: (`save` − `save.cpu`) −
(`save.sync` − `save.sync.cpu`). The barrier is the one stretch of the
wave that is meant to sleep; the rest is a wait for the GIL, a lock or
the scheduler."""

from benchmark.lib import launches


def read(run):
    whole = launches.off_cpu(run, "save")
    barrier = launches.off_cpu(run, "save.sync")
    if whole is None or barrier is None:
        return None
    return launches.over_launches(run, [whole, -barrier], 1000.0)
