"""A check, expected near 0: `save` less its six parts, in ms per
launch. What is left is the grouping of updates by logdb and shard, lock
waits between the parts and the profiler's own bookkeeping."""

from benchmark.lib import launches

PARTS = ("save.gather", "save.encode", "save.append", "save.table",
         "save.sync", "save.mirror")


def read(run):
    whole, parts = launches.seconds(run, "save"), launches.seconds(run, *PARTS)
    if whole is None or parts is None:
        return None
    return launches.over_launches(
        run, whole + [-s for s in parts], 1000.0
    )
