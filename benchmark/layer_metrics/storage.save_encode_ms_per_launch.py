"""One of the six parts of the loop's `save` span, in ms per launch:
`_Shard._record_update` over the wave's updates: entries encoded and
joined into batch records, hard states, the `WriteBatch.put`s, summed
over every shard of every co-hosted logdb (`save.encode`). None on a
program without the sub-span."""

from benchmark.lib import launches


def read(run):
    return launches.ms_per_launch(run, "save.encode")
