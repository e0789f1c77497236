"""One of the six parts of the loop's `save` span, in ms per launch:
`MemKV.commit_write_batch` of the batch just appended, the in-memory
table the reads are served from, summed over the shards (`save.table`).
None on a program without the sub-span."""

from benchmark.lib import launches


def read(run):
    return launches.ms_per_launch(run, "save.table")
