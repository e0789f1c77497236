"""One of the six parts of the loop's `save` span, in ms per launch:
`WalKV._append_group`: a record packed, its CRC, the write, the seal and
the flush, summed over the shards (`save.append`); a store that cannot
tell its parts apart has its whole commit here. None on a program
without the sub-span."""

from benchmark.lib import launches


def read(run):
    return launches.ms_per_launch(run, "save.append")
