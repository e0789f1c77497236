"""WAL records the save wave appended per launch (`n.save_wal_records`:
a write batch's operations and its commit seal, over every shard
written): each pays a header, a CRC and a `write` call."""

from benchmark.lib import launches


def read(run):
    return launches.per_launch(run, "n.save_wal_records")
