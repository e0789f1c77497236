"""Bytes the save wave appended to the WAL files per launch
(`n.save_wal_bytes`: the file offset after each group's flush less the
offset it began at). Beside `storage.save_bytes_per_step`, the commands
alone, it is what framing, entry headers, re-joined batch records, hard
states and max-index records cost on disk."""

from benchmark.lib import launches


def read(run):
    return launches.per_launch(run, "n.save_wal_bytes")
