"""Payload bytes handed to the save wave (`n.save_bytes`: the commands
of every entry of every replica saved in the step), per launch."""

from benchmark.lib import counters


def read(run):
    return counters.per_launch(run, "save_bytes")
