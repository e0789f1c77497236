"""Proposal entries staged (`n.entries_packed`) over the leader lanes
that staged work of their clients (`n.lanes_packed`: a row of proposals
or a ReadIndex context): 64 in the batched fleet cells, a handful under
single operations; per-lane host work is paid for this many."""

from benchmark.lib import counters


def read(run):
    return counters.ratio(run, "entries_packed", "lanes_packed")
