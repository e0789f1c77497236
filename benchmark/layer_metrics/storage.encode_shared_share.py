"""The share of the entries handed to `_Shard._save_entries` whose
batch-record body was taken from the save wave's shared bodies instead
of being encoded again (`n.save_entries_shared` over `n.save_entries`,
counted in the wave's parts and folded by `_book_wave`): co-hosted
replicas of a group save the same `Entry` objects, so R replicas on one
core can reach (R - 1) / R, and one replica a process shares nothing.
None on a program without the counters."""

from benchmark.lib import counters


def read(run):
    return counters.ratio(run, "save_entries_shared", "save_entries")
