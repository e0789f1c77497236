"""The fullest leader lane's entries appended and not yet committed when
`_pack` looks, in rows of max_entries_per_msg rounded up
(`n.hot_uncommitted_rows`, summed over launches), per launch: the
Replicates that wait behind the one a peer a step. Near 0 the host sets
the pace, not the row cap. None on a program without the counter."""

from benchmark.lib import counters


def read(run):
    return counters.per_pack(run, "hot_uncommitted_rows")
