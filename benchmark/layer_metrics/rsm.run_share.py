"""Share of applied entries that a run applied (`n.apply_run_entries`
over `n.apply_entries`, folded by the apply workers from the managers'
own counts): plain no-op-session entries go to the state machine as one
`update` call a contiguous run with one completion notify; session-
managed entries, config changes and empty new-leader entries go one by
one. None on a program without the counters."""

from benchmark.lib import counters


def read(run):
    return counters.ratio(run, "apply_run_entries", "apply_entries")
