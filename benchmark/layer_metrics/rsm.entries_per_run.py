"""Entries a run-level apply carried (`n.apply_run_entries` over
`n.apply_runs`): what one `update` call, one lock round trip and one
completion notify are paid for. A lane's committed batch in the fleet
cells, one or two operations under single-operation clients."""

from benchmark.lib import counters


def read(run):
    return counters.ratio(run, "apply_run_entries", "apply_runs")
