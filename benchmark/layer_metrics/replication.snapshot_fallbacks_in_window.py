"""Times inside the window a leader gave a lagging peer up to the
snapshot path (`n.snapshot_fallbacks`: VectorEngine._send_snapshot
entered from a catch-up). In a deployment without snapshots every one
is the line `peer N needs a snapshot but none exists`: expect 0. 0
where the program ran its catch-up sweep and fell back on none; None on
a program without the counters."""

from benchmark.lib import spans


def read(run):
    if spans.count(run, "catchup_entries") is None:
        return None
    return spans.count(run, "snapshot_fallbacks") or 0
