"""Mean time from a leader sending a snapshot to its seeing the restored
replica's acknowledgement (the span `snap.install`, recorded where the
leader's snapshot feedback finds the peer's match at the snapshot's
index), over the installs it saw acknowledged (`n.snapshots_acked`).
0 where the program counted its saves and saw no acknowledgement inside
the window; None on a program without the counters."""

from benchmark.lib import spans


def read(run):
    if spans.count(run, "snapshots_saved") is None:
        return None
    sums = spans._sums(run, ("snap.install", "n.snapshots_acked"))
    if sums is None or not sums[1]:
        return 0.0
    return sums[0] / sums[1] * 1000.0
