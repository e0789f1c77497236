"""Snapshots that leaders sent to a peer inside the window
(`n.snapshots_sent`: one an InstallSnapshot handed to the transport in
VectorEngine._send_snapshot). Beside snapshot.installs_in_window it says
how many images a replica's bring-up really costs: more sends than
installs are images shipped twice, or behind a log that was compacted
under the joiner. 0 where the program counted its saves and no send;
None on a program without the counters."""

from benchmark.lib import spans


def read(run):
    if spans.count(run, "snapshots_saved") is None:
        return None
    return spans.count(run, "snapshots_sent") or 0
