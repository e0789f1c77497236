"""Replicates per launch that a leader's host-log catch-up sent again
from below an index it had already sent (`n.replicate_resends`, in
VectorEngine._run_catchups: after a reject, or after `_ACK_LAUNCHES`
launches without the peer's match moving, it goes back to match + 1).
The kernel's own reject and resend inside the device window is counted
by replication.rejects_per_step. 0 where the program ran its catch-up
sweep and sent nothing twice; None on a program without the counters."""

from benchmark.lib import spans


def read(run):
    if spans.count(run, "catchup_entries") is None:
        return None
    launches = run.window["launches"]
    if not launches:
        return None
    return (spans.count(run, "replicate_resends") or 0) / launches
