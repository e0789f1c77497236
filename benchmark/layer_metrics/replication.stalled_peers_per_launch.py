"""Leaders' peer slots that owed progress and made none for eight of
the watch's sweeps or more (a sweep is a launch at least an election
timeout after the sweep before it), per launch (`n.peer_stall_steps`:
the progress watch, `VectorEngine._watch_progress`, folds every sampled
launch how many voting peers stand with a match below their leader's
last index that has not moved for `_STALL_LAUNCHES` sweeps; slots parked
for a snapshot or served by a catch-up are
`membership.parked_peer_steps_per_step`'s and `replication.catchup*`'s).
0 where the watch ran and found none; None on a program without it."""

from benchmark.lib import launches


def read(run):
    return launches.per_launch(run, "n.peer_stall_steps")
