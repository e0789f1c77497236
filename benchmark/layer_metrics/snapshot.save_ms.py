"""Wall time of one snapshot save on its snapshot worker: the span
`snap.save` (prepare under the apply section, the state machine's
save_snapshot to disk, the snapshotter's commit record) over the saves
counted (`n.snapshots_saved`). None on a program without them."""

from benchmark.lib import spans


def read(run):
    sums = spans._sums(run, ("snap.save", "n.snapshots_saved"))
    if sums is None or not sums[1]:
        return None
    return sums[0] / sums[1] * 1000.0
