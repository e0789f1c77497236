"""Arrays a kernel launch moved across the host<->device seam, put plus
fetched, over the window's launches (`n.seam_buffers` over `n.launches`,
both folded by the engine loop where it dispatches): 4 where a one-chip
K-step launch puts its planes as one int32 and one bool slab and fetches
its outputs the same way; a plane each elsewhere, 61 in the one-step
loop and 70 at K steps over the mesh. None on a program without the
counter."""

from benchmark.lib import counters


def read(run):
    return counters.ratio(run, "seam_buffers", "launches")
