"""Time per launch that the loop thread was off the CPU with no
blocking call to show for it: Σ over the eleven top-level spans but
`wait` of (wall − CPU), less `device_wait` (the fetch's
`block_until_ready`) and less the save wave's barrier (`save.sync` −
`save.sync.cpu`). What is left is the GIL, contended locks and the
scheduler. It is a sum over the thread's whole window, so the CPU
clock's skew between spans (PERF.md section 6, PR 37) cancels in it."""

from benchmark.lib import launches


def read(run):
    off = launches.off_cpu(run, *launches.BUSY)
    barrier = launches.off_cpu(run, "save.sync")
    device = launches.seconds(run, "device_wait")
    if off is None or barrier is None or device is None:
        return None
    return launches.over_launches(run, [off, -barrier, -device[0]], 1000.0)
