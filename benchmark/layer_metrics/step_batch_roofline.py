"""The step kernel's share of its bytes roof: the bytes one launch must
read and write on one chip (shape_bytes.launch_bytes over the chips the
state is sharded on) at the chip's peak HBM bandwidth, over the device
time a launch took. The kernel is integer and memory-bound: bytes bound
it, not FLOPs."""

from benchmark.lib import shape_bytes


def read(run):
    t = run.trace
    if not t or not t["launches"] or not t["kernel_s"]:
        return None
    s = run.shapes
    total = shape_bytes.launch_bytes(
        s["G"], s["P"], s["W"], s["K"], s["E"], s["R"], s["steps_per_sync"]
    )
    least_s = total / s["shards"] / run.peaks["hbm_bytes_per_s"]
    return least_s / (t["kernel_s"] / t["launches"]) * 100.0
