"""Host-clock time per launch in `device_wait`, a sub-span of fetch:
blocked until the step's output is ready. Near zero while the host sets
the pace; the first sign that a host-side gain has run into the kernel."""

from benchmark.lib import spans


def read(run):
    return spans.per_step_ms(run, "device_wait")
