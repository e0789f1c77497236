"""Host-clock time per launch in the `prepare` span: everything in
VectorEngine._run_once before _pack (a pending flush on the reconcile
path excepted, which times its own phases): reconciles, clock suspect,
snapshot status, route rebuild, ticks, request GC, the work set."""

from benchmark.lib import spans


def read(run):
    return spans.per_step_ms(run, "prepare")
