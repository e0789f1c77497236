"""The apply workers' own CPU time per launch over the same spans as
rsm.handle_ms_per_step, summed over the workers (time.thread_time on
each)."""

from benchmark.lib import spans


def read(run):
    return spans.per_step_ms(run, "rsm.handle.cpu")
