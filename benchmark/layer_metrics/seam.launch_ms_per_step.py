"""Host-clock time per launch in `launch`, a sub-span of dispatch: the
jitted step call, until it returns its futures."""

from benchmark.lib import spans


def read(run):
    return spans.per_step_ms(run, "launch")
