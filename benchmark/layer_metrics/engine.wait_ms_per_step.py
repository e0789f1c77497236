"""Host-clock time per launch in the loop's `wait` span: blocked in
_ready.wait with nothing to do, a fairness yield, and iterations that
launched nothing. Near zero means the loop is saturated and every
request queues for it. Stage profiler at full sampling, window delta."""

from benchmark.lib import spans


def read(run):
    return spans.per_step_ms(run, "wait")
