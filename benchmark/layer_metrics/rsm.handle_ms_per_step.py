"""Host-clock time per launch that the apply workers were at work, one
span a wake-up over the ready nodes a worker took (Node.handle_task on
each), summed over the workers: they run beside the loop, so this is
load on the shared GIL, not a share of the step."""

from benchmark.lib import spans


def read(run):
    return spans.per_step_ms(run, "rsm.handle")
