"""Host-clock time per launch in `put`, a sub-span of dispatch: the
jax.device_put of the inbox, the tick plane and (at steps_per_sync > 1)
the route planes."""

from benchmark.lib import spans


def read(run):
    return spans.per_step_ms(run, "put")
