"""Host-clock time per launch in `copy`, a sub-span of fetch: the
jax.device_get of the whole StepOutput once it is ready."""

from benchmark.lib import spans


def read(run):
    return spans.per_step_ms(run, "copy")
