"""The loop thread's own CPU time (time.thread_time) per launch in the
seven phases of engine.host_ms_per_step. The rest of that metric's wall
time the thread spent off the CPU: waiting for the GIL, which the
generator and the apply workers share, or blocked in a call."""

from benchmark.lib import spans


def read(run):
    return spans.per_step_ms(run, *(p + ".cpu" for p in spans.HOST_PHASES))
