"""CPU ms per launch the loop thread spent in the host-log catch-up
sweep (`catchup.cpu`: thread time around VectorEngine._maintain's pass
over the lanes with a catch-up running). It is inside `maintain`. None
on a program without the span."""

from benchmark.lib import spans


def read(run):
    return spans.per_step_ms(run, "catchup.cpu")
