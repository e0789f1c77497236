"""Phase spans the flight recorder's span store shed inside the window
because it was full. Expect 0: a shed span is idle time that the
breakdown puts under `host`."""

from benchmark.lib import spans


def read(run):
    return spans.count(run, "spans_dropped")
