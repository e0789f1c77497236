"""ReadIndex contexts the kernel dropped inside the window for want of
a slot (StepOutput.dropped_readindex, summed by the host as fetched).
Expect 0: the reads behind a dropped context die at the client's
timeout."""

from benchmark.lib import spans


def read(run):
    return spans.count(run, "readindex_dropped")
