"""Reads bound to a ReadIndex context (`n.reads_bound`) over the
contexts opened (`n.read_contexts`): a lane opens one context a step, so
under skew one context confirms hundreds of reads of the hot group."""

from benchmark.lib import counters


def read(run):
    return counters.ratio(run, "reads_bound", "read_contexts")
