"""Config-change entries applied inside the window, over all replicas
(`n.config_changes_applied`): a replacement is two changes, each applied
by every member. None on a program without the counter."""

from benchmark.lib import spans


def read(run):
    return spans.count(run, "config_changes_applied")
