"""Replacements that finished (the new replica caught up) inside the
window, whenever they started: beside the two a second that are started,
it says whether the rebalancer's work is carried or piles up."""


def read(run):
    return run.client.get("client.replacements_done_in_window")
