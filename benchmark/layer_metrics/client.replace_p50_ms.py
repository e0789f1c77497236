"""Median time a replacement took, over those that finished inside the
window: from the generator asking for the old replica's removal to the
new replica's applied count coming within one batch of the leader's
(delete committed, stop_cluster, add committed, start_cluster with join,
snapshot install, catch-up). None where none finished in the window."""


def read(run):
    return run.client.get("client.replace_p50_ms")
