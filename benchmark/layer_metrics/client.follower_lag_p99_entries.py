"""99th percentile, over all replicas, of the entries a replica was
behind its leader's applied count at the window's close."""


def read(run):
    return run.client.get("client.follower_lag_p99_entries")
