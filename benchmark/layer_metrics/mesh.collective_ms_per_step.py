"""Device time per launch under collective operations (the cross-shard
router's all-gather), mean over the chips."""


def read(run):
    t = run.trace
    if not t or not t["launches"] or t["collective_s"] is None:
        return None
    return t["collective_s"] / t["launches"] * 1000.0
