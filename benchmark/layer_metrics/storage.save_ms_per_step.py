"""Host-clock time per launch in the `save` phase: the batched WAL write
and its fsync barrier. Stage profiler at full sampling, window delta."""


def read(run):
    w = run.window
    if w["phase_ratio"] != 1 or not w["launches"]:
        return None
    return w["phases"].get("save", 0.0) / w["launches"] * 1000.0
