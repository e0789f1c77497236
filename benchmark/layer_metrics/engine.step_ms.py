"""Window seconds per iteration of the engine loop that launched the
kernel (step_stats() counts protocol steps, steps_per_sync to a launch)."""


def read(run):
    w = run.window
    return w["seconds"] / w["launches"] * 1000.0 if w["launches"] else None
