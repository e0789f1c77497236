"""Share of the traced window in which no operation ran on the device;
on several chips the idlest chip's. It says whether the engine's host
half or the kernel sets the step time."""


def read(run):
    t = run.trace
    if not t:
        return None
    return (1.0 - min(t["busy_s_per_chip"]) / t["window_s"]) * 100.0
