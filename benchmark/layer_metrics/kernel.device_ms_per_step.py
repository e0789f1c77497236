"""Device time of one kernel launch: the device-busy time inside the whole
executions of the step program in the traced stretch (the program that
took most device time there), mean over the chips used, over the number
of those executions."""


def read(run):
    t = run.trace
    if not t or not t["launches"]:
        return None
    return t["kernel_s"] / t["launches"] * 1000.0
