"""Protocol steps a kernel launch ran, over the window's launches
(`n.launch_steps` over `n.launches`, folded by the engine loop where it
dispatches): 1.0 in the one-step loop, the configuration's
`steps_per_sync` where it sets one, and where the engine chooses for
itself (`steps_per_sync` None) how often it chose three, the whole
commit in one launch. It is the factor between the program's step
counter and its launches in such a cell. None on a program without the
counters."""

from benchmark.lib import counters


def read(run):
    return counters.ratio(run, "launch_steps", "launches")
