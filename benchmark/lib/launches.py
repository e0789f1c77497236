"""Window sums of the engine's spans and counters per kernel launch, by
the program's own count of launches (`n.launches`, folded where the loop
dispatches), not by `window["launches"]`, which is protocol steps over
the configuration file's `steps_per_sync` and a third of a launch where
the engine chooses three steps. None as `spans._sums` gives it: a
program without a name, a run below full sampling; and None without a
launch."""
from __future__ import annotations

from benchmark.lib import spans

# the loop thread's top-level spans in which it has work: all but `wait`
BUSY = tuple(n for n in spans.TOP_LEVEL if n != "wait")


def seconds(run, *names):
    """Window sum under each of `names`, or None if any is missing."""
    return spans._sums(run, names)


def per_launch(run, *names):
    """Σ of the window sums under `names` per launch."""
    return over_launches(run, seconds(run, *names))


def ms_per_launch(run, *names):
    """Seconds under all of `names` per launch, in ms."""
    return over_launches(run, seconds(run, *names), 1000.0)


def over_launches(run, sums, scale: float = 1.0):
    """Σ `sums` (signed as the caller made them) per launch; None for
    None."""
    launches = spans.count(run, "launches")
    if sums is None or not launches:
        return None
    return sum(sums) / launches * scale


def off_cpu(run, *names):
    """Σ (wall − the thread's CPU) seconds over the spans `names`."""
    wall = seconds(run, *names)
    cpu = seconds(run, *(n + ".cpu" for n in names))
    return None if wall is None or cpu is None else sum(wall) - sum(cpu)
