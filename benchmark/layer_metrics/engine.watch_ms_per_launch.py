"""What the progress watch costs the loop thread, in ms per launch
(`watch`, a sub-span of `place`: `VectorEngine._watch_progress`, the
sweep of the three debts over the launch's final output and, every
eighth sweep, the pass over the state machines' applied indexes). It
runs on every launch, sampled or not, so this is what an untraced run
pays too; it is meant to stay under 0.2 % of a launch. None on a program
without the watch."""

from benchmark.lib import launches


def read(run):
    return launches.ms_per_launch(run, "watch")
