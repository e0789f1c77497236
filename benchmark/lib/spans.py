"""Readers of what the engine's own profiler recorded over the window.

`probes.snapshot` copies every entry of `core.profiler.samples` as
(count, sum) when the window opens and when it closes, and `probes.delta`
hands the difference to the readers as `run.window["phases"][name]`,
for every name that was recorded in between. Under those names the
program keeps a span's wall seconds, a span's CPU seconds (`<name>.cpu`),
a counter's increments (`n.<name>`) and a sampled request's shares
(`req.<w|r>.<stretch>`, `req.<w|r>.n` requests). They are whole only at
full sampling, so every reader returns None at another ratio, and None
where the program recorded nothing under a name: a program older than
the name, or a cell without such traffic.
"""
from __future__ import annotations

# the loop thread's top-level spans: at every instant of a sampled
# iteration it is in exactly one of them (trace.Profiler.begin)
TOP_LEVEL = ("wait", "prepare", "pack", "dispatch", "fetch", "place",
             "send_rep", "save", "send_resp", "apply", "reads", "maintain")
# the phases engine.host_ms_per_step sums
HOST_PHASES = ("pack", "place", "send_rep", "send_resp", "apply", "reads",
               "maintain")


def _sums(run, names):
    w = run.window
    if w["phase_ratio"] != 1:
        return None
    phases = w["phases"]
    if any(name not in phases for name in names):
        return None
    return [phases[name] for name in names]


def per_step_ms(run, *names):
    """Seconds under all of `names` per kernel launch, in ms."""
    sums = _sums(run, names)
    if sums is None or not run.window["launches"]:
        return None
    return sum(sums) / run.window["launches"] * 1000.0


def uncovered_ms_per_step(run):
    """Window seconds under no top-level span, per launch, in ms. The
    window's edges cut two spans, so one run reads a span too much or too
    little; the program's own test holds the cover to the spans' ends."""
    sums = _sums(run, TOP_LEVEL)
    if sums is None or not run.window["launches"]:
        return None
    w = run.window
    return (w["seconds"] - sum(sums)) / w["launches"] * 1000.0


def per_request(run, kind: str, stretch: str, scale: float = 1000.0):
    """Mean share of `stretch` in the path of the sampled requests of
    `kind` ("w" writes, "r" reads) completed in the window: ms, or a
    plain number with scale 1."""
    sums = _sums(run, (f"req.{kind}.{stretch}", f"req.{kind}.n"))
    if sums is None or not sums[1]:
        return None
    return sums[0] / sums[1] * scale


def count(run, name: str):
    """Increments of the counter `n.<name>` over the window."""
    sums = _sums(run, (f"n.{name}",))
    return None if sums is None else sums[0]
