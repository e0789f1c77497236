"""From a profiler trace (.xplane.pb) to device numbers.

The reduction is kept here so that every PR computes the same number the
same way. Only a device's own plane counts: a trace with no such plane
(any CPU run) reduces to nothing.

Per device the trace has one plane, and in it a line of XLA operations
and a line of XLA modules (one event per execution of a compiled
program): events with a start and a duration on the device's clock,
brought onto the host's timeline by the profiler (to a millisecond or
two, as measured on the v5e). Operations nest (a loop's event spans the
events of its body), so busy time is the union of the intervals, and an
operation's own time is its duration less what its children cover.
"""
from __future__ import annotations

import bisect
import glob
import os
import re

DEVICE_PLANE = re.compile(r"^/device:TPU:(\d+)$")
OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"
COLLECTIVE = re.compile(
    r"all-gather|all-reduce|all-to-all|collective-permute|reduce-scatter"
    r"|collective-broadcast",
    re.IGNORECASE,
)
ANCHOR = "benchmark_anchor"


def find_xplane(trace_dir: str) -> str:
    files = sorted(glob.glob(
        os.path.join(trace_dir, "plugins", "profile", "*", "*.xplane.pb")
    ))
    if not files:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    return files[-1]


def load(path: str):
    from jax.profiler import ProfileData

    return ProfileData.from_file(path)


def device_events(profile, line_name: str = OPS_LINE) -> dict:
    """device index -> [(start_ns, end_ns, name)] sorted by start, from
    the named line of each device plane."""
    out = {}
    for plane in profile.planes:
        m = DEVICE_PLANE.match(plane.name)
        if not m:
            continue
        events = []
        for line in plane.lines:
            if line.name != line_name:
                continue
            for e in line.events:
                if e.duration_ns > 0:
                    events.append(
                        (e.start_ns, e.start_ns + e.duration_ns, e.name)
                    )
        events.sort(key=lambda ev: (ev[0], -ev[1]))
        out[int(m.group(1))] = events
    return out


def main_program(modules, t0: float, t1: float):
    """The executions, whole inside [t0, t1), of the program that took
    most device time there: (name, [(start, end)]). In a window of
    steady traffic that program is the engine's step."""
    inside = [(s, e, n) for s, e, n in modules if s >= t0 and e <= t1]
    totals: dict = {}
    for s, e, n in inside:
        totals[n] = totals.get(n, 0.0) + (e - s)
    if not totals:
        return None, []
    name = max(totals, key=totals.get)
    return name, [(s, e) for s, e, n in inside if n == name]


def clip(events, t0: float, t1: float) -> list:
    return [
        (max(s, t0), min(e, t1), n) for s, e, n in events if e > t0 and s < t1
    ]


def busy_intervals(events) -> list:
    """Union of the events' intervals, as disjoint sorted (start, end)."""
    merged = []
    for s, e, _n in events:
        if merged and s <= merged[-1][1]:
            if e > merged[-1][1]:
                merged[-1][1] = e
        else:
            merged.append([s, e])
    return [(s, e) for s, e in merged]


def busy_ns(events) -> float:
    return sum(e - s for s, e in busy_intervals(events))


def self_times(events) -> dict:
    """name -> nanoseconds of the operation's own time (children taken
    out), over events that nest properly on one line."""
    totals: dict = {}
    stack: list = []  # [end, name, own]

    def close(upto):
        while stack and stack[-1][0] <= upto:
            end, name, own = stack.pop()
            totals[name] = totals.get(name, 0.0) + own
    for s, e, name in events:
        close(s)
        if stack:
            stack[-1][2] -= min(e, stack[-1][0]) - s
        stack.append([e, name, e - s])
    close(float("inf"))
    return totals


def short_name(name: str) -> str:
    """'%fusion.5 = s32[8]{0} fusion(...), kind=...' -> 'fusion.5 s32[8]{0}':
    the name XLA prints and the result's shape, without the operands."""
    op, _, rest = name.partition(" = ")
    shape = "" if rest.startswith("(") else rest.split(" ", 1)[0][:48]
    return (op.lstrip("%") + " " + shape).strip()


def top_ops(events, n: int = 10) -> list:
    ranked = sorted(self_times(events).items(), key=lambda kv: -kv[1])
    return [[short_name(name), ns / 1e9] for name, ns in ranked[:n]]


def collective_ns(events) -> float:
    """Time under collective operations (union: an -start/-done pair or a
    fused body may overlap)."""
    return busy_ns([ev for ev in events if COLLECTIVE.search(ev[2])])


def gaps(events, t0: float, t1: float) -> list:
    """The idle intervals of [t0, t1): (start, end), longest first."""
    out = []
    at = t0
    for s, e in busy_intervals(clip(events, t0, t1)):
        if s > at:
            out.append((at, s))
        at = max(at, e)
    if t1 > at:
        out.append((at, t1))
    return sorted(out, key=lambda g: g[0] - g[1])


def anchor_ns(profile):
    """Start (trace ns) of the harness's anchor annotation on the host
    plane: the instant the harness also read its own clock."""
    for plane in profile.planes:
        if not plane.name.startswith("/host:"):
            continue
        for line in plane.lines:
            for e in line.events:
                if e.name == ANCHOR:
                    return e.start_ns
    return None


def attribute_gaps(idle, spans, n: int = 10) -> list:
    """Idle seconds by what the host was doing: each idle interval is
    shared out over the host spans (start, end, name) that overlap it,
    and what no span covers goes to `host`. Spans must be sorted by
    start and disjoint (one engine loop thread)."""
    totals: dict = {}
    starts = [s for s, _e, _n in spans]
    for g0, g1 in idle:
        covered = 0.0
        i = max(0, bisect.bisect_right(starts, g0) - 1)
        while i < len(spans) and spans[i][0] < g1:
            s, e, name = spans[i]
            ov = min(e, g1) - max(s, g0)
            if ov > 0:
                totals[name] = totals.get(name, 0.0) + ov
                covered += ov
            i += 1
        rest = (g1 - g0) - covered
        if rest > 0:
            totals["host"] = totals.get("host", 0.0) + rest
    ranked = sorted(totals.items(), key=lambda kv: -kv[1])
    return [[name, ns / 1e9] for name, ns in ranked[:n]]
