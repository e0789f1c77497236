"""Window deltas of the program's own cumulative counters.

Every source the program offers counts from engine start, so bring-up
and the cold compile are inside it. The harness reads each one when the
window opens and when it closes and keeps the difference.
"""
from __future__ import annotations

from dragonboat_tpu.profile import compile_watch, diff_compiles


def snapshot(cluster, now: float) -> dict:
    """Cheap (no device access, no sort): safe on the generator's thread."""
    core = cluster.core
    fsyncs = 0
    for nh in cluster.hosts.values():
        h = nh.metrics.histogram("fsync_latency_seconds", (0, 0))
        fsyncs += h.count if h is not None else 0
    return {
        "t": now,
        "protocol_steps": core.step_stats()["steps"],
        "elections_started": core.counter_stats()["elections_started"],
        "compiles": compile_watch().snapshot(),
        # stage -> (samples, seconds); total seconds = seconds * ratio
        "phases": {
            name: (len(s), s.mean() * len(s))
            for name, s in list(core.profiler.samples.items())
        },
        "phase_ratio": core.profiler.ratio,
        "fsyncs": fsyncs,
    }


def delta(a: dict, b: dict, steps_per_sync: int) -> dict:
    """What happened between two snapshots. `launches` is iterations of
    the engine loop that ran the kernel: the program counts protocol
    steps, `steps_per_sync` to a launch."""
    phases = {}
    for name, (n1, s1) in b["phases"].items():
        n0, s0 = a["phases"].get(name, (0, 0.0))
        if n1 > n0:
            phases[name] = (s1 - s0) * b["phase_ratio"]
    steps = b["protocol_steps"] - a["protocol_steps"]
    return {
        "seconds": b["t"] - a["t"],
        "protocol_steps": steps,
        "launches": steps / steps_per_sync,
        "elections_started": b["elections_started"] - a["elections_started"],
        "compiles": diff_compiles(a["compiles"], b["compiles"]),
        "phases": phases,
        "phase_ratio": b["phase_ratio"],
        "fsyncs": b["fsyncs"] - a["fsyncs"],
    }
