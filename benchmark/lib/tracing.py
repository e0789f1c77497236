"""The traced part of a --trace 1 run: the device profiler over a short
stretch of the window, on a thread of its own so that the load generator
never waits for the profiler to start or to write its file.

The host tracer stays at the level that records only annotations and the
Python tracer is off: the trace is of the device. One annotation is
written at a known instant of the harness's clock, which puts the trace
and the engine's flight-recorder phase spans on one timeline.
"""
from __future__ import annotations

import shutil
import threading
import time

import jax

from benchmark.lib import trace_reduce
from dragonboat_tpu.trace import flight_recorder

TRACE_S = 10.0  # traced stretch; shorter if the window is
TRACE_LAUNCHES = 40  # or until this many launches: a trace of a fast loop
#                      grows by thousands of events a launch
LEAD_S = 1.0  # into the window before tracing starts


class WindowTrace:
    def __init__(self, cluster, trace_dir: str, seconds: float) -> None:
        self._cluster = cluster
        self._dir = trace_dir
        self._span = min(TRACE_S, max(0.5, seconds - 2 * LEAD_S))
        self._thread = None
        self.error = None
        self.t_start = self.t_stop = self.t_anchor = 0.0
        self.steps_start = self.steps_stop = 0
        self._spans: list = []  # (start, end, phase) on the harness's clock

    def start(self, t_open: float) -> None:
        shutil.rmtree(self._dir, ignore_errors=True)
        self._thread = threading.Thread(
            target=self._run, args=(t_open,), name="bench-trace", daemon=True
        )
        self._thread.start()

    def _run(self, t_open: float) -> None:
        try:
            time.sleep(max(0.0, t_open + LEAD_S - time.monotonic()))
            opts = jax.profiler.ProfileOptions()
            opts.python_tracer_level = 0
            opts.host_tracer_level = 1
            jax.profiler.start_trace(self._dir, profiler_options=opts)
            try:
                with jax.profiler.TraceAnnotation(trace_reduce.ANCHOR):
                    self.t_anchor = time.monotonic()
                steps = self._cluster.core.step_stats
                steps_per_sync = self._cluster.steps_per_sync
                self.steps_start = steps()["steps"]
                self.t_start = time.monotonic()
                enough = self.steps_start + TRACE_LAUNCHES * steps_per_sync
                while (time.monotonic() < self.t_start + self._span
                       and steps()["steps"] < enough):
                    time.sleep(0.2)
                    self._collect_spans()
                self.t_stop = time.monotonic()
                self.steps_stop = steps()["steps"]
            finally:
                jax.profiler.stop_trace()
        except Exception as e:  # reported by reduce(), on the main thread
            self.error = e

    def _collect_spans(self) -> None:
        """The flight recorder is a bounded ring that other events share,
        so the engine's phase spans are copied out while they are there."""
        last = self._spans[-1][1] if self._spans else self.t_start
        for ev in flight_recorder().dump(event="phase_span"):
            if ev.get("engine") == "vector" and ev["t"] > last:
                self._spans.append((ev["t"] - ev["dur"], ev["t"], ev["phase"]))

    def reduce(self, steps_per_sync: int):
        """Wait for the trace and reduce it. Returns (trace dict or None,
        breakdown or None): None where the trace holds no device plane,
        as on any backend without a device tracer."""
        self._thread.join()
        if self.error is not None:
            raise self.error
        profile = trace_reduce.load(trace_reduce.find_xplane(self._dir))
        anchor = trace_reduce.anchor_ns(profile)
        ops = trace_reduce.device_events(profile)
        modules = trace_reduce.device_events(profile, trace_reduce.MODULES_LINE)
        if not ops or anchor is None:
            return None, None
        # trace nanoseconds of an instant of the harness's clock
        to_ns = lambda t: anchor + (t - self.t_anchor) * 1e9  # noqa: E731
        t0, t1 = to_ns(self.t_start), to_ns(self.t_stop)
        per_chip = {d: trace_reduce.clip(ev, t0, t1) for d, ev in ops.items()}
        busy = [trace_reduce.busy_ns(ev) / 1e9 for ev in per_chip.values()]
        # the step program's whole executions inside the traced stretch:
        # their number is the launches, their device time the kernel's
        kernel, coll, launches, program = [], [], 0, None
        for d, ev in per_chip.items():
            program, runs = trace_reduce.main_program(modules[d], t0, t1)
            launches = len(runs)
            inside = [trace_reduce.clip(ev, s, e) for s, e in runs]
            kernel.append(sum(trace_reduce.busy_ns(x) for x in inside) / 1e9)
            coll.append(
                sum(trace_reduce.collective_ns(x) for x in inside) / 1e9
            )
        trace = {
            "window_s": self.t_stop - self.t_start,
            "busy_s": sum(busy) / len(busy),
            "busy_s_per_chip": busy,
            "program": program,
            "launches": launches,
            "launches_decoded":
                (self.steps_stop - self.steps_start) / steps_per_sync,
            "kernel_s": sum(kernel) / len(kernel),
            "collective_s": sum(coll) / len(coll) if len(busy) > 1 else None,
            "chips_traced": len(busy),
        }
        first = per_chip[min(per_chip)]
        spans = sorted((to_ns(s), to_ns(e), name) for s, e, name in self._spans)
        breakdown = {
            "device_ops": trace_reduce.top_ops(first, 10),
            "idle_gaps": trace_reduce.attribute_gaps(
                trace_reduce.gaps(first, t0, t1), _disjoint(spans), 10
            ),
        }
        return trace, breakdown


def _disjoint(spans: list) -> list:
    """The engine records `deliver` inside the phase that called it; keep
    the outer span and cut what overlaps the one before."""
    out = []
    for s, e, name in spans:
        if out and s < out[-1][1]:
            s = out[-1][1]
        if e > s:
            out.append((s, e, name))
    return out
